"""Ahead-of-time compiles for a described TPU v5e (no chip attached).

The TPU compiler refuses here what the Pallas interpreter accepts: tiles
that are not aligned, more VMEM than a kernel may use, a program that
does not fit HBM.  These tests compile the kernels of the main path at
real widths, the one-chip serve decode tick, and the acis-100m
gradient-sync program on a four-chip mesh, for a ``v5e:2x2`` topology.
Nothing runs, so they prove compilation only; ``chip_smoke.py`` runs the
same paths on the chip.

The topology is described inside a module fixture — never at import —
so every test worker collects the same tests and only the one running
this file loads the TPU compiler.
"""

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.core.api import CollectiveConfig, CollectiveEngine
from repro.kernels import chunk_scan as cs
from repro.kernels import fused_combine as fc
from repro.kernels import pack_combine as pc
from repro.kernels import quant_combine as qc
from repro.kernels import rwkv6_recurrence as rw
from repro.kernels import topk_accum as ta
from repro.models import Model

MIB = 1 << 20


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


# arena bytes, dtype, leaf sizes: the default 1 MiB Coalesce bucket and
# the tuner's largest (16 MiB), ragged leaves at ragged offsets
PACKS = [
    (MIB, jnp.float32, (768, 9216, 9216, 196608, 46000)),
    (MIB, jnp.bfloat16, (768, 17, 100_001, 2048, 129)),
    (16 * MIB, jnp.float32, (1_000_003, 2_000_000, 1_190_000)),
]


@pytest.mark.parametrize("op", [None, "add"])
@pytest.mark.parametrize("nbytes,dtype,sizes", PACKS,
                         ids=["1MiB-f32", "1MiB-bf16", "16MiB-f32"])
def test_fused_pack_compiles(one_chip, nbytes, dtype, sizes, op):
    n = nbytes // jnp.dtype(dtype).itemsize
    text = _compile_text(
        lambda a, *p: pc.fused_pack(a, *p, op=op, interpret=False),
        _sds((n,), dtype, one_chip),
        *[_sds((s,), dtype, one_chip) for s in sizes])
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("op", ["add", "mac"])
def test_fused_combine_compiles_ragged_bf16(one_chip, op):
    x = _sds((3_000_001,), jnp.bfloat16, one_chip)
    text = _compile_text(
        lambda a, b: fc.fused_combine(a, b, op=op, alpha=0.5,
                                      interpret=False), x, x)
    assert "tpu_custom_call" in text


def test_quant_combine_compiles(one_chip):
    q = _sds((4096, 256), jnp.int8, one_chip)
    s = _sds((4096,), jnp.float32, one_chip)
    text = _compile_text(
        lambda *a: qc.quant_combine(*a, interpret=False), q, s, q, s)
    assert "tpu_custom_call" in text


def test_topk_accumulate_compiles(one_chip):
    n, k = 1 << 18, (1 << 18) // 100
    text = _compile_text(
        lambda d, i, v: ta.topk_accumulate(d, i, v, interpret=False),
        _sds((n,), jnp.float32, one_chip), _sds((k,), jnp.int32, one_chip),
        _sds((k,), jnp.float32, one_chip))
    assert "tpu_custom_call" in text


def test_scans_compile_at_model_width(one_chip):
    """recurrentgemma-9b's 4096-wide RG-LRU and rwkv6-1.6b's 32 heads of
    64: a single feature block of 4096 overflowed VMEM, and per-token
    dynamic value slices did not lower."""
    x = _sds((2048, 4096), jnp.float32, one_chip)
    assert "tpu_custom_call" in _compile_text(
        lambda a, b: cs.rglru_scan(a, b, interpret=False), x, x)
    assert "tpu_custom_call" in _compile_text(
        lambda a: cs.prefix_sum(a, interpret=False),
        _sds((16384, 128), jnp.float32, one_chip))
    r = _sds((32, 512, 64), jnp.bfloat16, one_chip)
    u = _sds((32, 64), jnp.bfloat16, one_chip)
    assert "tpu_custom_call" in _compile_text(
        lambda *a: rw.rwkv6_recurrence(*a, interpret=False), r, r, r, r, u)


def test_decode_tick_reads_the_stacked_cache_from_on_chip_memory(one_chip):
    """The donated decode tick at qwen3-8b's widths on one chip, 64 slots
    of 1536 positions (201 MB of K and as much of V a layer): each
    layer's K and V are cut out of the stacked cache in pieces placed in
    on-chip memory (``S(1)``) and read there, and the program keeps no
    HBM scratch the size of a layer's slab, let alone of the stack."""
    cfg = dataclasses.replace(configs.get("qwen3-8b"), n_layers=2)
    model = Model(cfg)
    slots, seq = 64, 1536

    def shaped(tree):
        return jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), tree)

    def decode_tick(p, tok, c, idx):
        return model.decode_step(p, tok, c, idx)

    i32 = _sds((slots,), jnp.int32, one_chip)
    cache = shaped(jax.eval_shape(lambda: model.init_cache(slots, seq)))
    compiled = jax.jit(decode_tick, donate_argnums=(2,)).lower(
        shaped(model.param_shapes()), i32, cache, i32).compile()
    text = compiled.as_text()
    slab = slots * seq * cfg.n_kv_heads * cfg.head_dim * 2
    pieces = [line.split(" = ", 1)[1].split(" ", 1)[0]
              for line in text.splitlines()
              if " dynamic-slice(" in line and f",{seq},8,128]" in line]
    assert len(pieces) == 2, pieces           # K and V of the loop body
    assert all("S(1)" in t for t in pieces), pieces
    assert compiled.memory_analysis().temp_size_in_bytes < slab // 4


def test_gradient_sync_program_compiles_on_four_chips(topo, monkeypatch):
    """The acis-100m gradient sync through ``engine.gradient_sync`` with
    kernels and persistent arenas, on a 4-chip ``data`` mesh: the pack
    kernel and the ring's collective-permutes are in the program."""
    # kernels compile for the TPU, as they do on the chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.asarray(topo.devices), ("data",))
    assert mesh.devices.size == 4
    rep = NamedSharding(mesh, P())
    engine = CollectiveEngine(
        CollectiveConfig(backend="acis_compressed", use_kernels=True),
        inner_axis="data")
    grads = Model(configs.get("acis-100m")).param_shapes()
    arenas = engine.init_arenas(grads, axis_sizes={"data": 4})
    assert arenas, "acis-100m sync has no bucket arena"
    residual = jax.eval_shape(engine.init_state, grads)

    def sync(g, r, a):
        return engine.gradient_sync(g, r, arenas=a)

    def shaped(tree):
        return jax.tree.map(lambda x: _sds(x.shape, x.dtype, rep), tree)

    fn = jax.shard_map(sync, mesh=mesh, in_specs=P(), out_specs=P(),
                       check_vma=False)
    text = jax.jit(fn).lower(shaped(grads), shaped(residual),
                             shaped(arenas)).compile().as_text()
    assert "tpu_custom_call" in text
    assert "collective-permute" in text
