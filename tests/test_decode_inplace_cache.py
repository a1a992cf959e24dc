"""The decode step updates the stacked layer cache in place.

``decode_step`` carries each period position's stacked cache
[P, B, S, ...] through the layer scan and writes a token's rows at
[layer, row, index].  These tests hold it to the formulation it replaced,
where the stack rode the scan as ``xs``/``ys`` (kept below as the
reference), bit for bit; check that a tick writes only its own
(layer, row, position) entries; and check in the compiled program that
the donated stack enters and leaves the layer loop with no copy of it.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import Model
from repro.models import attention as A
from repro.models import decode as D
from repro.models import layers as L
from repro.models.transformer import _norm, _period_of, logits

B, S, TICKS = 3, 24, 3

# one arch-smoke config per cache kind
KINDS = {
    "gqa": "granite-3-8b",
    "moe-mla-rem": "deepseek-v2-236b",
    "moe-gqa": "qwen2-moe-a2.7b",
    "window-lru": "recurrentgemma-9b",
    "rwkv": "rwkv6-1.6b",
    "encdec": "whisper-small",
    "cross-self": "llama-3.2-vision-11b",
}


def reference_decode_step(params, cfg, token, cache, index, *, context=None):
    """The xs/ys formulation: the stacked cache is scanned with the
    parameters, each layer's slab is sliced out, updated and restacked."""
    x = L.embed_lookup(params["embed"], token[:, None])
    if cfg.family == "encdec":
        idx = jnp.asarray(index)
        if idx.ndim > 0:
            pos = jnp.take(params["dec_pos"], idx, axis=0)[:, None, :]
        else:
            pos = jax.lax.dynamic_slice_in_dim(
                params["dec_pos"], idx, 1, 0)[None]
        x = x + pos.astype(x.dtype)
    period, _, rem = _period_of(cfg)
    prefix_rem = cfg.family == "moe" and bool(rem)

    def run_rem(x, cache_rem):
        new = {}
        for name in sorted(cache_rem):
            kind = name.split("_", 1)[1]
            x, new[name] = D.block_decode(params["rem"][name], x,
                                          cache_rem[name], index, cfg, kind,
                                          context=context)
        return x, new

    new_cache = {"layers": None, "rem": cache["rem"]}
    if prefix_rem:
        x, new_cache["rem"] = run_rem(x, cache["rem"])

    def period_body(x, pc):
        pp, cc = pc
        new_cc = {}
        for j, kind in enumerate(period):
            name = f"pos{j}_{kind}"
            x, new_cc[name] = D.block_decode(pp[name], x, cc[name], index,
                                             cfg, kind, context=context)
        return x, new_cc

    x, new_cache["layers"] = jax.lax.scan(
        period_body, x, (params["layers"], cache["layers"]))
    if not prefix_rem:
        x, new_cache["rem"] = run_rem(x, cache["rem"])
    x = _norm(params["final_norm"], x, cfg)
    return logits(params, cfg, x)[:, 0, :], new_cache


def _setup(arch, seed=0):
    """Model, parameters, a cache filled with noise (window positions
    left at their own -1 fill) and the context input, if any."""
    cfg = configs.get_smoke(arch)
    model = Model(cfg)
    params = model.init(jax.random.key(seed))
    rng = np.random.default_rng(seed)

    def noise(a):
        if a.dtype == jnp.int32:
            return a
        return jnp.asarray(rng.standard_normal(a.shape), a.dtype)

    cache = jax.tree.map(noise, model.init_cache(B, S))
    spec = model.context_inputs(B)
    ctx = None if spec is None else jnp.asarray(
        rng.standard_normal(spec.shape), spec.dtype)
    return cfg, model, params, cache, ctx, rng


def _assert_trees_equal(got, want):
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        assert g.shape == w.shape and g.dtype == w.dtype, path
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("index_kind", ["scalar", "rows"])
@pytest.mark.parametrize("kind", [*KINDS, "gqa-row-groups"])
def test_decode_step_matches_xs_ys_reference(kind, index_kind, monkeypatch):
    """Bit-equal logits and caches over chained ticks, for a lockstep
    scalar position and for distinct per-row positions."""
    cfg, model, params, cache, ctx, rng = _setup(
        KINDS.get(kind, KINDS["gqa"]))
    budget = A.READ_BYTES
    if kind == "gqa-row-groups":
        # a read budget of one cache row: attention reads each layer in
        # B groups of one row, cut out of the stack one at a time (the
        # reference, traced without it, reads each layer whole)
        budget = S * cfg.n_kv_heads * cfg.head_dim * 2

    def decode(p, t, c, i, x):
        with monkeypatch.context() as m:
            m.setattr(A, "READ_BYTES", budget)
            return D.decode_step(p, cfg, t, c, i, context=x)

    new = jax.jit(decode)
    ref = jax.jit(lambda p, t, c, i, x: reference_decode_step(
        p, cfg, t, c, i, context=x))
    start = np.array([5, 0, 11], np.int32)
    got, want = cache, cache
    for tick in range(TICKS):
        tok = jnp.asarray(rng.integers(0, cfg.vocab, B), jnp.int32)
        idx = (jnp.int32(7 + tick) if index_kind == "scalar"
               else jnp.asarray(start + tick))
        lg, got = new(params, tok, got, idx, ctx)
        lg_ref, want = ref(params, tok, want, idx, ctx)
        np.testing.assert_array_equal(np.asarray(lg), np.asarray(lg_ref))
        _assert_trees_equal(got, want)


# leaves written at [layer, row, position] (window rings at position mod W)
KV_LEAVES = ("k", "v", "pos", "c_kv", "k_rope")


@pytest.mark.parametrize("kind", ["gqa", "moe-mla-rem", "window-lru",
                                  "encdec"])
def test_tick_writes_only_its_layer_row_position(kind):
    """One tick with distinct per-row positions changes, in every stacked
    K/V leaf, exactly the entries [l, row, idx[row]] of every layer l."""
    cfg, model, params, cache, ctx, rng = _setup(KINDS[kind], seed=1)
    idx = np.array([2, 9, 17], np.int32)
    tok = jnp.asarray(rng.integers(0, cfg.vocab, B), jnp.int32)
    _, out = jax.jit(lambda p, t, c, i, x: D.decode_step(
        p, cfg, t, c, i, context=x))(params, tok, cache, jnp.asarray(idx),
                                     ctx)
    checked = 0
    for name, leaves in cache["layers"].items():
        for leaf in KV_LEAVES:
            if leaf not in leaves:
                continue
            before = np.asarray(leaves[leaf])
            after = np.asarray(out["layers"][name][leaf])
            n_layers, _, width = before.shape[:3]
            changed = before != after
            if changed.ndim > 3:
                changed = changed.reshape(changed.shape[:3] + (-1,)).any(-1)
            want = np.zeros_like(changed)
            want[:, np.arange(B), idx % width] = True
            assert n_layers >= 1
            np.testing.assert_array_equal(changed, want,
                                          err_msg=f"{name}/{leaf}")
            checked += 1
    assert checked >= 2


_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%(\S+) = (\(.*?\)|\S+) ([\w-]+)\(")


def test_compiled_tick_keeps_the_donated_stack_in_place():
    """The jitted, donating decode tick of a dense model: every stacked
    cache leaf is aliased from input to output, and outside the layer
    loop no instruction makes a value of a stacked leaf's full shape
    (no broadcast or copy of the stack), only the loop and its tuples."""
    cfg = configs.get_smoke("granite-3-8b")
    model = Model(cfg)
    cache = jax.eval_shape(lambda: model.init_cache(4, 64))

    def decode_tick(p, tok, c, idx):
        return model.decode_step(p, tok, c, idx)

    i32 = jax.ShapeDtypeStruct((4,), jnp.int32)
    text = jax.jit(decode_tick, donate_argnums=(2,)).lower(
        model.param_shapes(), i32, cache, i32).compile().as_text()

    stacked = jax.tree.leaves(cache["layers"])
    assert len(stacked) == 2 and stacked[0].ndim == 5
    hlo_type = {"bfloat16": "bf16", "float32": "f32", "int32": "s32"}
    shapes = {f"{hlo_type[jnp.dtype(a.dtype).name]}"
              f"[{','.join(map(str, a.shape))}]" for a in stacked}

    entry = text[text.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    params = {}
    for line in entry.splitlines():
        m = re.search(r"parameter\((\d+)\).*op_name=\"c\[\\?'layers", line)
        if m:
            params[int(m.group(1))] = line
    assert len(params) == len(stacked)
    header = text.splitlines()[0]
    aliased = {int(p)
               for p in re.findall(r"\{\d*\}: \((\d+), \{\}", header)}
    assert set(params) <= aliased, (sorted(params), header[:300])

    allowed = {"parameter", "get-tuple-element", "tuple", "while"}
    for line in entry.splitlines():
        m = _INSTR.match(line)
        if m is None:
            continue
        _, typ, op = m.groups()
        if any(s in typ for s in shapes):
            assert op in allowed, line.strip()[:200]
