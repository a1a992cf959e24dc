"""Pallas bulk data path: kernel/registry parity and compiled equivalence.

Four layers of guarantees:

1. every ``switchops`` registry op carrying a Pallas kernel matches its
   ``kernels/ref.py`` oracle in interpret mode across dtypes (f32 / bf16 /
   int8 where the op admits it) and ragged sizes;
2. a program compiled with ``use_kernels=True`` is numerically equal to
   the default lowering on all four acis backends, error-feedback
   residual state included, arenas included;
3. the Coalesce ``batch_rings`` rewrite is **bit-compatible** with
   per-program ring launches (bandwidth and latency schedules both), and
   RS/AG buckets are bit-compatible with their per-leaf collectives;
4. the cost model covers the new ``batched_allreduce`` stage kind (the
   analytic time stays simulator-checkable) and the amortization helpers
   are sane.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import core as acis
from repro.core import make_engine, netmodel, switchops, tracing
from repro.core.types import ADD, MAX, MIN
from repro.core import ring as ring_mod
from repro.kernels import ops as kops, ref as kref

AV = jax.ShapeDtypeStruct
N = 8

ACIS_BACKENDS = ["acis", "acis_compressed", "acis_hierarchical",
                 "acis_hierarchical_compressed"]

# sizes chosen to exercise the kernels' lane padding: primes, non-128
# multiples, and one aligned size
RAGGED = [7, 129, 1000, 2048]


@pytest.fixture(scope="module")
def mesh22():
    return jax.make_mesh((2, 2), ("pod", "data"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def smap(fn, mesh, in_specs, out_specs):
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))


def _tol(dtype, name=None):
    if dtype == jnp.bfloat16:
        return dict(rtol=2e-2, atol=2e-2)
    if name == "prefix_sum":
        # the kernel's blocked scan associates differently from the
        # oracle's cumsum — long prefixes accumulate ~1 ulp per block
        return dict(rtol=1e-3, atol=1e-5)
    return dict(rtol=1e-5, atol=1e-6)


def _data(rng, size, dtype):
    if dtype == jnp.int8:
        return jnp.asarray(rng.integers(-40, 40, size=(size,)), jnp.int8)
    return jnp.asarray(rng.standard_normal((size,)), dtype)


# ---------------------------------------------------------------------------
# 1. registry parity: every kernel-carrying op vs its oracle
# ---------------------------------------------------------------------------

def _combine_args(rng, size, dtype):
    return (_data(rng, size, dtype), _data(rng, size, dtype)), {}


def _mac_args(rng, size, dtype):
    return (_data(rng, size, dtype), _data(rng, size, dtype)), \
        {"alpha": 0.5}


def _prefix_args(rng, size, dtype):
    return (_data(rng, size, dtype),), {}


def _topk_args(rng, size, dtype):
    k = max(size // 4, 1)
    dense = _data(rng, size, dtype)
    idx = jnp.asarray(rng.integers(0, size, size=(k,)), jnp.int32)
    vals = _data(rng, k, dtype)
    return (dense, idx, vals), {}


def _pack_args(rng, size, dtype):
    arena = _data(rng, size, dtype)
    cuts = sorted(set(rng.integers(1, size, size=2).tolist()))
    parts, lo = [], 0
    for hi in cuts + [size]:
        if hi > lo:
            parts.append(_data(rng, hi - lo, dtype))
            lo = hi
    return (arena, *parts), {"op": "add"}


# name → (arg factory, dtypes the op admits)
_REGISTRY_CASES = {
    "add": (_combine_args, (jnp.float32, jnp.bfloat16, jnp.int8)),
    "max": (_combine_args, (jnp.float32, jnp.bfloat16, jnp.int8)),
    "min": (_combine_args, (jnp.float32, jnp.bfloat16, jnp.int8)),
    "mac": (_mac_args, (jnp.float32, jnp.bfloat16)),
    "prefix_sum": (_prefix_args, (jnp.float32,)),
    "topk_accumulate": (_topk_args, (jnp.float32,)),
    "pack_combine": (_pack_args, (jnp.float32, jnp.bfloat16, jnp.int8)),
}


def test_every_registry_kernel_has_a_parity_case():
    """If load_kernels() grows an op, this file must grow its sweep."""
    switchops.load_kernels()
    with_kernel = {n for n in switchops.names()
                   if switchops.get(n).kernel is not None}
    assert with_kernel <= set(_REGISTRY_CASES), \
        f"untested kernels: {with_kernel - set(_REGISTRY_CASES)}"


@pytest.mark.parametrize("size", RAGGED)
@pytest.mark.parametrize("name", sorted(_REGISTRY_CASES))
def test_registry_kernel_matches_ref(rng, name, size):
    switchops.load_kernels()
    op = switchops.get(name)
    assert op.kernel is not None
    factory, dtypes = _REGISTRY_CASES[name]
    for dtype in dtypes:
        args, kw = factory(rng, size, dtype)
        got = op(*args, use_kernel=True, **kw)
        want = op(*args, use_kernel=False, **kw)
        got = jax.tree.leaves(got)
        want = jax.tree.leaves(want)
        for g, w in zip(got, want):
            assert g.shape == w.shape and g.dtype == w.dtype
            np.testing.assert_allclose(
                np.asarray(g, np.float32), np.asarray(w, np.float32),
                **_tol(dtype, name))


@pytest.mark.parametrize("op", [None, "add", "max", "min"])
def test_fused_pack_combine_vs_oracle(rng, op):
    """The fused pack+combine kernel directly vs the ref oracle, with a
    cross-dtype part (f32 leaf into a bf16 arena) and arena tail lanes
    that must survive the aliased write."""
    from repro.kernels import pack_combine as pc

    arena = jnp.asarray(rng.standard_normal((64,)), jnp.bfloat16)
    parts = [jnp.asarray(rng.standard_normal((s,)), jnp.float32)
             for s in (17, 5, 30)]
    got = pc.fused_pack(arena, *[p.astype(arena.dtype) for p in parts],
                        op=op, interpret=True)
    want = kref.pack_combine(arena, *parts, op=op)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)
    # lanes past sum(parts)=52 carry the original arena contents
    np.testing.assert_array_equal(np.asarray(got[52:], np.float32),
                                  np.asarray(arena[52:], np.float32))


@pytest.mark.parametrize("op", [None, "add", "max"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8])
def test_fused_pack_multiblock_bitwise(rng, dtype, op):
    """Leaves spanning, straddling and sitting inside the kernel's arena
    blocks at ragged offsets, with a ragged arena tail: bit-identical to
    the oracle, the tail untouched."""
    from repro.kernels import pack_combine as pc

    sizes = (5, pc.BLOCK + 3, 70, pc.BLOCK - 1, 2 * pc.BLOCK + 11)
    arena = _data(rng, sum(sizes) + 999, dtype)
    parts = [_data(rng, s, dtype) for s in sizes]
    got = pc.fused_pack(arena, *parts, op=op, interpret=True)
    want = kref.pack_combine(arena, *parts, op=op)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_fused_pack_overflow_rejected():
    from repro.kernels import pack_combine as pc

    with pytest.raises(ValueError, match="overflows"):
        pc.fused_pack(jnp.zeros((8,)), jnp.ones((9,)), interpret=True)


# ---------------------------------------------------------------------------
# satellite: _interpret_default follows the backend, re-checked per call
# ---------------------------------------------------------------------------

def test_interpret_default_env_override(monkeypatch):
    """Only the CPU backend interprets; the retired
    ``$ACIS_KERNEL_INTERPRET`` no longer forces the interpreter."""
    monkeypatch.setenv("ACIS_KERNEL_INTERPRET", "1")
    for backend, interpret in (("cpu", True), ("tpu", False),
                               ("gpu", False)):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert kops._interpret_default() is interpret


def test_interpret_default_not_cached(monkeypatch):
    """The old functools.cache pinned the first answer for the process
    lifetime; a backend change must take effect on the *next* call, and
    a kernel called without ``interpret`` follows it."""
    from repro.kernels import pack_combine as pc

    first = kops._interpret_default()
    assert first is True                      # tier-1 runs on CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert kops._interpret_default() is False
    monkeypatch.undo()
    assert kops._interpret_default() == first
    got = pc.fused_pack(jnp.zeros((5,)), jnp.ones((3,)))
    np.testing.assert_array_equal(np.asarray(got), [1, 1, 1, 0, 0])


# ---------------------------------------------------------------------------
# satellite: monoid-identity padding (non-add reductions over ragged sizes)
# ---------------------------------------------------------------------------

def test_pad_to_multiple_uses_monoid_identity():
    x = jnp.asarray([3.0, -7.0, 5.0])
    padded, size = ring_mod.pad_to_multiple(x, 4, monoid=MIN)
    assert size == 3
    assert float(padded[3]) == float(jnp.finfo(jnp.float32).max)
    padded, _ = ring_mod.pad_to_multiple(x, 4, monoid=MAX)
    assert float(padded[3]) == float(jnp.finfo(jnp.float32).min)
    padded, _ = ring_mod.pad_to_multiple(x, 4, monoid=ADD)
    assert float(padded[3]) == 0.0


@pytest.mark.parametrize("monoid", [MAX, MIN], ids=["max", "min"])
def test_ragged_nonadd_reduce_bitwise_correct(mesh8, rng, monoid):
    """A bandwidth-ring MAX/MIN over a size the ring must pad: literal-0
    padding would corrupt all-negative (resp. all-positive) data."""
    sign = -1.0 if monoid.name == "max" else 1.0
    x = sign * np.abs(rng.standard_normal((N, 13))).astype(np.float32) - 1.0
    eng = make_engine("acis", latency_optimal_below=0)  # force bandwidth
    c = eng.compile(lambda v: acis.reduce(v, monoid, axis="data"),
                    in_avals=(AV((13,), jnp.float32),), axis_size=N)
    out = smap(lambda v: c(v[0])[0][None], mesh8, P("data", None),
               P("data", None))(jnp.asarray(x))
    want = x.max(0) if monoid.name == "max" else x.min(0)
    np.testing.assert_array_equal(np.asarray(out)[0], want)


# ---------------------------------------------------------------------------
# 2. compiled programs: use_kernels=True == default path, all backends
# ---------------------------------------------------------------------------

def _run_sync(eng, mesh22, grads, keys, shapes):
    n_leaves = len(keys)

    def f(*ls):
        g = {k: l[0, 0] for k, l in zip(keys, ls)}
        state = eng.init_state(g)
        synced, new_state = eng.gradient_sync(g, state)
        outs = [synced[k][None, None] for k in keys]
        if state is not None:
            outs += [new_state[k][None, None] for k in keys]
        return tuple(outs)

    spec = P("pod", "data", None, None)
    n_out = n_leaves * (2 if eng.needs_residual() else 1)
    args = [jnp.asarray(grads[k].reshape((2, 2) + s))
            for k, s in zip(keys, shapes)]
    outs = smap(f, mesh22, (spec,) * n_leaves, (spec,) * n_out)(*args)
    return [np.asarray(o)[0, 0] for o in outs]


@pytest.mark.parametrize("backend", ACIS_BACKENDS)
def test_use_kernels_matches_default_path(mesh22, rng, backend):
    shapes = [(4, 3 + 7 * i) for i in range(5)]
    grads = {f"l{i}": rng.standard_normal((4,) + s).astype(np.float32)
             for i, s in enumerate(shapes)}
    keys = sorted(grads)
    hier = dict(inner_axis="data", outer_axis="pod")
    with_k = _run_sync(make_engine(backend, use_kernels=True, **hier),
                       mesh22, grads, keys, shapes)
    without = _run_sync(make_engine(backend, use_kernels=False, **hier),
                        mesh22, grads, keys, shapes)
    for i, (a, b) in enumerate(zip(with_k, without)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7,
                                   err_msg=f"output {i}")


def test_use_kernels_arena_path(mesh8, rng):
    """The fused arena-aliased pack (one Pallas launch instead of N
    dynamic_update_slice calls) under real arenas."""
    sizes = [97, 260, 31]
    grads = {f"l{i}": rng.standard_normal((N, s)).astype(np.float32)
             for i, s in enumerate(sizes)}
    keys = sorted(grads)
    outs = {}
    for uk in (False, True):
        eng = make_engine("acis", use_kernels=uk)

        def f(*ls):
            g = dict(zip(keys, [l[0] for l in ls]))
            ar = eng.init_arenas(g)
            synced, _, _ = eng.gradient_sync(g, None, arenas=ar)
            return tuple(synced[k][None] for k in keys)

        spec = P("data", None)
        outs[uk] = smap(f, mesh8, (spec,) * 3, (spec,) * 3)(
            *[jnp.asarray(grads[k]) for k in keys])
    for k, a, b in zip(keys, outs[True], outs[False]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7, err_msg=k)


def test_use_kernels_in_cache_key():
    a = make_engine("acis", use_kernels=True).config.cache_key()
    b = make_engine("acis", use_kernels=False).config.cache_key()
    c = make_engine("acis", batch_rings=True).config.cache_key()
    d = make_engine("acis").config.cache_key()
    assert a != b and c != d


# ---------------------------------------------------------------------------
# 3a. batched same-axis rings: bit-compatible, stage collapse
# ---------------------------------------------------------------------------

def _batch_prog(monoid):
    def prog(a, b, c):
        return (acis.reduce(a, monoid, axis="data"),
                acis.reduce(b, monoid, axis="data"),
                acis.reduce(c, monoid, axis="data"))
    return prog


@pytest.mark.parametrize("latency_below", [0, 1 << 30],
                         ids=["bandwidth", "latency"])
@pytest.mark.parametrize("monoid", [ADD, MAX], ids=["add", "max"])
def test_batched_ring_bitwise(mesh8, rng, monoid, latency_below):
    """k same-axis rings merged into one launch return bit-identical
    results under both ring schedules (chunk-aligned interleave: every
    lane keeps its fold order)."""
    avals = (AV((45,), jnp.float32), AV((16,), jnp.float32),
             AV((131,), jnp.float32))
    xs = [rng.standard_normal((N,) + a.shape).astype(np.float32) * 0.7
          for a in avals]
    outs = {}
    for br in (False, True):
        eng = make_engine("acis", batch_rings=br, bucket_bytes=0,
                          latency_optimal_below=latency_below)
        c = eng.compile(tracing.trace(_batch_prog(monoid)),
                        in_avals=avals, axis_size=N)
        kinds = c.stage_kinds()
        if br:
            assert kinds.count("batched_allreduce") == 1
            assert "allreduce" not in kinds
        else:
            assert kinds.count("allreduce") == 3
        spec = P("data", None)
        outs[br] = smap(
            lambda *vs: tuple(o[None] for o in c(*[v[0] for v in vs])),
            mesh8, (spec,) * 3, (spec,) * 3)(*[jnp.asarray(x) for x in xs])
    for i in range(3):
        np.testing.assert_array_equal(np.asarray(outs[True][i]),
                                      np.asarray(outs[False][i]))


def test_batched_ring_composes_with_buckets(mesh8, rng):
    """Small buckets leave several same-axis bucket allreduces; batching
    merges them into one launch and the sync stays exact."""
    sizes = [64, 96, 32, 80, 48]
    grads = {f"l{i}": rng.standard_normal((N, s)).astype(np.float32)
             for i, s in enumerate(sizes)}
    keys = sorted(grads)
    outs = {}
    for br in (False, True):
        eng = make_engine("acis", batch_rings=br, bucket_bytes=512)

        def f(*ls):
            g = dict(zip(keys, [l[0] for l in ls]))
            synced, _ = eng.gradient_sync(g, None)
            return tuple(synced[k][None] for k in keys)

        spec = P("data", None)
        outs[br] = smap(f, mesh8, (spec,) * len(keys),
                        (spec,) * len(keys))(
            *[jnp.asarray(grads[k]) for k in keys])
        compiled = next(iter(eng._sync_cache.values()))
        if br:
            assert "batched_allreduce" in compiled.stage_kinds()
    for i, k in enumerate(keys):
        np.testing.assert_array_equal(np.asarray(outs[True][i]),
                                      np.asarray(outs[False][i]))
        np.testing.assert_allclose(np.asarray(outs[False][i])[0],
                                   grads[k].mean(0), atol=1e-4)


def test_batched_ring_skips_dependent_reduces(rng):
    """A reduce consuming another reduce's output must not share its
    launch."""
    eng = make_engine("acis", batch_rings=True, bucket_bytes=0)

    def prog(x, y):
        a = acis.reduce(x, axis="data")
        b = acis.reduce(acis.map(lambda v: v * 0.5, a, name="h"),
                        axis="data")
        c = acis.reduce(y, axis="data")
        return a, b, c

    c = eng.compile(tracing.trace(prog),
                    in_avals=(AV((16,), jnp.float32),) * 2, axis_size=N)
    kinds = c.stage_kinds()
    # a and c batch together; b (dependent) stays its own launch — as a
    # plain ring, possibly with its feeding map fused in
    assert kinds.count("batched_allreduce") == 1
    assert kinds.count("allreduce") + kinds.count("map+allreduce") == 1
    c.source.validate()


def test_batched_stage_leads_its_dispatch_group():
    from repro.core.executor import _axis_groups

    class FakeStage:
        def __init__(self, kind, axis):
            self.kind, self.axis = kind, axis

    stages = [FakeStage("allreduce", "data"),
              FakeStage("batched_allreduce", "data"),
              FakeStage("map", "")]
    groups = _axis_groups(stages, (0, 1, 2))
    data_group = next(idxs for ax, idxs in groups if ax == "data")
    assert data_group == (1, 0)


# ---------------------------------------------------------------------------
# 3b. RS/AG bucketing
# ---------------------------------------------------------------------------

def _rs_prog(a, b, c):
    return (tracing.reduce_scatter(a, axis="data"),
            tracing.reduce_scatter(b, axis="data"),
            tracing.reduce_scatter(c, axis="data"))


def _ag_prog(a, b, c):
    return (tracing.all_gather(a, axis="data"),
            tracing.all_gather(b, axis="data"),
            tracing.all_gather(c, axis="data"))


@pytest.mark.parametrize("case", ["rs", "ag"])
def test_rs_ag_buckets_bitwise(mesh8, rng, case):
    prog = _rs_prog if case == "rs" else _ag_prog
    avals = (AV((16,), jnp.float32), AV((16, 3), jnp.float32),
             AV((8, 4), jnp.float32))
    xs = [rng.standard_normal((N,) + a.shape).astype(np.float32)
          for a in avals]
    outs, kinds = {}, {}
    for bb in (0, None):
        eng = make_engine("acis", bucket_bytes=bb)
        c = eng.compile(tracing.trace(prog), in_avals=avals, axis_size=N)
        kinds[bb] = c.stage_kinds()
        spec = P("data", None)
        outs[bb] = smap(
            lambda *vs: tuple(o[None] for o in c(*[v[0] for v in vs])),
            mesh8, (spec,) * 3, (spec,) * 3)(*[jnp.asarray(x) for x in xs])
    coll = "reduce_scatter" if case == "rs" else "allgather"
    assert kinds[0].count(coll) == 3
    assert kinds[None].count(coll) == 1     # 3 collectives → 1 bucket
    for i in range(3):
        np.testing.assert_array_equal(np.asarray(outs[None][i]),
                                      np.asarray(outs[0][i]))


def test_rs_bucket_respects_scatter_axis_semantics(mesh8, rng):
    """Each rank's bucketized RS share equals the concat of its per-leaf
    shares — checked on every rank, not just rank 0."""
    avals = (AV((16,), jnp.float32), AV((24,), jnp.float32))
    xs = [rng.standard_normal((N,) + a.shape).astype(np.float32)
          for a in avals]
    eng = make_engine("acis")
    c = eng.compile(tracing.trace(lambda a, b: (
        tracing.reduce_scatter(a, axis="data"),
        tracing.reduce_scatter(b, axis="data"))),
        in_avals=avals, axis_size=N)
    spec = P("data", None)
    outs = smap(lambda *vs: tuple(o[None] for o in c(*[v[0] for v in vs])),
                mesh8, (spec,) * 2, (P("data", None),) * 2)(
        *[jnp.asarray(x) for x in xs])
    for x, o in zip(xs, outs):
        got = np.asarray(o)                  # [N, leaf_size/N]
        want = x.sum(0).reshape(N, -1)       # rank r holds chunk r
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_rs_ag_pair_still_fuses_to_allreduce():
    """The RS∘AG → allreduce rebuild (RsAgPattern) must survive RS/AG
    bucketing: the pair is never split across a bucket boundary."""
    eng = make_engine("acis")

    def prog(a, b):
        return (tracing.all_gather(tracing.reduce_scatter(a, axis="data"),
                                   axis="data"),
                tracing.all_gather(tracing.reduce_scatter(b, axis="data"),
                                   axis="data"))

    c = eng.compile(tracing.trace(prog),
                    in_avals=(AV((16,), jnp.float32),) * 2, axis_size=N)
    kinds = c.stage_kinds()
    assert "reduce_scatter" not in kinds
    assert "allgather" not in kinds
    assert kinds.count("allreduce") == 2


def test_ragged_rs_stays_unbucketed():
    """Leading dim not divisible by the axis size: the per-leaf RS owns
    the ragged split; Coalesce must leave it alone."""
    eng = make_engine("acis")
    c = eng.compile(tracing.trace(lambda a, b: (
        tracing.reduce_scatter(a, axis="data"),
        tracing.reduce_scatter(b, axis="data"))),
        in_avals=(AV((13,), jnp.float32), AV((21,), jnp.float32)),
        axis_size=N)
    assert c.stage_kinds().count("reduce_scatter") == 2


# ---------------------------------------------------------------------------
# 4. cost model + simulator coverage for the new stage kind
# ---------------------------------------------------------------------------

def test_batched_allreduce_stage_time_equals_allreduce():
    p = netmodel.PAPER
    for m in (1 << 10, 1 << 20):
        assert netmodel.stage_time("batched_allreduce", N, m, p) == \
            netmodel.stage_time("allreduce", N, m, p)
    t = netmodel.stage_time_terms("batched_allreduce", N, 1 << 20)
    assert t == netmodel.stage_time_terms("allreduce", N, 1 << 20)


def test_batched_ring_amortization_helpers():
    p = netmodel.PAPER
    sizes = [1 << 16] * 6
    sep, bat = netmodel.batched_ring_times(N, sizes, p)
    assert bat < sep
    # the saving is exactly the (k-1) amortized hop walks
    hop_walk = 2 * (N - 1) * (p.fpga_link + p.port)
    np.testing.assert_allclose(sep - bat, (len(sizes) - 1) * hop_walk,
                               rtol=1e-9)
    for kind in ("reduce_scatter", "allgather"):
        sep, tot = netmodel.bucketed_collective_times(kind, N, sizes, p)
        assert tot < sep
    with pytest.raises(ValueError):
        netmodel.bucketed_collective_times("alltoall", N, sizes, p)


def test_batched_stage_analytic_vs_simulated(rng):
    """The simulator runs the batched kind through the same ring walk the
    analytic model charges: per-stage t_model is populated and the
    simulated time stays within the established envelope."""
    from repro.cgra.simulate import SwitchSim

    eng = make_engine("acis", batch_rings=True, bucket_bytes=0)
    c = eng.compile(tracing.trace(_batch_prog(ADD)),
                    in_avals=(AV((1 << 12,), jnp.float32),
                              AV((1 << 11,), jnp.float32),
                              AV((1 << 13,), jnp.float32)),
                    axis_size=N)
    assert "batched_allreduce" in c.stage_kinds()
    xs = [np.asarray(rng.standard_normal((N, 1 << s)), np.float32)
          for s in (12, 11, 13)]
    out, rep = SwitchSim({"data": N}).run(c, *xs)
    batched = [s for s in rep.stages if s.kind == "batched_allreduce"]
    assert batched and all(s.t_model for s in batched)
    for s in batched:
        assert 0.5 < s.deviation < 2.0
    # simulated numerics: plain per-leaf sums
    for x, o in zip(xs, out):
        np.testing.assert_allclose(np.asarray(o)[0], x.sum(0),
                                   rtol=1e-4, atol=1e-5)


def test_tune_space_covers_new_knobs():
    import importlib

    # repro.tune re-exports the search *function*; get the module
    search = importlib.import_module("repro.tune.search")
    assert "use_kernels" in search.TUNABLE_FIELDS
    assert "batch_rings" in search.TUNABLE_FIELDS
    assert set(search.DEFAULT_SPACE["use_kernels"]) == {False, True}
    assert set(search.DEFAULT_SPACE["batch_rings"]) == {False, True}
