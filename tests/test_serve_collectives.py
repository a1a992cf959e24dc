"""Compiled serving data path: TP decode through engine.compile.

Covers the serve/collectives layer: dense tensor-parallel decode and the
MoE expert all-to-all dispatch/combine as compiled switch programs
(numerics vs the plain path, incl. under obs.recording()), the shared
SwitchProgramCache across engine replicas, SLO-aware admission, and the
deque/batched-reset engine mechanics.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs, obs
from repro.core.api import CollectiveConfig
from repro.models import Model
from repro.serve.collectives import (PROGRAM_CACHE, ServeCollectives,
                                     SwitchProgramCache)
from repro.serve.engine import Request, ServeEngine, SLOPolicy

TP = 2


def _fixture(arch, key=0, slots=4, seq=48):
    cfg = configs.get_smoke(arch)
    model = Model(cfg)
    params = model.init(jax.random.key(key))
    cache = model.init_cache(slots, seq)
    return cfg, model, params, cache


def _tree_allclose(a, b, tol):
    fa = sorted(jax.tree_util.tree_flatten_with_path(a)[0],
                key=lambda kv: str(kv[0]))
    fb = sorted(jax.tree_util.tree_flatten_with_path(b)[0],
                key=lambda kv: str(kv[0]))
    assert len(fa) == len(fb)
    for (ka, la), (kb, lb) in zip(fa, fb):
        d = np.abs(np.asarray(la, np.float32)
                   - np.asarray(lb, np.float32)).max()
        assert d <= tol, (jax.tree_util.keystr(ka), float(d))


@pytest.fixture(scope="module")
def dense():
    return _fixture("acis-100m")


@pytest.fixture(scope="module")
def moe():
    return _fixture("qwen2-moe-a2-7b", key=1)


# ---------------------------------------------------------------------------
# numerics: compiled TP decode vs the plain (unsharded) path
# ---------------------------------------------------------------------------

def test_dense_compiled_decode_matches_plain(dense):
    cfg, model, params, cache = dense
    sc = ServeCollectives(cfg, TP, cache=SwitchProgramCache())
    dec_c = sc.decode_fn(params, cache, mode="compiled", donate=False)
    dec_d = sc.decode_fn(params, cache, mode="direct", donate=False)
    plain = jax.jit(lambda p, t, c, i: model.decode_step(p, t, c, i))

    tok = jnp.array([3, 5, 7, 9], jnp.int32)
    cc, cd, cp = cache, cache, cache
    for step in range(4):
        i = jnp.full(4, step, jnp.int32)
        lc, cc = dec_c(params, tok, cc, i)
        ld, cd = dec_d(params, tok, cd, i)
        lp, cp = plain(params, tok, cp, i)
        # compiled vs uncompiled-acis: identical rank-local math, bit-exact
        assert (np.asarray(lc) == np.asarray(ld)).all()
        # vs the unsharded path: TP sums bf16 partials -> ulp-level slack
        np.testing.assert_allclose(np.asarray(lc), np.asarray(lp),
                                   atol=3e-2, rtol=3e-2)
        tok = jnp.argmax(lc, -1).astype(jnp.int32)
    _tree_allclose(cc, cd, 0.0)
    _tree_allclose(cc, cp, 3e-2)


def test_moe_compiled_dispatch_combine_matches_plain(moe):
    """The MoE expert all-to-all (dispatch + Type-4 fused combine with the
    shared-expert all-reduce) through engine.compile vs plain moe.py."""
    cfg, model, params, cache = moe
    assert cfg.moe.n_shared, "smoke config must exercise the fused combine"
    sc = ServeCollectives(cfg, TP, cache=SwitchProgramCache())
    dec_c = sc.decode_fn(params, cache, mode="compiled", donate=False)
    plain = jax.jit(lambda p, t, c, i: model.decode_step(p, t, c, i))

    # the decode tick compiles an alltoall and a fused allreduce+alltoall
    kinds = [name for name, _, _ in sc.decode_programs(4)]
    assert "serve_moe_alltoall" in kinds
    assert "serve_moe_combine" in kinds

    tok = jnp.array([11, 2, 250, 77], jnp.int32)
    cc, cp = cache, cache
    for step in range(3):
        i = jnp.full(4, step, jnp.int32)
        lc, cc = dec_c(params, tok, cc, i)
        lp, cp = plain(params, tok, cp, i)
        np.testing.assert_allclose(np.asarray(lc), np.asarray(lp),
                                   atol=5e-2, rtol=5e-2)
        tok = jnp.argmax(lp, -1).astype(jnp.int32)
    _tree_allclose(cc, cp, 5e-2)


def test_moe_compiled_path_under_recording(moe):
    """Same numerics with obs recording on, and the serve counters land."""
    cfg, model, params, cache = moe
    plain = jax.jit(lambda p, t, c, i: model.decode_step(p, t, c, i))
    tok = jnp.array([4, 8, 15, 16], jnp.int32)
    i = jnp.zeros(4, jnp.int32)
    with obs.recording() as rec:
        sc = ServeCollectives(cfg, TP, cache=SwitchProgramCache())
        dec = sc.decode_fn(params, cache, mode="compiled", donate=False)
        lc, _ = dec(params, tok, cache, i)
    lp, _ = plain(params, tok, cache, i)
    np.testing.assert_allclose(np.asarray(lc), np.asarray(lp),
                               atol=5e-2, rtol=5e-2)
    assert rec.counter("serve.program_cache_miss") >= 3
    assert rec.counter("compile.programs") >= 3


def test_fused_combine_stage_is_type4(moe):
    cfg, _, _, _ = moe
    sc = ServeCollectives(cfg, TP, cache=SwitchProgramCache())
    by_name = {name: prog for name, prog, _ in sc.decode_programs(4)}
    assert "allreduce+alltoall" in by_name["serve_moe_combine"].explain()
    # analytic costs are finite and ordered: a prefill pass moves more
    # bytes than a decode tick
    assert 0 < sc.decode_comm_time(4) < sc.prefill_comm_time(4, 16)


# ---------------------------------------------------------------------------
# the engine on the compiled transport
# ---------------------------------------------------------------------------

def test_engine_on_compiled_collectives_matches_direct(dense, rng):
    """Full continuous-batching run over the compiled transport: identical
    completions to the uncompiled (direct-ring) transport, slots recycled."""
    cfg, model, params, _ = dense
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab, 3 + i).astype(np.int32),
                    max_new_tokens=4 + (i % 3))
            for i in range(5)]

    def run(mode):
        sc = ServeCollectives(cfg, TP, cache=SwitchProgramCache())
        eng = ServeEngine(model, params, slots=2, max_seq=48, collectives=sc)
        eng._decode = sc.decode_fn(params, eng.cache, mode=mode)
        for r in reqs:
            eng.submit(Request(**{f.name: getattr(r, f.name)
                                  for f in r.__dataclass_fields__.values()}))
        return eng.run_to_completion()

    done_c = run("compiled")
    done_d = run("direct")
    assert len(done_c) == len(done_d) == 5
    for a, b in zip(done_c, done_d):
        assert (a.rid, a.tokens) == (b.rid, b.tokens)


def test_engine_keeps_params_and_cache_sharded(dense):
    """The engine holds parameters on the TP shardings (created sharded,
    or placed once) and creates its KV cache on the TP cache shardings,
    and both stay there across ticks — with the same completions either
    way."""
    cfg, model, params, _ = dense
    sc = ServeCollectives(cfg, TP, cache=SwitchProgramCache())
    pshard = sc.shardings(sc.param_specs(model.param_shapes()))
    placed = jax.jit(model.init, out_shardings=pshard)(jax.random.key(0))
    prompt = np.arange(4, dtype=np.int32)

    def serve(p):
        eng = ServeEngine(model, p, slots=2, max_seq=48, collectives=sc)
        want = jax.tree.leaves(sc.shardings(sc.cache_specs(eng.cache)))
        assert [x.sharding for x in jax.tree.leaves(eng.cache)] == want
        eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=3))
        done = eng.run_to_completion()
        assert [x.sharding for x in jax.tree.leaves(eng.cache)] == want
        assert [x.sharding for x in jax.tree.leaves(eng.params)] == \
            jax.tree.leaves(pshard)
        return done[0].tokens

    assert serve(placed) == serve(params)


def test_shared_program_cache_across_replicas(dense):
    """Two ServeEngine replicas sharing one SwitchProgramCache: the second
    replica's decode build is all cache hits — no recompiles, asserted via
    the obs counters."""
    cfg, model, params, _ = dense
    shared = SwitchProgramCache()
    prompt = np.arange(4, dtype=np.int32)

    def replica():
        sc = ServeCollectives(cfg, TP, cache=shared)
        eng = ServeEngine(model, params, slots=2, max_seq=48, collectives=sc)
        eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=2))
        return eng.run_to_completion()

    with obs.recording() as rec:
        done1 = replica()
        misses_after_first = rec.counter("serve.program_cache_miss")
        compiles_after_first = rec.counter("compile.programs")
        assert misses_after_first >= 1
        done2 = replica()
    assert done1[0].tokens == done2[0].tokens
    # second replica: hits only — miss and compile counters unchanged
    assert rec.counter("serve.program_cache_miss") == misses_after_first
    assert rec.counter("compile.programs") == compiles_after_first
    assert rec.counter("serve.program_cache_hit") > 0
    assert shared.stats()["hits"] > 0
    assert shared.stats()["misses"] == misses_after_first


def test_default_cache_is_process_wide(dense):
    cfg, _, _, _ = dense
    sc = ServeCollectives(cfg, TP)
    assert sc.cache is PROGRAM_CACHE


# ---------------------------------------------------------------------------
# SLO-aware admission
# ---------------------------------------------------------------------------

def test_slo_admission_rejects_impossible_deadline(dense):
    cfg, model, params, _ = dense
    rec = obs.Recorder()
    eng = ServeEngine(model, params, slots=2, max_seq=64,
                      recorder=rec, admission=SLOPolicy())
    # warm the tick-time estimate so the policy has a basis
    eng.submit(Request(rid=0, prompt=np.arange(3, dtype=np.int32),
                       max_new_tokens=2))
    eng.run_to_completion()
    eng.submit(Request(rid=1, prompt=np.arange(5, dtype=np.int32),
                       max_new_tokens=8, deadline_s=1e-9))
    eng.submit(Request(rid=2, prompt=np.arange(3, dtype=np.int32),
                       max_new_tokens=2, deadline_s=60.0))
    done = eng.run_to_completion()
    assert [r.rid for r in eng.rejected] == [1]
    assert sorted(c.rid for c in done) == [0, 2]
    assert rec.counter("serve.slo_rejected") == 1
    assert rec.gauges.get("serve.deadline_headroom_s", 0) > 0


def test_slo_admission_defers_on_prefill_pressure(dense):
    cfg, model, params, _ = dense
    rec = obs.Recorder()
    eng = ServeEngine(model, params, slots=3, max_seq=64, recorder=rec,
                      admission=SLOPolicy(max_concurrent_prefills=1))
    for i in range(3):
        eng.submit(Request(rid=i, prompt=np.arange(4, dtype=np.int32),
                           max_new_tokens=2))
    done = eng.run_to_completion()
    # everything still completes; admission was staggered, not starved
    assert sorted(c.rid for c in done) == [0, 1, 2]
    assert rec.counter("serve.admit_deferred") >= 1


def test_tick_time_estimate_prefers_measured(dense):
    cfg, model, params, _ = dense
    sc = ServeCollectives(cfg, TP, cache=SwitchProgramCache())
    eng = ServeEngine(model, params, slots=2, max_seq=48, collectives=sc)
    analytic = eng.tick_time_estimate()
    assert analytic == sc.decode_comm_time(2) > 0
    eng.submit(Request(rid=0, prompt=np.arange(3, dtype=np.int32),
                       max_new_tokens=2))
    eng.run_to_completion()
    assert eng.tick_time_estimate() == float(np.median(eng._tick_times))


# ---------------------------------------------------------------------------
# engine mechanics: deque queue, queue-depth gauge, batched slot reset
# ---------------------------------------------------------------------------

def test_queue_is_deque_with_depth_gauge(dense):
    import collections
    cfg, model, params, _ = dense
    rec = obs.Recorder()
    eng = ServeEngine(model, params, slots=1, max_seq=64, recorder=rec)
    assert isinstance(eng.queue, collections.deque)
    for i in range(3):
        eng.submit(Request(rid=i, prompt=np.arange(2, dtype=np.int32),
                           max_new_tokens=1))
    eng.step()
    # gauged before admission: all three were queued, one took the slot
    assert rec.gauges["serve.queue_depth"] == 3
    # one tick's f32 logits of every slot, pulled to the host once
    assert rec.counter("serve.logits_bytes") == eng.slots * cfg.vocab * 4
    assert rec.gauges["serve.decode_p50_s"] > 0
    assert rec.gauges["serve.decode_p99_s"] > 0


def test_batched_slot_reset_single_traversal(dense, monkeypatch):
    """All admits in a tick share ONE cache tree traversal."""
    cfg, model, params, _ = dense
    eng = ServeEngine(model, params, slots=4, max_seq=64)
    calls = []
    orig = ServeEngine._reset_slot_caches

    def spy(self, slot_ids):
        calls.append(list(slot_ids))
        return orig(self, slot_ids)

    monkeypatch.setattr(ServeEngine, "_reset_slot_caches", spy)
    for i in range(4):
        eng.submit(Request(rid=i, prompt=np.arange(2, dtype=np.int32),
                           max_new_tokens=1))
    eng.step()
    assert calls == [[0, 1, 2, 3]]


# ---------------------------------------------------------------------------
# validation
# ---------------------------------------------------------------------------

def test_rejects_indivisible_tp(dense):
    cfg, _, _, _ = dense
    with pytest.raises(ValueError, match="n_kv_heads"):
        ServeCollectives(cfg, 4)   # smoke acis-100m has n_kv_heads=2


def test_rejects_unsupported_family():
    cfg = configs.get_smoke("rwkv6-1.6b")
    with pytest.raises(NotImplementedError):
        ServeCollectives(cfg, 2)


def test_rejects_xla_backend(dense):
    cfg, _, _, _ = dense
    with pytest.raises(ValueError, match="acis"):
        ServeCollectives(cfg, 2, config=CollectiveConfig(backend="xla"))


def test_slo_expired_deadline_rejects_even_under_prefill_cap():
    """Pre-PR ordering left an expired request parked at the queue head,
    re-deferred every tick by the prefill cap; the deadline check now
    runs first."""
    class StubEngine:
        slots = 2
        collectives = None

        def tick_time_estimate(self):
            return None

    pol = SLOPolicy(max_concurrent_prefills=1)
    expired = Request(rid=0, prompt=np.arange(4, dtype=np.int32),
                      max_new_tokens=4, deadline_s=0.5,
                      t_submit=time.monotonic() - 1.0)    # waited 1s > 0.5s
    assert pol.decide(expired, StubEngine(), n_prefilling=1) == "reject"
    # without a deadline the cap still defers
    fresh = Request(rid=1, prompt=np.arange(4, dtype=np.int32),
                    max_new_tokens=4, t_submit=time.monotonic())
    assert pol.decide(fresh, StubEngine(), n_prefilling=1) == "defer"


def test_slo_membership_inflates_estimate():
    """Masked ranks degrade the fabric: the same deadline that admits on
    a healthy membership rejects once enough ranks are dead."""
    from repro.elastic import Membership

    class StubEngine:
        slots = 2
        collectives = None

        def tick_time_estimate(self):
            return 1e-3

    req = Request(rid=0, prompt=np.arange(10, dtype=np.int32),
                  max_new_tokens=10, deadline_s=0.04,
                  t_submit=time.monotonic())
    healthy = SLOPolicy(membership=Membership.all_alive(4))
    assert healthy.decide(req, StubEngine(), 0) == "admit"   # est ~0.02s
    degraded = SLOPolicy(membership=Membership.all_alive(4).drop(1, 2, 3))
    assert degraded.decide(req, StubEngine(), 0) == "reject"  # est ~0.08s


def test_slo_dead_fabric_rejects_deadlines_end_to_end(dense):
    """All ranks masked => infinite tick estimate: deadline-carrying
    requests reject at admission instead of hanging mid-decode, while
    best-effort traffic still completes."""
    from repro.elastic import Membership

    cfg, model, params, _ = dense
    rec = obs.Recorder()
    eng = ServeEngine(model, params, slots=2, max_seq=64, recorder=rec,
                      admission=SLOPolicy(
                          membership=Membership.all_alive(2).drop(0, 1)))
    eng.submit(Request(rid=0, prompt=np.arange(3, dtype=np.int32),
                       max_new_tokens=2))
    eng.run_to_completion()                     # warm the tick estimate
    eng.submit(Request(rid=1, prompt=np.arange(4, dtype=np.int32),
                       max_new_tokens=2, deadline_s=60.0))
    eng.submit(Request(rid=2, prompt=np.arange(3, dtype=np.int32),
                       max_new_tokens=2))       # best-effort: unaffected
    done = eng.run_to_completion()
    assert [r.rid for r in eng.rejected] == [1]
    assert sorted(c.rid for c in done) == [0, 2]
    assert rec.counter("serve.slo_rejected") == 1
