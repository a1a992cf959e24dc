"""Device time by program scope (``bench/scopes.py``): the scope map of
compiled HLO text, the view with the program's spans and modules, the
report's per-tick numbers, and one traced run of a tiny cell."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
from bench import scopes, xplane
from repro import obs
from test_bench_readers import VIEW, fake_run, reader

MS = 1_000_000          # ns

HLO = """HloModule jit_decode_tick, is_scheduled=true, entry_computation_layout={()}

%fused_computation.3 (p0: bf16[2,8]) -> bf16[2,8] {
  %p0 = bf16[2,8]{1,0} parameter(0)
  ROOT %neg.1 = bf16[2,8]{1,0} negate(%p0), metadata={op_name="jit(decode_tick)/decode.mlp/neg"}
}

ENTRY %main.9 (cache: bf16[2,8]) -> bf16[2,8] {
  %cache.1 = bf16[2,8]{1,0} parameter(0), metadata={op_name="cache['layers']"}
  %while.5 = (bf16[2,8]{1,0}) while(%tuple.4), condition=%cond.2, body=%body.1, metadata={op_name="jit(decode_tick)/decode.kv_cache/while"}
  %get-tuple-element.440 = bf16[2,8]{1,0} get-tuple-element(%while.5), index=0, metadata={op_name="jit(decode_tick)/decode.kv_cache/while"}
  %copy.118 = bf16[2,8]{1,0} copy(%get-tuple-element.440), backend_config={"flag_configs":[]}
  %fusion.95 = bf16[2,8]{1,0} fusion(%copy.118), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(decode_tick)/decode.kv_cache/while/body/closed_call/decode.attn/acis.allreduce.s0/add"}
  %fusion = bf16[2,8]{1,0} fusion(%cache.1), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(decode_tick)/jit(_take)/gather"}
  %copy-start.6 = (bf16[2,8]{1,0}, u32[]) copy-start(%cache.1)
  ROOT %copy-done.6 = bf16[2,8]{1,0} copy-done(%copy-start.6)
}
"""


def test_scope_map_of_hlo_text():
    module, m = scopes.module_scopes(HLO)
    assert module == "jit_decode_tick"
    assert m["while.5"] == "decode.kv_cache"
    # a copy XLA inserted has no op_name: it takes its operand's scope
    assert m["copy.118"] == "decode.kv_cache"
    # the innermost scope wins, whatever its family
    assert m["fusion.95"] == "acis.allreduce.s0"
    assert m["neg.1"] == "decode.mlp"
    # an op_name without a scope of the program's, and an async copy of
    # a parameter (whose op_name names no scope), stay unscoped; the
    # called computation is no operand
    assert m["fusion"] is None
    assert m["copy-start.6"] is None and m["copy-done.6"] is None


def test_innermost_scope_of_a_path():
    assert scopes.innermost("jit(f)/train.grad_sync/acis.reduce.s2/add") \
        == "acis.reduce.s2"
    assert scopes.innermost("jit(f)/jit(_take)/gather") is None
    assert scopes.innermost(None) is None


def test_scope_map_of_the_compiled_decode_tick():
    """The CPU-compiled decode of the program finds its cache writes and
    the layer scan's cache slicing under decode.kv_cache."""
    from repro import configs
    from repro.models import Model
    from repro.serve.engine import ServeEngine

    model = Model(configs.get_smoke("acis-100m"))
    eng = ServeEngine(model, model.init(jax.random.key(0)), slots=2,
                      max_seq=32)
    tok = jnp.zeros(2, jnp.int32)
    text = eng._decode.lower(eng.params, tok, eng.cache,
                             tok).compile().as_text()
    module, m = scopes.module_scopes(text)
    assert module == "jit_decode_tick"
    found = set(m.values())
    assert {"decode.attn", "decode.kv_cache", "decode.mlp",
            "decode.head"} <= found
    moving = [n for n, s in m.items() if s == "decode.kv_cache"
              and ("dynamic-update-slice" in n or "scatter" in n
                   or "dynamic-slice" in n or n.startswith("copy"))]
    assert moving, sorted(n for n, s in m.items() if s == "decode.kv_cache")


# The hand-built view: device 0 ran decode_tick 0-6 ms (a while loop
# 0-5 ms holding an attention fusion 0-2 ms and the cache update 2-4 ms;
# the transport 5-6 ms) and a reset program 8-9 ms; the copy of the new
# cache 6-7 ms has no op_name.  The host was in its decode dispatch 0-1
# ms, waited 1-7 ms, pulled the logits 7-7.5 ms and sampled 7.5-8 ms;
# 9-10 ms it was in the harness's bookkeeping.  Two ticks.
OPS = [(0, 5 * MS, "while.5"), (0, 2 * MS, "fusion.2"),
       (2 * MS, 4 * MS, "bitcast_dynamic-update-slice_fusion.4"),
       (5 * MS, 6 * MS, "fusion.95"), (6 * MS, 7 * MS, "copy.118"),
       (int(7.2 * MS), int(7.4 * MS), "fusion.77"),
       (8 * MS, 9 * MS, "scatter.1")]
MAPS = {"jit_decode_tick": {
    "while.5": "decode.kv_cache", "fusion.2": "decode.attn",
    "bitcast_dynamic-update-slice_fusion.4": "decode.kv_cache",
    "fusion.95": "acis.allreduce.s0", "copy.118": "decode.kv_cache",
    "fusion.77": None}}
PVIEW = scopes.ProgramView(
    ops={"/device:TPU:0": OPS},
    host=[(0, 10 * MS, "bench.window"), (0, 8 * MS, "bench.engine_step"),
          (9 * MS, 10 * MS, "bench.clients")],
    window=(0, 10 * MS),
    spans=[(0, MS, "serve.dispatch"), (MS, 7 * MS, "serve.device_wait"),
           (7 * MS, int(7.5 * MS), "serve.logits_pull"),
           (int(7.5 * MS), 8 * MS, "serve.sample")],
    modules={"/device:TPU:0": [(0, int(7.5 * MS), "jit_decode_tick"),
                               (8 * MS, 9 * MS, "jit_scatter")]})


def test_report_by_scope_per_tick():
    r = scopes.report(PVIEW, MAPS, 2)
    ms = r["scopes_ms"]
    # kv_cache: the loop's own 1 ms, the update 2 ms and the copy 1 ms
    assert ms["decode.kv_cache"] == pytest.approx(4 / 2)
    assert ms["decode.attn"] == pytest.approx(2 / 2)
    assert ms["acis.allreduce.s0"] == pytest.approx(1 / 2)
    assert ms["unscoped"] == pytest.approx(0.2 / 2)
    assert ms["module:jit_scatter"] == pytest.approx(1 / 2)
    assert r["busy_ms"] == pytest.approx(8.2 / 2)
    assert r["unscoped_share"] == pytest.approx(100 * 1.2 / 8.2)
    assert r["cache_moving_ms"] == pytest.approx(3 / 2)
    m = r["metrics"]
    assert m["serve.kv_cache_ms_per_tick"] == pytest.approx(2.0)
    assert m["serve.transport_ms_per_tick"] == pytest.approx(0.5)
    assert m["serve.logits_pull_ms_per_tick"] == pytest.approx(0.25)
    assert r["spans_ms"]["serve.device_wait"] == pytest.approx(3.0)


def test_report_marks_ops_missing_from_their_programs_map():
    maps = {"jit_decode_tick": dict(MAPS["jit_decode_tick"])}
    del maps["jit_decode_tick"]["copy.118"]
    assert scopes.report(PVIEW, maps, 2)["scopes_ms"]["unmapped"] == \
        pytest.approx(0.5)


def test_train_sync_counts_the_sync_and_its_stages():
    view = dataclasses.replace(
        PVIEW, ops={"/device:TPU:0": [(0, 3 * MS, "fusion.1"),
                                      (3 * MS, 4 * MS, "fusion.2"),
                                      (4 * MS, 6 * MS, "fused_pack.3")]},
        modules={"/device:TPU:0": [(0, 10 * MS, "jit_train_step_acis")]})
    maps = {"jit_train_step_acis": {"fusion.1": "train.fwd_bwd",
                                    "fusion.2": "train.grad_sync",
                                    "fused_pack.3": "acis.reduce.s0"}}
    r = scopes.report(view, maps, 1)
    assert r["metrics"]["train.sync_ms_per_step"] == pytest.approx(3.0)
    assert r["unscoped_share"] == pytest.approx(0.0)


def test_gaps_are_named_by_the_innermost_span():
    gaps = dict((round(s * 1e3, 6), label)
                for label, s in scopes.label_gaps(PVIEW))
    # 7.4-8 ms: the host sampled (inside bench.engine_step); 9-10 ms:
    # the harness's bookkeeping; 7-7.2 ms: the logits pull
    assert gaps[0.6] == "serve.sample"
    assert gaps[1.0] == "bench.clients"
    assert gaps[0.2] == "serve.logits_pull"
    assert "bench.engine_step" not in gaps.values()


def test_existing_readers_read_as_before_on_a_view_with_spans():
    """Every reader the benchmark has gives, on the view it was tested
    on, the values it gave before, with or without the program's spans
    and modules beside the harness's view."""
    with_spans = scopes.ProgramView(
        ops=VIEW.ops, host=VIEW.host, window=VIEW.window,
        collectives=VIEW.collectives, spans=PVIEW.spans,
        modules={"/device:TPU:0": [(0, 12 * MS, "jit_decode_tick")]})
    stats = {"ticks": 2, "host_step_s": 0.1, "decode_s": 0.06,
             "positions": [0, 4], "window_s": 0.5, "tokens": 70}
    want = {"serve.device_idle_share": 30.0,
            "train.device_idle_share": 30.0,
            "serve.collective_ms_per_tick": 2.0,
            "serve.host_ms_per_tick": 20.0,
            "serve.step_mfu": 0.0005312,
            "train.step_mfu": 0.05712}
    for name, value in want.items():
        for view in (VIEW, with_spans):
            got = reader(name)(fake_run(view=view, stats=stats))
            assert got == pytest.approx(value, rel=1e-12), name
    assert xplane.breakdown(with_spans) == xplane.breakdown(VIEW)


def test_read_keeps_the_programs_spans(tmp_path):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((32, 32))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with obs.span("serve.dispatch"):
            y = f(x)
        with obs.span("serve.device_wait"):
            y.block_until_ready()
    jax.profiler.stop_trace()
    view = scopes.read(str(tmp_path))
    assert [n for _, _, n in sorted(view.spans)] == ["serve.dispatch",
                                                     "serve.device_wait"]
    assert {n for _, _, n in view.host} == {"bench.window"}
    assert scopes.span_s(view, "serve.dispatch") > 0


def test_spy_keeps_the_first_call_and_build_is_restored():
    import types

    f = jax.jit(lambda x, y: x * y)
    loop = types.SimpleNamespace(build=lambda run: (f, "state"))
    with scopes.spy_on(loop) as spies:
        step, state = loop.build(None)
        assert state == "state" and spies == [step]
        np.testing.assert_allclose(step(jnp.ones(3), jnp.full(3, 2.0)),
                                   2.0)
        step(jnp.ones(5), jnp.ones(5))
    assert loop.build is not None and loop.build(None)[0] is f
    assert spies[0].args[0].shape == (3,)
    assert "multiply" in spies[0].text()


def test_a_traced_run_of_a_tiny_cell(tmp_path):
    root = bench_tiny.make_root(tmp_path)
    out = scopes.main(["--workload", bench_tiny.CHAT, "--seed",
                       "3000000019", "--seconds", "0.5"], root=root,
                      require_accelerator=False, cache=False)
    assert out["correct"] and out["programs"] == ["jit_decode_tick"]
    assert set(out["spans_ms"]) == {
        "serve.admit", "serve.feed", "serve.dispatch", "serve.device_wait",
        "serve.logits_pull", "serve.sample"}
    assert out["compiles"] > 0 and out["compile_s"] > 0
    assert out["per_layer"]["serve.host_ms_per_tick"]["value"] > 0
    # the CPU has no device planes: nothing is scoped
    assert "scopes_ms" not in out
