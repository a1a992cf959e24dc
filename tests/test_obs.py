"""repro.obs tests: metrics registry, Perfetto timelines, drift watchdog.

The acceptance bar (ISSUE 8): a recorded gradient_sync on the
{pod: 2, data: 4} topology exports a Perfetto-loadable ``.trace.json``
whose wave structure matches the ExecutionPlan; the same exporter works
on a raw ``SwitchSim`` report; and the drift watchdog recommends a
re-fit on x2-perturbed link parameters while staying quiet on
self-replay.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core as acis
from repro import obs, tune
from repro.core import make_engine
from repro.cgra.simulate import SwitchSim
from repro.obs import metrics as obs_metrics
from repro.obs.drift import DriftWatchdog
from repro.obs.report import RunReport
from repro.obs.spans import StageSpan

AV = jax.ShapeDtypeStruct
N = 8


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_recorder_basics():
    rec = obs.Recorder()
    rec.count("a")
    rec.count("a", 2)
    rec.gauge("g", 7.5)
    rec.observe("h", 1.0)
    rec.observe("h", 3.0)
    rec.event("e", detail="x")
    assert rec.counter("a") == 3
    assert rec.counter("missing") == 0
    snap = rec.snapshot()
    assert snap["gauges"]["g"] == 7.5
    assert snap["hists"]["h"]["n"] == 2
    assert snap["hists"]["h"]["mean"] == 2.0
    assert snap["hists"]["h"]["min"] == 1.0
    assert snap["hists"]["h"]["max"] == 3.0
    assert snap["events"] == [{"name": "e", "detail": "x"}]
    assert json.loads(json.dumps(snap)) == snap      # JSON-able
    assert "a = 3" in rec.summary()
    rec.clear()
    assert rec.counter("a") == 0 and not rec.events


def test_recording_context_installs_and_restores():
    assert obs.current() is obs.null_recorder
    with obs.recording() as rec:
        assert obs.current() is rec
        assert rec.enabled
        obs_metrics.RECORDER.count("x")
        assert rec.counter("x") == 1
    assert obs.current() is obs.null_recorder


def test_null_recorder_noops():
    assert not obs.null_recorder.enabled
    obs.null_recorder.count("x")
    obs.null_recorder.observe("x", 1.0)
    obs.null_recorder.gauge("x", 1.0)
    obs.null_recorder.event("x")
    assert obs.null_recorder.counter("x") == 0
    assert not obs.null_recorder.events


def test_event_cap_never_grows_unbounded():
    rec = obs.Recorder()
    for _ in range(obs_metrics.MAX_EVENTS + 5):
        rec.event("e")
    assert len(rec.events) == obs_metrics.MAX_EVENTS
    assert rec.dropped_events == 5
    assert rec.snapshot()["dropped_events"] == 5


# ---------------------------------------------------------------------------
# shared stage-record schema (satellite: executor / tune dedup)
# ---------------------------------------------------------------------------

def test_stage_trace_is_stage_span():
    assert tune.StageTrace is StageSpan


def test_executor_instrument_emits_shared_spans():
    eng = make_engine("acis")
    c = eng.compile(
        lambda a, b: acis.map(lambda x, y: x * y + 1.0, a, b, name="mul"),
        in_avals=(AV((256,), jnp.float32),) * 2)
    with obs.recording() as rec:
        out, tr = tune.record_instrumented(
            c, jnp.ones(256), jnp.full(256, 2.0))
    assert all(isinstance(s, StageSpan) for s in tr.stages)
    assert tr.stages[0].t_start == 0.0                 # normalized
    assert all(s.duration >= 0 for s in tr.stages)
    assert rec.counter("exec.instrumented_stages") == len(tr.stages)
    assert rec.hists["exec.stage_s"].n == len(tr.stages)
    np.testing.assert_allclose(np.asarray(out[0]), np.full(256, 3.0))


# ---------------------------------------------------------------------------
# Perfetto export: acceptance on the {pod:2, data:4} gradient sync
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def hier_run():
    """A real gradient_sync program on {pod: 2, data: 4}, recorded on the
    dataplane simulator."""
    sizes = {"data": 4, "pod": 2}
    eng = make_engine("acis_hierarchical", inner_axis="data",
                      outer_axis="pod")
    grads = {"b": AV((7,), jnp.float32), "w": AV((4, 33), jnp.float32)}
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    compiled = eng._sync_program(treedef, tuple(leaves), None,
                                 axis_sizes=sizes)
    sim = SwitchSim(eng.topology(axis_size=sizes))
    rng = np.random.default_rng(0)
    # simulator leading dims follow topology order: inner (data=4) first
    xs = [rng.standard_normal((4, 2) + av.shape).astype(np.float32)
          for av in leaves]
    _, trace, report = tune.record_sim(compiled, sim, *xs)
    return eng, compiled, trace, report


def _x_events(events):
    return [e for e in events if e["ph"] == "X" and e["name"] != "inject"]


def test_perfetto_schema_round_trip(hier_run, tmp_path):
    _, compiled, trace, _ = hier_run
    path = tmp_path / "sync.trace.json"
    obs.timeline.save(path, trace, compiled.plan)
    loaded = json.loads(path.read_text())

    events = loaded["traceEvents"]
    assert events, "empty trace"
    for e in events:
        assert e["ph"] in ("M", "X", "i")
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        if e["ph"] == "X":
            assert isinstance(e["ts"], float) and e["ts"] >= 0
            assert isinstance(e["dur"], float) and e["dur"] >= 0
        if e["ph"] == "i":
            assert e["s"] == "p"
    # metadata names the process and every lane
    meta = {e["name"] for e in events if e["ph"] == "M"}
    assert {"process_name", "thread_name"} <= meta


def test_perfetto_wave_structure_matches_plan(hier_run):
    _, compiled, trace, _ = hier_run
    plan = compiled.plan
    tr = obs.chrome_trace(trace, plan)
    xs = _x_events(tr["traceEvents"])
    assert len(xs) == len(compiled.stages)

    # every slice's wave matches the ExecutionPlan's wave assignment...
    wave_of = {i: w for w, grp in enumerate(plan.waves) for i in grp}
    for e in xs:
        assert e["args"]["wave"] == wave_of[e["args"]["stage"]]
    # ...and one instant per plan wave marks the boundary
    instants = [e for e in tr["traceEvents"] if e["ph"] == "i"]
    assert len(instants) == plan.n_waves
    # waves start in order
    starts = [e["ts"] for e in sorted(instants,
                                      key=lambda e: e["args"]["wave"])]
    assert starts == sorted(starts)
    # one lane per axis, wave lane reserved at tid 0
    assert all(e["tid"] >= 1 for e in xs)
    assert all(e["tid"] == 0 for e in instants)


def test_exporter_parity_sim_report_vs_executor_schema(mesh8, rng):
    """One exporter, two sources: the raw SwitchSim report and the
    shared-schema ProgramTrace built from it agree event for event on
    the cgra_nas_is workload."""
    eng = make_engine("acis")
    c = eng.compile(lambda h, k: (acis.reduce(h), acis.all_to_all(k)),
                    in_avals=(AV((16,), jnp.float32),
                              AV((64,), jnp.float32)),
                    axis_size=N)
    assert c.stage_kinds() == ["allreduce+alltoall"]
    h = rng.standard_normal((N, 16)).astype(np.float32)
    k = rng.standard_normal((N, 64)).astype(np.float32)
    sim = SwitchSim(eng.topology(axis_size=N))
    _, trace, report = tune.record_sim(c, sim, h, k)

    ev_sim = obs.chrome_trace(report, c.plan)["traceEvents"]
    ev_exe = obs.chrome_trace(trace, c.plan)["traceEvents"]
    key = lambda e: (e["name"], e["tid"], e["ts"], e["dur"],
                     e["args"]["stage"], e["args"]["wave"])
    xs_sim = sorted(map(key, _x_events(ev_sim)))
    xs_exe = sorted(map(key, _x_events(ev_exe)))
    assert xs_sim == xs_exe
    # both JSON-serializable (sim rows carry Placement objects)
    json.dumps(ev_sim), json.dumps(ev_exe)


def test_instrumented_timeline_uses_local_lane():
    eng = make_engine("acis")
    c = eng.compile(
        lambda a: acis.map(lambda x: x + 1.0, a, name="inc"),
        in_avals=(AV((64,), jnp.float32),))
    _, tr = tune.record_instrumented(c, jnp.zeros(64))
    out = obs.chrome_trace(tr, c.plan)
    xs = _x_events(out["traceEvents"])
    assert xs and all("@" not in e["name"] for e in xs)
    lanes = {e["args"]["name"] for e in out["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert "(local)" in lanes


# ---------------------------------------------------------------------------
# drift watchdog
# ---------------------------------------------------------------------------

def _nas_is(eng):
    return eng.compile(lambda h, k: (acis.reduce(h), acis.all_to_all(k)),
                       in_avals=(AV((16,), jnp.float32),
                                 AV((64,), jnp.float32)),
                       axis_size=N)


def _nas_inputs(rng):
    return (rng.standard_normal((N, 16)).astype(np.float32),
            rng.standard_normal((N, 64)).astype(np.float32))


def test_drift_quiet_on_self_replay(rng):
    eng = make_engine("acis")
    c = _nas_is(eng)
    sim = SwitchSim(eng.topology(axis_size=N))
    _, trace, _ = tune.record_sim(c, sim, *_nas_inputs(rng))
    wd = DriftWatchdog()
    for _ in range(2):
        assert wd.observe(c.plan, c.topology, trace) > 0
    assert wd.alerts() == []
    with obs.recording() as rec:
        assert not wd.refit_recommended()
    assert rec.counter("drift.flagged") == 0


def test_drift_fires_on_mismodeled_stage(rng):
    """A deliberately mis-modeled stage — measured durations x3 — must be
    flagged with the pooled ratio near 3."""
    eng = make_engine("acis")
    c = _nas_is(eng)
    sim = SwitchSim(eng.topology(axis_size=N))
    _, trace, _ = tune.record_sim(c, sim, *_nas_inputs(rng))
    slow = dataclasses.replace(trace, stages=tuple(
        dataclasses.replace(s, t_end=s.t_start + 3.0 * s.duration)
        for s in trace.stages))
    wd = DriftWatchdog()
    with obs.recording() as rec:
        for _ in range(2):
            wd.observe(c.plan, c.topology, slow)
        assert wd.refit_recommended()
    alerts = wd.alerts()
    assert alerts and alerts[0].ratio == pytest.approx(3.0, rel=0.35)
    assert alerts[0].drift > wd.threshold
    assert rec.counter("drift.flagged") >= 1
    assert any(n == "drift.refit_recommended" for n, _ in rec.events)
    assert "DRIFT" in wd.report()


def test_drift_recommends_refit_on_perturbed_links(rng):
    """x2-perturbed simulator link parameters drift every collective key
    past threshold, and the recommended re-fit actually runs."""
    eng = make_engine("acis")
    c = _nas_is(eng)
    sim = SwitchSim(eng.topology(axis_size=N))
    net = sim.nets["data"]
    sim.nets["data"] = dataclasses.replace(
        net, bw=net.bw * 0.5, fpga_link=net.fpga_link * 2.0)
    wd = DriftWatchdog()
    for _ in range(2):
        _, trace, _ = tune.record_sim(c, sim, *_nas_inputs(rng))
        wd.observe(c.plan, c.topology, trace)
    assert wd.refit_recommended()
    fit = wd.refit()                    # closes the loop: tune.fit
    assert isinstance(fit, tune.NetFit)
    assert fit.n_stages >= 1


def test_drift_rejects_bad_threshold():
    with pytest.raises(ValueError):
        DriftWatchdog(threshold=1.0)


# ---------------------------------------------------------------------------
# explain() symmetry (satellite) + RunReport surfacing
# ---------------------------------------------------------------------------

def test_explain_without_recording_says_so(hier_run):
    _, compiled, _, _ = hier_run
    out = compiled.explain()
    assert "no recording attached" in out
    assert "meas_us" not in out.splitlines()[1]      # no phantom columns


def test_explain_accepts_run_report(hier_run):
    _, compiled, trace, _ = hier_run
    rep = RunReport(trace, compiled=compiled)
    from_report = compiled.explain(trace=rep)
    from_trace = compiled.explain(trace=trace)
    assert from_report == from_trace
    assert "mispredict ratio (meas/model)" in from_report
    assert "meas_us" in from_report


def test_run_report_text_json_save(hier_run, tmp_path):
    _, compiled, trace, _ = hier_run
    rec = obs.Recorder()
    rec.count("compile.programs")
    rep = RunReport.from_run(compiled, trace, recorder=rec)
    text = rep.text()
    assert "drift watchdog" in text and "counters:" in text
    payload = rep.to_json()
    assert payload["trace"]["stages"] == len(trace.stages)
    assert payload["program"]["waves"] == compiled.plan.n_waves
    assert "refit_recommended" in payload["drift"]
    assert payload["metrics"]["counters"]["compile.programs"] == 1
    json.dumps(payload)
    p = rep.save(tmp_path / "report.json")
    assert json.loads(open(p).read())["name"] == rep.name
    t = rep.save_trace(tmp_path / "run.trace.json")
    assert json.loads(open(t).read())["traceEvents"]


def test_obs_cli_report_and_trace(hier_run, tmp_path, capsys):
    from repro.obs.__main__ import main

    _, _, trace, _ = hier_run
    src = tmp_path / "run.jsonl"
    tune.save_jsonl(src, trace)

    out = tmp_path / "run.trace.json"
    assert main(["trace", str(src), "-o", str(out)]) == 0
    loaded = json.loads(out.read_text())
    assert len(_x_events(loaded["traceEvents"])) == len(trace.stages)

    assert main(["report", str(src), "--json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["trace"]["stages"] == len(trace.stages)

    assert main(["report", str(src)]) == 0
    assert "trace" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# counters threaded through compile / sim / serve
# ---------------------------------------------------------------------------

def test_compile_and_sim_counters(rng):
    eng = make_engine("acis")
    with obs.recording() as rec:
        c = _nas_is(eng)
        sim = SwitchSim(eng.topology(axis_size=N))
        sim.run(c, *_nas_inputs(rng))
    assert rec.counter("compile.programs") >= 1
    assert rec.counter("emit.kernel_stage") \
        + rec.counter("emit.reference_stage") >= 1
    assert rec.counter("sim.runs") == 1
    assert rec.counter("sim.stages") == len(c.stages)
    assert rec.hists["plan.wave_width"].n == c.plan.n_waves
    assert rec.counter("cgra.placed") + rec.counter("cgra.host_fallback") \
        == len(c.stages)


def test_sync_cache_counters():
    eng = make_engine("acis")
    grads = {"w": AV((32,), jnp.float32)}
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    with obs.recording() as rec:
        a = eng._sync_program(treedef, tuple(leaves), None,
                              axis_sizes={"data": N})
        b = eng._sync_program(treedef, tuple(leaves), None,
                              axis_sizes={"data": N})
    assert a is b
    assert rec.counter("compile.cache_miss") == 1
    assert rec.counter("compile.cache_hit") == 1


def test_serve_engine_counters():
    from repro import configs
    from repro.models import Model
    from repro.serve.engine import Request, ServeEngine

    cfg = configs.get_smoke("acis-100m")
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    rec = obs.Recorder()
    eng = ServeEngine(model, params, slots=2, max_seq=64, recorder=rec)
    eng.submit(Request(rid=0, prompt=np.arange(3, dtype=np.int32),
                       max_new_tokens=2))
    done = eng.run_to_completion()
    assert len(done) == 1
    assert rec.counter("serve.ticks") >= 1
    assert rec.counter("serve.admitted") == 1
    assert rec.counter("serve.retired") == 1
    assert rec.hists["serve.decode_s"].n >= 1
    assert rec.gauges["serve.active"] >= 0


# ---------------------------------------------------------------------------
# obs.span, timed compiles, the serve tick's phases, device scopes
# ---------------------------------------------------------------------------

@pytest.fixture
def annotations(monkeypatch):
    """Names of the profiler annotations entered, in order."""
    entered = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(self.name)

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    return entered


def test_span_annotates_always_and_records_only_when_enabled(annotations):
    import time

    assert not obs.current().enabled
    with obs.span("t.off"):
        pass
    assert annotations == ["t.off"]
    with obs.recording() as rec:
        with obs.span("t.outer"):
            with obs.span("t.inner"):
                time.sleep(0.01)
    assert annotations == ["t.off", "t.outer", "t.inner"]
    assert "t.off_s" not in rec.hists
    inner, outer = rec.hists["t.inner_s"], rec.hists["t.outer_s"]
    assert inner.n == outer.n == 1
    assert outer.total >= inner.total >= 0.01
    # a recorder passed in takes the span whatever is installed
    mine = obs.Recorder()
    with obs.span("t.mine", mine):
        pass
    assert mine.hists["t.mine_s"].n == 1
    assert obs.null_recorder.hists == {}


def test_span_records_when_the_body_raises():
    rec = obs.Recorder()
    with pytest.raises(ValueError):
        with obs.span("t.raises", rec):
            raise ValueError
    assert rec.hists["t.raises_s"].n == 1


def test_compiles_are_timed_while_an_enabled_recorder_is_installed():
    def triple_plus_one(x):
        return x * 3 + 1

    with obs.recording() as rec:
        assert obs_metrics._compile_listener_on
        jax.jit(triple_plus_one)(jnp.arange(7.0)).block_until_ready()
    assert not obs_metrics._compile_listener_on
    mine = [f for n, f in rec.events if n == "compile"
            and "triple_plus_one" in f["fun_name"]]
    assert len(mine) == 1 and mine[0]["s"] > 0
    assert rec.hists["compile.s"].n == sum(
        1 for n, _ in rec.events if n == "compile")
    # nothing is recorded once the recorder is uninstalled
    n = len(rec.events)
    jax.jit(lambda x: x - 2)(jnp.arange(5.0)).block_until_ready()
    assert len(rec.events) == n
    # the null recorder installed explicitly keeps the listener off
    prev = obs.install(obs.null_recorder)
    assert not obs_metrics._compile_listener_on
    obs.install(prev)


TICK_PHASES = ["serve.admit", "serve.feed", "serve.dispatch",
               "serve.device_wait", "serve.logits_pull", "serve.sample"]


def test_serve_tick_spans_its_six_phases_in_order(annotations):
    from repro import configs
    from repro.models import Model
    from repro.serve.engine import Request, ServeEngine

    cfg = configs.get_smoke("acis-100m")
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    rec = obs.Recorder()
    eng = ServeEngine(model, params, slots=2, max_seq=64, recorder=rec)
    for rid in range(2):
        eng.submit(Request(rid=rid, prompt=np.arange(3, dtype=np.int32),
                           max_new_tokens=3))
    eng.step()
    assert annotations == TICK_PHASES
    eng.run_to_completion()
    ticks = eng.ticks
    # the last call finds no active slot and stops after admission
    assert annotations == TICK_PHASES * ticks + ["serve.admit"]
    assert rec.hists["serve.admit_s"].n == ticks + 1
    assert all(rec.hists[p + "_s"].n == ticks for p in TICK_PHASES[1:])
    # the decode interval is dispatch + wait + pull, and nothing else
    # but the statements between them
    parts = sum(rec.hists[p + "_s"].total for p in
                ("serve.dispatch", "serve.device_wait", "serve.logits_pull"))
    decode = rec.hists["serve.decode_s"].total
    assert parts <= decode <= parts + 2e-3 * ticks
    assert rec.counter("serve.logits_bytes") == \
        ticks * eng.slots * cfg.vocab * 4
    assert "serve.host_sync" not in rec.counters


def test_decode_and_train_programs_carry_scopes_and_names(mesh8):
    from jax.sharding import PartitionSpec as P

    from repro import configs
    from repro.core.api import CollectiveConfig, CollectiveEngine
    from repro.models import Model
    from repro.serve.engine import ServeEngine
    from repro.train import optimizer as opt_lib
    from repro.train.step import build_train_step_acis, init_state

    cfg = configs.get_smoke("acis-100m")
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    eng = ServeEngine(model, params, slots=2, max_seq=32)
    tok = jnp.zeros(2, jnp.int32)
    text = eng._decode.lower(params, tok, eng.cache, tok).as_text(
        debug_info=True)
    assert "jit_decode_tick" in text
    for scope in ("decode.attn", "decode.kv_cache", "decode.mlp",
                  "decode.head"):
        assert scope in text, scope

    engine = CollectiveEngine(CollectiveConfig(backend="acis"),
                              inner_axis="data")
    opt = opt_lib.adamw(1e-3)
    step = build_train_step_acis(model, opt, mesh8, engine)
    state = init_state(model, opt, jax.random.key(1), engine)
    batch = {"tokens": jax.device_put(
        jnp.zeros((8, 16), jnp.int32),
        jax.sharding.NamedSharding(mesh8, P("data")))}
    text = step.__wrapped__.lower(state, batch).as_text(debug_info=True)
    assert "jit_train_step_acis" in text
    for scope in ("train.fwd_bwd", "train.grad_sync", "train.optimizer",
                  "acis."):
        assert scope in text, scope
