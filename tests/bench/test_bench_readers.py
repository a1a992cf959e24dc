"""Each per-layer reader against a trace view built by hand, and the
trace reduction against a trace recorded here."""

import importlib.util
import types

import jax
import jax.numpy as jnp
import pytest

import bench_tiny
from bench import xplane
from bench.counts import dense

MS = 1_000_000          # ns

# device 0: matmul 0-4 ms, all-reduce 4-5 ms, pack 5-5.5 ms, idle to 8,
# matmul 8-9 ms; device 1: matmul 0-2 ms, collective-permute 2-6 ms.
# The window is 0-10 ms; the harness was in its step 0-6 ms and in its
# bookkeeping 6-10 ms.
VIEW = xplane.TraceView(
    ops={"/device:TPU:0": [(0, 4 * MS, "fusion.12"),
                           (4 * MS, 5 * MS, "all-reduce.3"),
                           (5 * MS, int(5.5 * MS), "fused_pack.1"),
                           (8 * MS, 9 * MS, "fusion.12"),
                           (9.5 * MS, 12 * MS, "fusion.7")],
         "/device:TPU:1": [(0, 2 * MS, "fusion.12"),
                           (2 * MS, 6 * MS, "collective-permute-done.4")]},
    host=[(0, 10 * MS, "bench.window"), (0, 6 * MS, "bench.engine_step"),
          (6 * MS, 10 * MS, "bench.clients")],
    window=(0, 10 * MS),
    # device 0: the all-reduce and an asynchronous all-gather hidden
    # behind its matmul; device 1: the collective-permute
    collectives={"/device:TPU:0": [(MS, int(3.5 * MS), "all-gather-start.2"),
                                   (4 * MS, 5 * MS, "all-reduce.3")],
                 "/device:TPU:1": [(2 * MS, 6 * MS,
                                    "collective-permute-done.4")]})

CONFIG = {"hidden_size": 8, "intermediate_size": 16, "num_hidden_layers": 2,
          "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 4,
          "vocab_size": 10}


def reader(name):
    path = bench_tiny.REPO / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def fake_run(**kw):
    base = dict(view=VIEW, stats={}, config=CONFIG, chips=2,
                traffic={"seq_len": 7},
                peak={"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e6})
    base.update(kw)
    return types.SimpleNamespace(**base)


def test_union_and_gaps():
    assert xplane.union_ns([(0, 4), (2, 6), (8, 9)]) == 7
    assert xplane.gaps([(1, 2), (4, 5)], (0, 10)) == [(5, 10), (2, 4),
                                                       (0, 1)]


def test_busy_idle_and_busiest():
    # device 0 in the window: 0-5.5, 8-9 and 9.5-10 ms busy
    assert xplane.busy_s(VIEW, "/device:TPU:0") == pytest.approx(7e-3)
    assert xplane.busiest(VIEW) == "/device:TPU:0"
    assert reader("serve.device_idle_share")(fake_run()) == \
        pytest.approx(30.0)
    assert reader("train.device_idle_share")(fake_run()) == \
        pytest.approx(30.0)
    assert reader("train.device_idle_share")(fake_run(view=None)) is None


def test_collective_ms_per_tick_takes_the_busiest_device():
    # device 1 spends 4 ms in collectives, device 0 1 + 2.5 ms; 2 ticks
    r = reader("serve.collective_ms_per_tick")
    assert r(fake_run(stats={"ticks": 2})) == pytest.approx(2.0)
    quiet = xplane.TraceView({"/device:TPU:0": [(0, MS, "fusion")]}, [],
                             (0, 2 * MS))
    assert r(fake_run(view=quiet, stats={"ticks": 2})) is None


def test_host_ms_per_tick():
    r = reader("serve.host_ms_per_tick")
    run = fake_run(stats={"ticks": 4, "host_step_s": 0.1, "decode_s": 0.06})
    assert r(run) == pytest.approx(10.0)
    assert r(fake_run(stats={"ticks": 4, "host_step_s": 0.1})) is None


def test_step_mfu_readers():
    serve = fake_run(stats={"positions": [0, 4], "window_s": 0.5})
    want = 100 * dense.decode_flops(CONFIG, [0, 4]) / 0.5 / (2 * 1e9)
    assert reader("serve.step_mfu")(serve) == pytest.approx(want)
    train = fake_run(stats={"tokens": 70, "window_s": 2.0})
    want = 100 * 70 * dense.train_flops_per_token(CONFIG, 7) / 2.0 / 2e9
    assert reader("train.step_mfu")(train) == pytest.approx(want)
    assert reader("serve.step_mfu")(fake_run()) is None


def test_self_times_of_nested_ops():
    nested = [(0, 10, "while.1"), (1, 4, "fusion.2"), (5, 6, "fusion.3"),
              (12, 13, "copy.4")]
    got = {n: t * 1e9 for t, n in xplane.self_times(nested)}
    assert got == pytest.approx({"while.1": 6, "fusion.2": 3,
                                 "fusion.3": 1, "copy.4": 1})


def test_collective_opcode_whatever_the_name():
    hit = xplane.COLLECTIVE.search
    assert hit("%psum.3 = bf16[64,1,4096]{2,1,0} all-reduce(bf16[64,1,4096] "
               "%x), replica_groups={{0,1,2,3}}")
    assert hit("%collective-permute-start.1 = (bf16[8]) "
               "collective-permute-start(bf16[8] %y)")
    assert not hit("%all-reduce-like_fusion.2 = f32[8] fusion(f32[8] %z)")


def test_short_name_of_an_hlo_instruction():
    assert xplane.short_name("%fused_pack.1 = f32[20480]{0:T(1024)} "
                             "custom-call(f32[20480] %a)") == "fused_pack.1"
    assert xplane.short_name("jit_step_fn(94523)") == "jit_step_fn(94523)"


def test_breakdown_names_ops_and_gaps():
    bd = xplane.breakdown(VIEW)
    ops = dict(bd["device_ops"])
    assert ops["fusion"] == pytest.approx(4e-3 + 1e-3 + 0.5e-3)
    assert ops["all-reduce"] == pytest.approx(1e-3)
    # the longest gap (5.5-8 ms) falls in the harness's bookkeeping
    label, seconds = bd["idle_gaps"][0]
    assert label == "bench.clients" and seconds == pytest.approx(2.5e-3)


def test_read_a_recorded_trace(tmp_path):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.train_step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    view = xplane.read(str(tmp_path))
    assert view.window_s > 0
    assert {n for _, _, n in view.host} >= {"bench.window",
                                            "bench.train_step"}
    # the CPU has no device planes: every device reader finds nothing
    assert view.ops == {} and xplane.idle_share(view) is None
    assert xplane.breakdown(view) is None
