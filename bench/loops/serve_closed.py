"""Closed-loop chat serving through the program's ``ServeEngine``.

``clients`` clients each hold one request at a time and send the next
as soon as the last completes.  On one chip the engine decodes through
its plain jitted step; on several it decodes tensor-parallel over them
through ``ServeCollectives``' compiled hook.

Set-up makes the weights from the seed, builds the engine, and runs it
for ``warmup_ticks`` ticks with the clients' first requests staggered
over them, so the window opens on a loop in steady state.  Every output
token is stamped with the host clock as the tick that made it returns
(the tick ends in the engine's pull of the logits to the host).  After
the window the finished requests are compared with the plain
reference.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from bench import correct, gen, xplane

TRACE_SECONDS = 3.0


class Clients:
    """Request bookkeeping: submit times, token times, completions."""

    def __init__(self, eng, traffic: dict, seed: int, vocab: int):
        from repro.serve.engine import Request
        self.Request = Request
        self.eng = eng
        self.source = gen.chat_requests(traffic, seed, vocab)
        self.next_rid = 0
        self.prompt: dict = {}
        self.t_submit: dict = {}
        self.t_tokens: dict = {}
        self.t_done: dict = {}
        self.tokens: dict = {}
        self.n_done = 0

    def submit(self) -> None:
        prompt, n_out = next(self.source)
        rid = self.next_rid
        self.next_rid += 1
        self.prompt[rid] = prompt
        self.t_tokens[rid] = []
        self.t_submit[rid] = time.perf_counter()
        self.eng.submit(self.Request(rid=rid, prompt=prompt,
                                     max_new_tokens=n_out))

    def after_tick(self) -> None:
        """Stamp the tick's new tokens; resubmit for each completion."""
        eng, now = self.eng, time.perf_counter()
        for s in np.flatnonzero(eng.rid >= 0):
            rid = int(eng.rid[s])
            got, have = len(eng.generated[s]), len(self.t_tokens[rid])
            if got > have:
                self.t_tokens[rid].extend([now] * (got - have))
        done = eng.done[self.n_done:]
        self.n_done = len(eng.done)
        for c in done:
            ts = self.t_tokens[c.rid]
            if len(c.tokens) > len(ts):
                ts.extend([now] * (len(c.tokens) - len(ts)))
            self.tokens[c.rid] = np.asarray(c.tokens, np.int32)
            self.t_done[c.rid] = now
            self.submit()


def window_metrics(cl: Clients, t0: float, t1: float) -> dict:
    n_tok, tbt, ttft = 0, [], []
    for rid, ts in cl.t_tokens.items():
        ts = np.asarray(ts)
        inside = ts[(ts > t0) & (ts <= t1)]
        n_tok += inside.size
        if inside.size > 1:
            tbt.extend(np.diff(inside))
        if ts.size and t0 < ts[0] <= t1:
            ttft.append(ts[0] - cl.t_submit[rid])
    return {"decode_tokens_per_s": n_tok / (t1 - t0),
            "tbt_p95_ms": float(np.percentile(tbt, 95)) * 1e3,
            "ttft_p95_ms": float(np.percentile(ttft, 95)) * 1e3,
            "_tokens": n_tok, "_gaps": len(tbt), "_first_tokens": len(ttft)}


def build(run):
    """(engine, recorder) for the run's configuration and chips."""
    import jax
    from repro.models import Model
    from repro.obs.metrics import Recorder
    from repro.serve.engine import ServeEngine

    t = run.traffic
    mcfg = run.arch.model_config(run.config, max_seq=t["max_seq"])
    model = Model(mcfg)
    rec = Recorder() if run.trace else None
    if run.chips > 1:
        from repro.serve.collectives import ServeCollectives
        sc = ServeCollectives(mcfg, run.chips, devices=list(run.devices))
        shard = sc.shardings(sc.param_specs(model.param_shapes()))
        params = run.arch.program_params(run.config, model, run.seed, shard)
        eng = ServeEngine(model, params, slots=t["slots"],
                          max_seq=t["max_seq"], recorder=rec,
                          collectives=sc)
        if run.tp_hook != "compiled":
            eng._decode = sc.decode_fn(eng.params, eng.cache,
                                       mode=run.tp_hook)
    else:
        one = jax.sharding.SingleDeviceSharding(run.devices[0])
        params = run.arch.program_params(run.config, model, run.seed, one)
        eng = ServeEngine(model, params, slots=t["slots"],
                          max_seq=t["max_seq"], recorder=rec)
    return eng, rec


def run(run, *, t_start: float, keep_trace=None) -> None:
    import jax

    t = run.traffic
    eng, rec = build(run)
    cl = Clients(eng, t, run.seed, run.config["vocab_size"])
    warm = t["warmup_ticks"]
    starts = [i * warm // t["clients"] for i in range(t["clients"])]
    for tick in range(warm):
        while starts and starts[0] <= tick:
            starts.pop(0)
            cl.submit()
        eng.step()
        cl.after_tick()
    run.log(f"warmed {warm} ticks, {cl.n_done} requests done")

    seconds = min(run.seconds, TRACE_SECONDS) if run.trace else run.seconds
    if run.trace:
        xplane.start(run.trace_dir)
        rec.clear()
    ticks, host_step, positions, tick_s = 0, 0.0, [], []
    n_compiles = run.compiles.n
    # set-up's garbage (traced programs and the like) out of the
    # collector's sight: its full passes would stall the window
    gc.collect()
    gc.freeze()
    t0 = time.perf_counter()
    run.stats["setup_s"] = t0 - t_start
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            if run.trace:
                positions.extend(int(eng.pos[s]) for s in
                                 np.flatnonzero(eng.rid >= 0))
            a = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.engine_step"):
                eng.step()
            host_step += time.perf_counter() - a
            ticks += 1
            with jax.profiler.TraceAnnotation("bench.clients"):
                cl.after_tick()
            now = time.perf_counter()
            tick_s.append(now - a)
            if now - t0 >= seconds:
                break
    t1 = time.perf_counter()
    gc.unfreeze()
    run.compiles_in_window = run.compiles.n - n_compiles
    if run.trace:
        run.view = xplane.collect(run.trace_dir, keep_trace)
    run.read_memory_peak()
    m = window_metrics(cl, t0, t1)
    run.metrics = {k: v for k, v in m.items() if not k.startswith("_")}
    run.metrics["setup_s"] = run.stats["setup_s"]
    slow = np.argsort(tick_s)[::-1][:5]
    run.log(f"ticks: median {np.median(tick_s) * 1e3:.2f} ms; longest "
            + ", ".join(f"#{i} {tick_s[i] * 1e3:.1f} ms" for i in slow))
    run.log(f"window {t1 - t0:.3f} s, {ticks} ticks, {m['_tokens']} "
            f"tokens, {m['_gaps']} gaps, {m['_first_tokens']} first "
            f"tokens: {run.metrics}")
    done_in_window = [r for r, td in cl.t_done.items() if t0 < td <= t1]
    run.attempted = len({r for r, ts in cl.t_tokens.items()
                         if any(t0 < x <= t1 for x in ts)})
    run.failed = len(eng.rejected)
    run.stats.update(ticks=ticks, window_s=t1 - t0, host_step_s=host_step,
                     positions=positions)
    if run.trace:
        run.stats["decode_s"] = rec.hists["serve.decode_s"].total

    sample = correct.serve_sample(cl, done_in_window, run.seed,
                                  t["check"]["tokens"])
    # the program's state goes before the reference runs
    eng.params = eng.cache = eng._decode = None
    del eng, cl.eng
    gc.collect()
    run.checks.update(correct.serve_checks(run, sample, run.control))

