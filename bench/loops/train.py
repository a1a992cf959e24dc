"""Training through the program's ``build_train_step_acis``.

One compiled step with its state: parameters made from the seed,
AdamW, the traffic's collective backend with kernels, persistent sync
arenas and donation, on a ``data`` mesh over the cell's chips.  Set-up
drives that step through its first ``checked_steps`` steps (which also
compiles it) and records what the comparison needs; the window then
keeps stepping the same state.  Every step ends in
``block_until_ready``.  Batches are distinct rows of the bigram
language, drawn from the seed in one call.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from bench import correct, gen, xplane
from bench import weights as W

TRACE_SECONDS = 3.0


def build(run):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core.api import CollectiveConfig, CollectiveEngine
    from repro.models import Model
    from repro.train import optimizer as opt_lib
    from repro.train.step import TrainState, build_train_step_acis

    t, hp = run.traffic, run.traffic["optimizer"]
    mcfg = run.arch.model_config(run.config, max_seq=t["seq_len"],
                                 remat=t["remat"])
    model = Model(mcfg)
    mesh = Mesh(np.asarray(run.devices), ("data",))
    rep = NamedSharding(mesh, P())
    engine = CollectiveEngine(
        CollectiveConfig(backend=t["backend"], use_kernels=t["use_kernels"]),
        inner_axis="data")
    opt = opt_lib.adamw(hp["lr"], b1=hp["b1"], b2=hp["b2"], eps=hp["eps"],
                        weight_decay=hp["weight_decay"])
    step = build_train_step_acis(model, opt, mesh, engine, donate=True,
                                 microbatches=t["microbatches"])
    params = run.arch.program_params(run.config, model, run.seed, rep)
    grads_like = jax.tree.map(lambda p: jax.ShapeDtypeStruct(
        p.shape, jnp.float32 if t["microbatches"] > 1 else p.dtype), params)
    arenas = engine.init_arenas(grads_like,
                                axis_sizes={"data": run.chips})
    arenas = None if arenas is None else jax.device_put(arenas, rep)
    state = TrainState(params, jax.jit(opt.init, out_shardings=rep)(params),
                       jax.device_put(jnp.zeros((), jnp.int32), rep),
                       engine.init_state(params), arenas)
    return step, state, mesh, hp


def run(run, *, t_start: float, keep_trace=None) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    t = run.traffic
    c = run.config
    step, state, mesh, hp = build(run)
    rows = t["rows_per_chip"] * run.chips
    batches = gen.bigram_batches(
        W.root_key(run.seed, W.DATA), t, c["vocab_size"], rows,
        t["pool_batches"], NamedSharding(mesh, P("data")))

    to_canon = lambda tree: run.arch.from_program(c, tree)  # noqa: E731
    leaf_norms = jax.jit(lambda tree: {
        k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
        for k, v in to_canon(tree).items()})
    change = jax.jit(lambda p, key: {
        k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)
                                       - p0.astype(jnp.float32))))
        for (k, v), p0 in zip(to_canon(p).items(),
                              run.arch.canonical(c, key).values())})

    # the checked steps: the window's own step on its own feed
    n_check = t["checked_steps"]
    prog = {"nll": []}
    for i in range(n_check):
        state, m = step(state, {"tokens": batches[i]})
        prog["nll"].append(float(m["nll"]))
        if i == 0:
            g = leaf_norms(state.opt["m"])
            prog["grad"] = {k: float(v) / (1 - hp["b1"])
                            for k, v in g.items()}
    prog["delta"] = {k: float(v) for k, v in change(
        state.params, W.root_key(run.seed, W.WEIGHTS)).items()}
    checked = [np.asarray(b) for b in batches[:n_check]]
    run.log(f"checked steps: nll {prog['nll']}")

    seconds = min(run.seconds, TRACE_SECONDS) if run.trace else run.seconds
    if run.trace:
        xplane.start(run.trace_dir)
    n, nlls = 0, []
    n_compiles = run.compiles.n
    # set-up's garbage (traced programs and the like) out of the
    # collector's sight: its full passes would stall the window
    gc.collect()
    gc.freeze()
    t0 = time.perf_counter()
    run.stats["setup_s"] = t0 - t_start
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            b = batches[(n_check + n) % len(batches)]
            with jax.profiler.TraceAnnotation("bench.train_step"):
                state, m = step(state, {"tokens": b})
                jax.block_until_ready((state, m))
            nlls.append(m["nll"])
            n += 1
            if time.perf_counter() - t0 >= seconds:
                break
    t1 = time.perf_counter()
    gc.unfreeze()
    run.compiles_in_window = run.compiles.n - n_compiles
    if run.trace:
        run.view = xplane.collect(run.trace_dir, keep_trace)
    run.read_memory_peak()
    tokens = n * rows * t["seq_len"]
    run.metrics = {"train_tokens_per_s": tokens / (t1 - t0),
                   "setup_s": run.stats["setup_s"]}
    run.attempted = n
    run.failed = int(sum(not np.isfinite(float(x)) for x in nlls))
    run.stats.update(steps=n, tokens=tokens, window_s=t1 - t0)
    run.log(f"window {t1 - t0:.3f} s, {n} steps, {tokens} tokens: "
            f"{run.metrics}")

    # the program's state goes before the reference runs
    del state, batches, m, step
    gc.collect()
    feed = [jnp.asarray(b) for b in checked]
    ref = run.ref.train_readings(run.seed, c, feed, hp, t["z_loss"])
    run.checks.update(correct.train_checks(prog, ref, run.log))
    # the controls, and the fault of a step that leaves half of the
    # batch out, each put in the program's place
    if run.control:
        for name, kw in [(q, {"quant": q}) for q in run.control] + [
                ("half_batch", {"batches": [
                    correct.half_batch(b) for b in checked]})]:
            got = run.ref.train_readings(
                run.seed, c, kw.get("batches", feed), hp, t["z_loss"],
                quant=kw.get("quant"))
            run.checks.update({f"{k}.{name}": v for k, v in
                               correct.train_checks(got, ref,
                                                    run.log).items()})
