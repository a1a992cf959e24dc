"""End-to-end driver: train the ~100M-param model with ACiS gradient sync.

    PYTHONPATH=src python examples/train_e2e.py \
        --backend acis_compressed --steps 300

Demonstrates the whole stack at laptop scale: synthetic bigram data →
composable model → explicit in-network gradient sync (shared-scale int8
with error feedback — Types 2+3) → AdamW → checkpoints → resume.  Loss
must descend toward the bigram entropy floor; the final report prints the
wire-bytes saving of the compressed transport vs f32.
"""

import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import time

import jax
import numpy as np

from repro import configs
from repro.core import make_engine
from repro.data.pipeline import BigramStream, DataConfig
from repro.models import Model
from repro.train import optimizer as opt_lib
from repro.train.loop import LoopConfig, TrainLoop
from repro.train.step import (build_train_step_acis,
                              build_train_step_gspmd, init_state)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="acis_compressed",
                    choices=["xla", "acis", "acis_compressed",
                             "acis_hierarchical"])
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--arch", default="acis-100m")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CI-sized)")
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()

    cfg = configs.get_smoke(args.arch) if args.smoke else \
        configs.get(args.arch)
    model = Model(cfg)
    print(f"model {cfg.name}: {cfg.param_count() / 1e6:.1f}M params, "
          f"backend={args.backend}")

    # every device: pairs on "model" where they divide, the rest on "data"
    n = len(jax.devices())
    tp = 2 if n % 2 == 0 else 1
    mesh = jax.make_mesh((n // tp, tp), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    optimizer = opt_lib.adamw(opt_lib.warmup_cosine(3e-4, 20, args.steps))

    if args.backend == "xla":
        step = build_train_step_gspmd(model, optimizer, mesh, donate=False)
        engine = None
        state = init_state(model, optimizer, jax.random.key(0), engine)
    else:
        engine = make_engine(args.backend, inner_axis="data")
        # donate the state so the persistent gradient-sync bucket arenas
        # (init_state arenas=True) are written in place every step — the
        # pack transient is ~1x bucket size instead of 2x
        step = build_train_step_acis(model, optimizer, mesh, engine,
                                     donate=True)
        state = init_state(model, optimizer, jax.random.key(0), engine,
                           mesh=mesh, arenas=True)
        if state.sync_arenas is not None:
            sizes = [int(np.prod(a.shape)) * a.dtype.itemsize
                     for a in state.sync_arenas]
            print(f"sync arenas: {len(sizes)} buckets, "
                  f"{sum(sizes) / 1e6:.1f} MB (donated in place)")
    stream = BigramStream(DataConfig(
        vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch, seed=7))
    print(f"data: bigram entropy floor = {stream.entropy():.3f} nats")

    loop = TrainLoop(step, stream, LoopConfig(
        total_steps=args.steps, log_every=max(args.steps // 20, 1),
        ckpt_every=max(args.steps // 4, 1), ckpt_dir=args.ckpt_dir))

    with jax.set_mesh(mesh):
        state = loop.maybe_restore(state)
        t0 = time.time()
        state = loop.run(state)
        dt = time.time() - t0

    if engine is not None and engine.last_sync_program() is not None:
        # the compiled switch program gradient_sync actually ran: the
        # Coalesce buckets and the ExecutionPlan wave structure per stage
        compiled_sync = engine.last_sync_program()
        print("\ngradient-sync switch program "
              f"(analytic {compiled_sync.program_time() * 1e6:.1f}us/sync):")
        print(compiled_sync.explain())

    first = loop.metrics_log[0]["nll"]
    last = loop.metrics_log[-1]["nll"]
    print("\nstep,nll,accuracy")
    for m in loop.metrics_log:
        print(f"{m['step']},{m['nll']:.4f},{m['accuracy']:.4f}")
    toks = args.steps * args.batch * args.seq
    print(f"\n{args.steps} steps in {dt:.1f}s "
          f"({toks / dt:.0f} tok/s); nll {first:.3f} → {last:.3f} "
          f"(floor {stream.entropy():.3f})")
    if engine is not None and engine.compressed:
        params = sum(int(np.prod(p.shape))
                     for p in jax.tree.leaves(state.params))
        print(f"wire per sync: f32 ring {2 * 4 * params / 1e6:.1f} MB-eq "
              f"→ int16-partials {2 * 2 * params / 1e6:.1f} MB-eq "
              f"(+1/256 scales) — 2.0x reduction, EF-exact")
    bar = 0.5 if args.steps >= 200 else 0.1
    assert last < first - bar, \
        f"training failed to descend ({first:.3f} -> {last:.3f})"
    print("OK")


if __name__ == "__main__":
    main()
