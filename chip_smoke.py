"""Bring-up smoke run of the main path on a TPU, through the user entry points.

    python chip_smoke.py             # one chip: Pallas kernels, acis-100m
                                     # train step, acis-100m serving
    python chip_smoke.py --chips 4   # four chips: data-parallel gradient
                                     # sync and qwen3-8b tensor-parallel decode

Everything runs in this one process: a chip belongs to one process at a
time.  Each phase checks its result against a reference and prints its
wall time on its own line; a failed check raises, so the script exits
non-zero.  Without a TPU it exits non-zero before any phase.  The last
line of stdout is one JSON object naming the device:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

JAX's persistent compilation cache goes where ``$JAX_COMPILATION_CACHE_DIR``
says; without it, in ``.jax_cache/`` beside this file, so a second run of
the same checkout compiles nothing it compiled before.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

SEED = 0
# per-step nll agreement between a gradient-sync backend and the `xla`
# backend on identical batches: the ring sums bf16 gradients in another
# order, the int8 codec quantizes them, and AdamW turns either into a
# slightly different update — a shift of 0.2% of the ~10.4-nat loss of a
# random init is far above both and far below a broken sync
LOSS_BAND = 2e-2
# compiled vs XLA TP decode: every layer's all-reduce sums bf16 partials
# in its own order, so the logits agree to a few bf16 ulps of their scale
LOGIT_BAND = 5e-2


def check(cond: bool, what: str) -> None:
    if not cond:
        raise RuntimeError(f"check failed: {what}")


def setup_compile_cache() -> str:
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    return jax.config.jax_compilation_cache_dir


def _cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def run_phase(name: str, fn, *args, **kw) -> None:
    """Run one phase and print its wall time (compilation included)."""
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    print(f"phase {name}: {time.perf_counter() - t0:.3f} s  {out}",
          flush=True)


# ---------------------------------------------------------------------------
# kernels: every kernels/ops.py kernel, compiled, against its ref.py oracle
# ---------------------------------------------------------------------------

# 1 MiB bucket packs of acis-100m gradient leaves at ragged offsets: the
# final norm (768), the stacked ln1/ln2 (12 x 768), one layer's wk
# (768 x 256) slices, and a ragged remainder short of the arena's end
PACK_CASES = (
    (jnp.float32, 262144, (768, 9216, 9216, 196608, 46000)),
    (jnp.bfloat16, 524288, (768, 9216, 9216, 196608, 196608, 111111)),
)


def _max_err(got, want) -> float:
    return float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                 - want.astype(jnp.float32))))


def _close(name, got, want, tol):
    err = _max_err(got, want)
    scale = float(jnp.max(jnp.abs(want.astype(jnp.float32)))) or 1.0
    check(got.shape == want.shape and got.dtype == want.dtype,
          f"{name}: {got.shape}/{got.dtype} vs {want.shape}/{want.dtype}")
    check(err <= tol * scale, f"{name}: max err {err} > {tol} x {scale}")
    return err


def _same(name, got, want):
    check(got.shape == want.shape and got.dtype == want.dtype,
          f"{name}: {got.shape}/{got.dtype} vs {want.shape}/{want.dtype}")
    check(bool(jnp.all(got == want)), f"{name}: not bit-identical")


def kernel_phase(key, *, pack_cases=PACK_CASES, combine_n=4 << 20,
                 combine_ragged=3_000_001, quant_rows=4096,
                 topk_n=1 << 20, prefix_shape=(16384, 128),
                 rglru_shape=(2048, 4096), wkv_shape=(32, 512, 64)) -> str:
    """Every ``kernels/ops.py`` kernel against its ``ref.py`` oracle.
    The sizes default to real widths; ``tests/test_chip_smoke.py``
    passes small ones to rehearse the phase on the CPU, where the
    kernels interpret."""
    from repro.kernels import ops, ref

    keys = iter(jax.random.split(key, 64))

    def normal(shape, dtype=jnp.float32):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dtype)

    done = []
    for dtype, n, sizes in pack_cases:
        arena = normal((n,), dtype)
        parts = [normal((s,), dtype) for s in sizes]
        for op in (None, "add"):
            _same(f"pack_combine {dtype.__name__} op={op}",
                  ops.pack_combine(arena, *parts, op=op),
                  ref.pack_combine(arena, *parts, op=op))
        done.append(f"pack_combine[{dtype.__name__} {n}, bit-identical]")

    for dtype, n in ((jnp.float32, combine_n), (jnp.bfloat16, combine_ragged)):
        x, y = normal((n,), dtype), normal((n,), dtype)
        for name in ("add", "max", "min"):
            _same(f"combine_{name} {dtype.__name__}",
                  getattr(ops, f"combine_{name}")(x, y),
                  getattr(ref, f"combine_{name}")(x, y))
        tol = 1e-6 if dtype == jnp.float32 else 1e-2
        err = _close(f"combine_mac {dtype.__name__}",
                     ops.combine_mac(x, y, 0.5), ref.combine_mac(x, y, 0.5),
                     tol)
        done.append(f"combine[{dtype.__name__} {n}: add/max/min "
                    f"bit-identical, mac err {err:.1e}]")

    qa = jax.random.randint(next(keys), (quant_rows, 256), -127, 128,
                            jnp.int32).astype(jnp.int8)
    qb = jax.random.randint(next(keys), (quant_rows, 256), -127, 128,
                            jnp.int32).astype(jnp.int8)
    sa = jax.random.uniform(next(keys), (quant_rows,), minval=1e-3,
                            maxval=1e-1)
    sb = jax.random.uniform(next(keys), (quant_rows,), minval=1e-3,
                            maxval=1e-1)
    (q, s), (q_ref, s_ref) = (ops.quant_combine(qa, sa, qb, sb),
                              ref.quant_combine(qa, sa, qb, sb))
    _close("quant_combine scales", s, s_ref, 1e-6)
    # a requantized lane may round the other way at an exact .5 tie
    off = int(jnp.sum(q != q_ref))
    check(_max_err(q, q_ref) <= 1, "quant_combine: int8 lanes differ by >1")
    done.append(f"quant_combine[{quant_rows}x256, {off} lanes off by 1]")

    k = max(topk_n // 100, 1)
    dense = normal((topk_n,))
    idx = jax.random.randint(next(keys), (k,), 0, topk_n, jnp.int32)
    vals = normal((k,))
    # the kernel scatters through a one-hot MXU matmul
    err = _close("topk_accumulate", ops.topk_accumulate(dense, idx, vals),
                 ref.topk_accumulate(dense, idx, vals), 1e-2)
    done.append(f"topk_accumulate[{topk_n} k={k}, err {err:.1e}]")

    x = normal(prefix_shape)
    err = _close("prefix_sum", ops.prefix_sum(x), ref.prefix_sum(x), 1e-4)
    done.append(f"prefix_sum[{prefix_shape}, err {err:.1e}]")

    a = jax.nn.sigmoid(normal(rglru_shape))
    b = normal(rglru_shape)
    err = _close("rglru_scan", ops.rglru_scan(a, b), ref.rglru_scan(a, b),
                 1e-4)
    done.append(f"rglru_scan[{rglru_shape}, err {err:.1e}]")

    h, t, d = wkv_shape
    r, kk, v = (0.5 * normal((h, t, d)) for _ in range(3))
    w = jax.nn.sigmoid(normal((h, t, d)) + 2.0)
    u = 0.5 * normal((h, d))
    o, s_final = ops.rwkv6_recurrence(r, kk, v, w, u)
    o_ref, s_ref = jax.vmap(ref.rwkv6_recurrence)(r, kk, v, w, u)
    err = max(_close("rwkv6_recurrence out", o, o_ref, 1e-4),
              _close("rwkv6_recurrence state", s_final, s_ref, 1e-4))
    done.append(f"rwkv6_recurrence[{wkv_shape}, err {err:.1e}]")
    return "compiled and matched: " + ", ".join(done)


# ---------------------------------------------------------------------------
# training: build_train_step_acis on a `data` mesh
# ---------------------------------------------------------------------------

def train_losses(cfg, mesh, backend: str, batches, *, microbatches: int,
                 hlo: bool = False):
    """Per-step nll of ``build_train_step_acis`` on ``batches``; acis
    backends run the Pallas kernels, persistent arenas and donation.
    With ``hlo`` also returns the compiled step's HLO text."""
    from repro.core.api import CollectiveConfig, CollectiveEngine
    from repro.models import Model
    from repro.train import optimizer as opt_lib
    from repro.train.step import build_train_step_acis, init_state

    model = Model(cfg)
    optimizer = opt_lib.adamw(3e-4)
    acis = backend != "xla"
    engine = CollectiveEngine(
        CollectiveConfig(backend=backend, use_kernels=acis),
        inner_axis="data")
    step = build_train_step_acis(model, optimizer, mesh, engine, donate=acis,
                                 microbatches=microbatches)
    with jax.set_mesh(mesh):
        state = init_state(model, optimizer, jax.random.key(SEED), engine,
                           mesh=mesh, arenas=acis, microbatches=microbatches)
        check(not acis or state.sync_arenas is not None,
              f"{backend}: no sync arenas")
        text = step.__wrapped__.lower(state, batches[0]).compile().as_text() \
            if hlo else None
        losses = []
        for batch in batches:
            state, metrics = step(state, batch)
            losses.append(float(metrics["nll"]))
    check(all(np.isfinite(losses)), f"{backend}: non-finite loss {losses}")
    return (losses, text) if hlo else losses


def make_batches(cfg, mesh, *, batch: int, seq: int, steps: int):
    from repro.data.pipeline import BigramStream, DataConfig

    stream = BigramStream(DataConfig(vocab=cfg.vocab, seq_len=seq,
                                     global_batch=batch, seed=SEED))
    sharding = NamedSharding(mesh, P("data"))
    return [{"tokens": jax.device_put(stream.batch(i)["tokens"], sharding)}
            for i in range(steps)]


# acis-100m trains at remat="none": the f32 attention probabilities of 8 x
# 1024 tokens alone take 9 GiB, and one 8-sequence pass overflows the
# 16 GB of a v5e by 0.36 GB (ahead-of-time compile), so each step
# accumulates two microbatches of 4 per device
MICROBATCHES = 2


def train_phase(cfg, devices, *, batch: int = 8, seq: int = 1024,
                steps: int = 3, microbatches: int = MICROBATCHES) -> str:
    mesh = jax.sharding.Mesh(np.asarray(devices), ("data",))
    batches = make_batches(cfg, mesh, batch=batch, seq=seq, steps=steps)
    losses, text = train_losses(cfg, mesh, "acis_compressed", batches,
                                microbatches=microbatches, hlo=True)
    check("tpu_custom_call" in text,
          "acis_compressed step has no Pallas kernel in its HLO")
    xla0 = train_losses(cfg, mesh, "xla", batches[:1],
                        microbatches=microbatches)[0]
    check(abs(losses[0] - xla0) <= LOSS_BAND,
          f"step-0 nll {losses[0]} vs xla {xla0}")
    return (f"{cfg.name} acis_compressed+kernels batch {batch}x{seq} "
            f"({microbatches} microbatches): "
            f"nll {losses}, xla step-0 nll {xla0}, tpu_custom_call in HLO")


def dp_sync_phase(cfg, devices, *, batch: int = 32, seq: int = 1024,
                  steps: int = 3, microbatches: int = MICROBATCHES) -> str:
    """Data-parallel gradient sync across ``devices``: acis and
    acis_compressed against the xla backend on identical batches."""
    mesh = jax.sharding.Mesh(np.asarray(devices), ("data",))
    check(list(mesh.devices.flat) == list(devices), "mesh devices")
    batches = make_batches(cfg, mesh, batch=batch, seq=seq, steps=steps)
    ref_losses = train_losses(cfg, mesh, "xla", batches,
                              microbatches=microbatches)
    out = [f"xla nll {ref_losses}"]
    for backend in ("acis", "acis_compressed"):
        losses, text = train_losses(cfg, mesh, backend, batches,
                                    microbatches=microbatches, hlo=True)
        check("collective-permute" in text, f"{backend}: no ring in HLO")
        check("tpu_custom_call" in text, f"{backend}: no kernel in HLO")
        gap = max(abs(a - b) for a, b in zip(losses, ref_losses))
        check(gap <= LOSS_BAND, f"{backend} nll {losses} vs xla "
              f"{ref_losses}: gap {gap} > {LOSS_BAND}")
        out.append(f"{backend} nll {losses} (max gap {gap:.2e})")
    return (f"{cfg.name} on {len(devices)} chips, batch {batch}x{seq}, "
            f"band {LOSS_BAND}: " + "; ".join(out))


# ---------------------------------------------------------------------------
# serving: ServeEngine against a plain jitted decode_step loop
# ---------------------------------------------------------------------------

def _lockstep_reference(model, params, prompts, n_new: int, max_seq: int):
    """Greedy tokens of a plain jitted ``model.decode_step`` loop with one
    prompt per batch row, all rows in lockstep."""
    decode = jax.jit(model.decode_step)
    cache = model.init_cache(len(prompts), max_seq)
    prompts = np.asarray(prompts, np.int32)
    t_prompt = prompts.shape[1]
    tok = prompts[:, 0]
    out = []
    for t in range(t_prompt + n_new - 1):
        lg, cache = decode(params, jnp.asarray(tok), cache,
                           jnp.full(len(prompts), t, jnp.int32))
        if t + 1 < t_prompt:
            tok = prompts[:, t + 1]
        else:
            tok = np.asarray(jnp.argmax(lg, -1)).astype(np.int32)
            out.append(tok)
    return np.stack(out, 1)


def serve_phase(cfg, *, slots: int = 4, max_seq: int = 128,
                prompt_len: int = 16, n_new: int = 16) -> str:
    from repro.models import Model
    from repro.serve.engine import Request, ServeEngine

    model = Model(cfg)
    params = model.init(jax.random.key(SEED))
    rng = np.random.default_rng(SEED)
    # two waves of `slots` requests: the second reuses every slot
    waves = [rng.integers(0, cfg.vocab, (slots, prompt_len)).astype(np.int32)
             for _ in range(2)]
    eng = ServeEngine(model, params, slots=slots, max_seq=max_seq)
    for w, prompts in enumerate(waves):
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=w * slots + i, prompt=p,
                               max_new_tokens=n_new))
    done = eng.run_to_completion()
    check(len(done) == 2 * slots, f"{len(done)} of {2 * slots} completed")
    got = np.asarray([c.tokens for c in done]).reshape(2, slots, n_new)
    for w, prompts in enumerate(waves):
        want = _lockstep_reference(model, params, prompts, n_new, max_seq)
        check(np.array_equal(got[w], want),
              f"wave {w}: engine tokens {got[w].tolist()} vs plain "
              f"decode loop {want.tolist()}")
    return (f"{cfg.name} {2 * slots} requests x {n_new} tokens in "
            f"{eng.ticks} ticks, greedy tokens equal to the plain decode loop")


def tp_decode_phase(cfg, devices, *, slots: int = 4, max_seq: int = 128,
                    prompt_len: int = 8, n_new: int = 8) -> str:
    """``ServeCollectives`` tensor-parallel decode across ``devices``:
    parameters created sharded, the compiled hook against the xla hook."""
    from repro.models import Model
    from repro.serve.collectives import ServeCollectives
    from repro.serve.engine import Request, ServeEngine

    tp = len(devices)
    model = Model(cfg)
    sc = ServeCollectives(cfg, tp, devices=list(devices))
    check(list(sc.mesh.devices.flat) == list(devices), "tp mesh devices")
    pspecs = sc.param_specs(model.param_shapes())
    params = jax.jit(model.init, out_shardings=sc.shardings(pspecs))(
        jax.random.key(SEED))
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    per_dev = [sum(s.data.nbytes for x in jax.tree.leaves(params)
                   for s in x.addressable_shards if s.device == d)
               for d in devices]
    check(max(per_dev) < n_bytes, "a device holds the whole model")

    eng = ServeEngine(model, params, slots=slots, max_seq=max_seq,
                      collectives=sc)
    cache_sh = sc.shardings(sc.cache_specs(eng.cache))
    tok = jnp.arange(slots, dtype=jnp.int32)
    idx = jnp.zeros(slots, jnp.int32)
    fresh = lambda: jax.tree.map(jnp.copy, eng.cache)  # noqa: E731
    lg_c, _ = eng._decode(params, tok, fresh(), idx)   # the compiled hook
    lg_x, _ = sc.decode_fn(params, eng.cache, mode="xla")(
        params, tok, fresh(), idx)
    gap = _max_err(lg_c, lg_x)
    scale = float(jnp.max(jnp.abs(lg_x.astype(jnp.float32))))
    check(bool(jnp.all(jnp.isfinite(lg_c))), "non-finite logits")
    check(gap <= LOGIT_BAND * scale,
          f"first-tick logits: compiled vs xla gap {gap} > "
          f"{LOGIT_BAND} x {scale}")

    rng = np.random.default_rng(SEED)
    for i in range(slots):
        eng.submit(Request(rid=i, prompt=rng.integers(
            0, cfg.vocab, prompt_len).astype(np.int32), max_new_tokens=n_new))
    done = eng.run_to_completion()
    check(len(done) == slots and all(len(c.tokens) == n_new for c in done),
          "requests not completed")
    check(all(x.sharding == s for x, s in zip(jax.tree.leaves(eng.cache),
                                              jax.tree.leaves(cache_sh))),
          "KV cache left its TP sharding")
    return (f"{cfg.name} tp={tp}: {n_bytes / 1e9:.2f} GB of params, at most "
            f"{max(per_dev) / 1e9:.2f} GB per chip; first-tick logits "
            f"compiled vs xla max gap {gap:.3e} (scale {scale:.3e}, band "
            f"{LOGIT_BAND}); {slots} requests x {n_new} tokens in "
            f"{eng.ticks} ticks, cache still sharded")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the cross-chip paths (DP gradient "
                         "sync, qwen3-8b TP decode) on four chips")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    print(f"device platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: no TPU found; nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"found {len(devices)}", file=sys.stderr)
        return 1
    cache_dir = setup_compile_cache()
    before = _cache_entries(cache_dir)
    print(f"compile cache {cache_dir}: {before} entries", flush=True)

    from repro import configs

    if args.chips == 1:
        run_phase("kernels", kernel_phase, jax.random.key(SEED))
        run_phase("train", train_phase, configs.get("acis-100m"),
                  devices[:1])
        run_phase("serve", serve_phase, configs.get("acis-100m"))
    else:
        run_phase("dp_sync", dp_sync_phase, configs.get("acis-100m"),
                  devices[:4])
        run_phase("tp_decode", tp_decode_phase, configs.get("qwen3-8b"),
                  devices[:4])
    print(f"compile cache {cache_dir}: {before} -> "
          f"{_cache_entries(cache_dir)} entries", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
