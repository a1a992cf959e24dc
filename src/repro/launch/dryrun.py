import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# The three lines above MUST run before any other import (jax locks the
# device count at first backend init).  Everything below may import jax.

"""Multi-pod dry-run: lower + compile every (arch × shape) on the
production meshes and record memory/cost/collective analyses.

    python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
    python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k --multi-pod
    python -m repro.launch.dryrun --all --out results/dryrun   # orchestrates
                                                               # subprocesses

Single-cell mode prints ``memory_analysis()`` / ``cost_analysis()`` (proving
the program fits and giving the roofline terms) and writes a JSON record.
``--all`` runs each cell in its own subprocess so one pathological cell
cannot take down the sweep, and aggregates per-cell JSONs.
"""

import argparse
import json
import subprocess
import sys
import time
import traceback


def _probe_costs(compiled) -> dict:
    from repro.roofline import analysis
    cost = compiled.cost_analysis()
    coll = analysis.collective_bytes(compiled.as_text())
    return {"flops": float(cost.get("flops", 0.0)),
            "hbm_bytes": float(cost.get("bytes accessed", 0.0)),
            "coll_bytes": float(coll["total_bytes"])}


def run_cell(arch: str, shape: str, multi_pod: bool, out_path: str | None,
             *, microbatches=None, remat=None, skip_probes=False,
             extra_config=None) -> dict:
    import jax
    from repro.launch import cells
    from repro.launch.mesh import make_production_mesh
    from repro.launch.shapes import SHAPES
    from repro.roofline import analysis
    from repro import configs as cfgs

    # ---- 1. full production artifact (rolled scans): proves the sharding
    # is coherent at 256/512 chips and that memory fits.
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    built = cells.build_cell(arch, shape, mesh, microbatches=microbatches,
                             remat=remat, extra_config=extra_config)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = built.lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    print(f"== {arch} × {shape} × {built.mesh_desc} ==")
    print("memory_analysis:", mem)                 # proves it fits
    cost = compiled.cost_analysis()
    print("cost_analysis (rolled): flops={flops:.3e} bytes={ba:.3e}".format(
        flops=float(cost.get("flops", 0)),
        ba=float(cost.get("bytes accessed", 0))))

    # ---- 2. linear probes (unrolled): exact per-device roofline counts.
    # Single-pod only (the roofline table is single-pod per the spec);
    # multi-pod runs are the sharding proof, not the perf model.
    record: dict = {
        "arch": arch, "shape": shape, "mesh": built.mesh_desc,
        "multi_pod": multi_pod,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory_analysis": {
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
        },
        "status": "ok",
    }

    if not multi_pod and not skip_probes:
        cfg = cfgs.get(arch)
        plen, rlen, n_periods = cells.probe_layer_counts(cfg)
        kind = SHAPES[shape].kind
        mb_cell = (microbatches or cells.TRAIN_MICROBATCHES.get(
            arch, cells.TRAIN_MICROBATCHES["default"])) \
            if kind == "train" else 1
        ladder = [(1, 1), (2, 1)] + ([(1, 2), (2, 2)]
                                     if kind == "train" else [])
        costs = {}
        for periods, mb in ladder:
            tp = time.time()
            probe = cells.build_probe(arch, shape, mesh, periods=periods,
                                      microbatches=mb,
                                      extra_config=extra_config)
            pc = probe.lowered.compile()
            costs[(periods, mb)] = _probe_costs(pc)
            print(f"probe(p={periods}, mb={mb}): "
                  f"flops={costs[(periods, mb)]['flops']:.3e} "
                  f"({time.time() - tp:.1f}s)")
            del probe, pc
        composed = cells.compose_probe_costs(
            costs, n_periods=n_periods, mb_cell=mb_cell, kind=kind)
        chips = 256
        roof = analysis.Roofline(
            arch=arch, shape=shape, mesh=built.mesh_desc, chips=chips,
            flops=composed["flops"], hbm_bytes=composed["hbm_bytes"],
            coll_bytes=composed["coll_bytes"],
            coll_detail={"probe_raw": {f"{p}x{m}": c
                                       for (p, m), c in costs.items()}},
            model_flops=analysis.model_flops_for(arch, shape),
            per_device_bytes=record["memory_analysis"]["temp_bytes"])
        record.update(roof.to_dict())
        record["probe_composition"] = {
            "n_periods": n_periods, "period_len": plen, "rem_len": rlen,
            "mb_cell": mb_cell}
        print(f"bottleneck={record['bottleneck']} "
              f"t_comp={record['t_compute_s']:.4f}s "
              f"t_mem={record['t_memory_s']:.4f}s "
              f"t_coll={record['t_collective_s']:.4f}s "
              f"useful={record['useful_flops_ratio']:.3f}")

    if out_path:
        with open(out_path, "w") as f:
            json.dump(record, f, indent=1)
    print(f"(lower {t_lower:.1f}s compile {t_compile:.1f}s)")
    return record


def run_all(out_dir: str, multi_pod_too: bool = True,
            timeout: int = 2400) -> None:
    from repro.launch.shapes import all_cells, applicable

    os.makedirs(out_dir, exist_ok=True)
    results = []
    jobs = []
    for arch, shape in all_cells():
        ok, reason = applicable(arch, shape)
        meshes = [False] + ([True] if multi_pod_too else [])
        if not ok:
            for mp in meshes:
                results.append({"arch": arch, "shape": shape,
                                "multi_pod": mp, "status": reason})
            continue
        for mp in meshes:
            jobs.append((arch, shape, mp))

    for arch, shape, mp in jobs:
        tag = f"{arch}__{shape}__{'mp' if mp else 'sp'}"
        out_path = os.path.join(out_dir, tag + ".json")
        if os.path.exists(out_path):
            with open(out_path) as f:
                results.append(json.load(f))
            print(f"[cached] {tag}")
            continue
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               "--arch", arch, "--shape", shape, "--out", out_path]
        if mp:
            cmd.append("--multi-pod")
        print(f"[run] {tag}", flush=True)
        try:
            proc = subprocess.run(cmd, capture_output=True, text=True,
                                  timeout=timeout)
            if proc.returncode == 0 and os.path.exists(out_path):
                with open(out_path) as f:
                    results.append(json.load(f))
            else:
                err = (proc.stderr or "")[-2000:]
                results.append({"arch": arch, "shape": shape,
                                "multi_pod": mp, "status": "FAIL",
                                "error": err})
                print(f"[FAIL] {tag}\n{err}", flush=True)
        except subprocess.TimeoutExpired:
            results.append({"arch": arch, "shape": shape, "multi_pod": mp,
                            "status": "TIMEOUT"})
            print(f"[TIMEOUT] {tag}", flush=True)

    with open(os.path.join(out_dir, "summary.json"), "w") as f:
        json.dump(results, f, indent=1)
    n_ok = sum(1 for r in results if r.get("status") == "ok")
    n_skip = sum(1 for r in results if str(r.get("status", "")).startswith("SKIP"))
    n_bad = len(results) - n_ok - n_skip
    print(f"\n== dry-run sweep: {n_ok} ok, {n_skip} skipped, {n_bad} failed "
          f"of {len(results)} cell×mesh combos ==")
    if n_bad:
        sys.exit(1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--remat", default=None)
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--extra", default=None,
                    help="JSON dict of ModelConfig overrides "
                         "(perf-iteration lever)")
    args = ap.parse_args()
    if args.all:
        run_all(args.out or "results/dryrun",
                multi_pod_too=not args.single_pod_only)
    else:
        try:
            extra = json.loads(args.extra) if args.extra else None
            run_cell(args.arch, args.shape, args.multi_pod, args.out,
                     microbatches=args.microbatches, remat=args.remat,
                     extra_config=extra)
        except Exception:
            traceback.print_exc()
            sys.exit(1)


if __name__ == "__main__":
    main()
