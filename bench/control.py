"""Readings that set a cell's correctness limits, at the cell's own size.

    python bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3

For each seed, in one process: one run of the cell as ``bench/run.py``
makes it (set-up, window, the timed path's output against the plain
reference), plus the control, the reference one precision below the
bfloat16 the configuration states (int8 or fp8 operands) put in the
program's place; for training also the fault of a step that leaves half
of its batch out.  Each control and fault is judged as the program is:
its readings against the limits of ``bench/limits/<cell>.json``, so it
has to come out not correct.  Prints one JSON line per seed with every
number and each verdict, and a summary line: the largest reading of the
program and the smallest of the control and of each fault, per number,
and the verdicts of every seed.  The benchmark's own runs never run the
control.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

if __name__ == "__main__":
    sys.path[0] = str(Path(__file__).resolve().parent.parent)

from bench import run as harness  # noqa: E402


def verdicts(readings: dict, limits: dict) -> dict:
    """``correct`` of the program and of each control or fault (the
    suffix of its readings' names), every compared number held against
    the limit of the program's own number of that name."""
    out = {}
    for who in ["program"] + sorted({n.split(".", 1)[1] for n in readings
                                     if "." in n}):
        checks = {}
        for name, entry in limits.items():
            if entry.get("compared", True) is False:
                continue
            key = name if who == "program" else f"{name}.{who}"
            if key in readings:
                checks[name] = {"value": readings[key],
                                "limit": entry.get("limit")}
        ok = bool(checks) and all(
            c["value"] is not None and c["limit"] is not None
            and c["value"] <= c["limit"] for c in checks.values())
        out[who] = {"correct": ok, "checks": checks}
    return out


def main(argv=None, *, root: Path = harness.ROOT,
         require_accelerator: bool = True, cache: bool = True) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", nargs="+", default=["int8", "fp8"],
                    choices=["int8", "fp8"])
    args = ap.parse_args(argv)
    cell, devs, peak, log = harness.prepare(args.workload, root,
                                            require_accelerator, cache)
    readings, verdict_runs = [], []
    for seed in args.seeds:
        t0 = time.perf_counter()
        run, result = harness.execute(cell, seed, args.seconds, False,
                                      devs, peak, log,
                                      control=args.controls,
                                      t_start=t0)
        judged = verdicts(run.checks, cell.limits)
        for who, v in judged.items():
            log(f"{who}: correct {v['correct']} {v['checks']}")
        line = {"seed": seed, "readings": dict(run.checks),
                "verdicts": {k: v["correct"] for k, v in judged.items()},
                "metrics": {k: v["value"]
                            for k, v in result["metrics"].items()}}
        print(json.dumps(line), flush=True)
        readings.append(run.checks)
        verdict_runs.append(line["verdicts"])
        del run
    summary = {}
    for name in readings[0]:
        vals = [r[name] for r in readings if r.get(name) is not None]
        low = "." not in name          # the program's own reading
        summary[name] = (max if low else min)(vals) if vals else None
    summary["verdicts"] = {who: [v.get(who) for v in verdict_runs]
                           for who in verdict_runs[0]}
    print(json.dumps({"summary": summary, "seeds": args.seeds}), flush=True)
    return summary


if __name__ == "__main__":
    main()
