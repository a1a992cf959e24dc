"""Run one benchmark cell on the accelerator and print one JSON line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  Its model
configuration (``bench/configs/<config>.json``) names an architecture
(``bench/arch/<architecture>.py`` for the program's side,
``bench/ref/<architecture>.py`` for the plain reference); its traffic
(``bench/traffic/<traffic>.json``) names a loop kind
(``bench/loops/<kind>.py``); its correctness limits are in
``bench/limits/<cell>.json``; each per-layer metric is read by
``bench/metrics/<metric>.py``.

Without an accelerator, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.  ``--trace 0`` prints the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics from a profiler
trace of a short window.  Either way the timed path's output is compared
with the plain reference after the window, and each number compared is
printed beside its limit, last on stderr and last in the result line.
JAX's compilation cache is ``.jax_cache/`` in the checkout.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


class Skip(Exception):
    """The run cannot be made here (no accelerator, too few chips, a
    checkout without the program)."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    def __init__(self, root: Path, name: str):
        self.root = root
        spec = load_json(root / "BENCHMARK.json")
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                             f"known: {sorted(cells)}")
        self.spec = spec
        self.name = name
        self.work = cells[name]
        self.chips = self.work["chips"]
        cfgs = {c["name"]: c for c in spec["configs"]}
        self.config = load_json(root / cfgs[self.work["config"]]["file"])
        self.traffic = load_json(
            root / "bench" / "traffic" / f"{self.work['traffic']}.json")
        path = root / "bench" / "limits" / f"{name}.json"
        self.limits = load_json(path) if path.exists() else {}

    def _applies(self, metric: dict, reported: set) -> bool:
        if "workloads" in metric:
            return self.name in metric["workloads"]
        return metric["moves"] in reported if "moves" in metric else True

    def end_to_end(self) -> list[dict]:
        return [m for m in self.spec["end_to_end"]
                if self._applies(m, set())]

    def per_layer(self) -> list[dict]:
        reported = {m["name"] for m in self.end_to_end()}
        return [m for m in self.spec["per_layer"]
                if self._applies(m, reported)]

    def module(self, package: str, name: str):
        return importlib.import_module(f"bench.{package}.{name}")

    def reader(self, metric: str):
        path = self.root / "bench" / "metrics" / f"{metric}.py"
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{metric.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


def peaks(root: Path, kind: str) -> dict:
    table = load_json(root / "bench" / "peaks.json")
    if kind not in table:
        raise ValueError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def devices_for(chips: int, require_accelerator: bool):
    import jax
    devs = jax.devices()
    d = devs[0]
    print(f"device platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", file=sys.stderr, flush=True)
    if require_accelerator and d.platform == "cpu":
        raise Skip("no accelerator found; nothing was run")
    if len(devs) < chips:
        raise Skip(f"the cell needs {chips} chips, found {len(devs)}")
    return devs


def setup_cache(root: Path) -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (over ``$JAX_COMPILATION_CACHE_DIR``, which may point
    outside it)."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    # every program, however quick to compile, so that only a cell's
    # first run in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir


class CompileCounter:
    """Counts executables built (compiled or loaded from the cache)."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.n = 0
        self._event = dispatch.BACKEND_COMPILE_EVENT
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self._event:
            self.n += 1

    def close(self):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


class Run:
    """What a loop hands back and the readers read."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 devices, peak: dict, log, control: tuple = (),
                 tp_hook: str = "compiled"):
        self.cell = cell
        self.config = cell.config
        self.traffic = cell.traffic
        self.chips = cell.chips
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        # precisions of the controls to read too (the reference one
        # precision below the configuration's, in the program's place)
        self.control = tuple(control)
        self.devices = devices[:cell.chips]
        self.peak = peak
        self.log = log
        self.trace_dir = str(cell.root / ".bench_trace" / cell.name)
        self.arch = cell.module("arch", cell.config["architecture"])
        self.ref = cell.module("ref", cell.config["architecture"])
        self.compiles = CompileCounter()
        self.tp_hook = tp_hook
        # filled by the loop
        self.metrics: dict = {}        # end-to-end values
        self.stats: dict = {}          # counts the readers read
        self.view = None               # TraceView of a traced run
        self.checks: dict = {}         # name -> value compared
        self.attempted = 0
        self.failed = 0
        self.memory_peak_bytes = 0
        self.compiles_in_window = 0

    def read_memory_peak(self) -> None:
        """``peak_bytes_in_use`` of the fullest of the run's chips.  The
        runtime counts the buffers it holds (parameters, state, cache,
        outputs); a compiled program's scratch is not among them, so the
        whole memory_stats of each chip goes to the log."""
        stats = [d.memory_stats() or {} for d in self.devices]
        for d, s in zip(self.devices, stats):
            self.log(f"memory_stats {d}: {s}")
        self.memory_peak_bytes = max(
            int(s.get("peak_bytes_in_use", 0)) for s in stats)


def check_lines(run: Run) -> tuple[bool, dict]:
    """Each number compared beside its limit; correct when every limit
    holds (a missing limit never holds).  A number its limits file marks
    ``"compared": false`` is only logged."""
    out, ok = {}, True
    for name, value in run.checks.items():
        entry = run.cell.limits.get(name, {})
        if entry.get("compared", True) is False:
            run.log(f"reading {name}: {value} (not compared)")
            continue
        lim = entry.get("limit")
        good = lim is not None and value is not None and value <= lim
        ok = ok and good
        out[name] = {"value": value, "limit": lim}
    return ok and bool(out), out


def execute(cell: Cell, seed: int, seconds: float, trace: bool, devs,
            peak: dict, log, *, keep_trace=None, control: tuple = (),
            tp_hook: str = "compiled",
            t_start: float = T_START) -> tuple[Run, dict]:
    """Drive one run of ``cell``; returns it with its result line."""
    d0 = devs[0]
    run = Run(cell, seed, seconds, trace, devs, peak, log, control, tp_hook)
    try:
        loop = cell.module("loops", cell.traffic["kind"])
        loop.run(run, t_start=t_start, keep_trace=keep_trace)
    finally:
        run.compiles.close()
    log(f"compilations inside the window: {run.compiles_in_window}")

    metrics = {}
    if trace:
        for m in cell.per_layer():
            value = cell.reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.end_to_end():
            metrics[m["name"]] = {"value": run.metrics[m["name"]],
                                  "unit": m["unit"]}
    correct, checks = check_lines(run)
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs),
              "memory_peak_bytes": run.memory_peak_bytes}
    result = {"correct": correct, "attempted": run.attempted,
              "failed": run.failed, "metrics": metrics, "device": device}
    if trace and run.view is not None:
        from bench import xplane
        device["busy_s"] = sum(xplane.busy_s(run.view, d)
                               for d in run.view.ops) / max(
                                   len(run.view.ops), 1)
        device["window_s"] = run.view.window_s
        bd = xplane.breakdown(run.view)
        if bd is not None:
            result["breakdown"] = bd
    result["checks"] = checks
    return run, result


def prepare(argv_cell: str, root: Path, require_accelerator: bool,
            cache: bool):
    """(cell, devices, peaks, log) or raise :class:`Skip`."""
    def log(msg: str) -> None:
        print(f"[{time.perf_counter() - T_START:8.2f}s] {msg}",
              file=sys.stderr, flush=True)

    cell = Cell(root, argv_cell)
    if not (root / "src" / "repro").is_dir():
        raise Skip(f"no program under {root / 'src'}")
    if str(root / "src") not in sys.path:
        sys.path.insert(0, str(root / "src"))
    devs = devices_for(cell.chips, require_accelerator)
    if cache:
        log(f"compile cache {setup_cache(root)}")
    peak = peaks(root, devs[0].device_kind) if require_accelerator else \
        {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    return cell, devs, peak, log


def main(argv=None, *, root: Path = ROOT, require_accelerator: bool = True,
         cache: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="also copy the raw profiler trace to this "
                         "directory")
    ap.add_argument("--tp-hook", default="compiled",
                    choices=("compiled", "xla"),
                    help="tensor-parallel decode transport of a serving "
                         "cell on several chips: the compiled switch "
                         "programs (the cell as defined) or XLA's "
                         "built-in collectives, for comparison")
    args = ap.parse_args(argv)
    try:
        cell, devs, peak, log = prepare(args.workload, root,
                                        require_accelerator, cache)
    except Skip as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    run, result = execute(cell, args.seed, args.seconds, bool(args.trace),
                          devs, peak, log, keep_trace=args.keep_trace,
                          tp_hook=args.tp_hook)
    del run
    gc.collect()
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[0] = str(ROOT)      # import ``bench`` as a package
    sys.exit(main())
