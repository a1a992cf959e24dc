"""Device time by program scope, and the program's host spans, on the
clock of one profiler trace.

The program names its device regions with ``jax.named_scope``:
``decode.attn``, ``decode.kv_cache``, ``decode.mlp`` and ``decode.head``
in the decode tick (the layer scan, whose slicing and stacking move the
stacked cache, runs under ``decode.kv_cache``); ``train.fwd_bwd``,
``train.grad_sync`` and ``train.optimizer`` in the train step; and
``acis.<kind>.s<i>`` around each stage of a compiled switch program.
Its host phases are ``repro.obs.span`` regions (``serve.admit`` ...
``serve.sample``), written into the trace as annotations.

A device operation in the trace carries only its HLO instruction name
(``%fusion.12 = ...``) and, on the ``XLA Modules`` line, the program it
ran in.  Its scope comes from that program's compiled text, where every
instruction's ``metadata={op_name=...}`` holds the scope path.  An
instruction XLA inserted (the copy of a loop's output, an asynchronous
copy) has no op_name and takes its operand's.  An operation counts for
its innermost scope.

    python bench/scopes.py --workload <cell> --seed <n> [--seconds 3]

makes one traced run of a cell through the harness (``bench/run.py``'s
set-up, loop and check) with ``repro.obs.recording()`` installed from
the start, so that every compile is timed, takes the compiled text of
the program the loop built, and prints one JSON line: device ms per
tick or step by scope on the busiest device, the program's spans, the
seconds of set-up's compiles and persistent-cache loads, and the
longest idle gaps, each named by the innermost span, the program's or
the harness's.
"""

from __future__ import annotations

import argparse
import bisect
import contextlib
import dataclasses
import glob
import json
import os
import re
import sys
import tempfile
from pathlib import Path
from typing import Iterator, Optional

if __name__ == "__main__":
    sys.path[0] = str(Path(__file__).resolve().parent.parent)

from bench import xplane  # noqa: E402

# name prefixes of the program's scopes and of its host spans
SCOPES = ("decode.", "train.", "acis.")
SPANS = ("serve.", "train.")
INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+) = (.*)$")
OP_NAME = re.compile(r'op_name="([^"]*)"')
# references to called computations, which are not operands
CALLED = re.compile(r"(?:calls|to_apply|body|condition|"
                    r"branch_computations)=\{?[^,]*")
MODULE_ID = re.compile(r"\(\d+\)$")


def innermost(path: Optional[str]) -> Optional[str]:
    """The last component of an op_name path that is a program scope."""
    for part in reversed((path or "").split("/")):
        if part.startswith(SCOPES):
            return part
    return None


def module_scopes(hlo_text: str) -> tuple[str, dict]:
    """(module name, {instruction name: innermost scope or None}) of a
    compiled module's text; an instruction without an op_name takes the
    scope of its first operand that has one."""
    module = hlo_text.split(",", 1)[0].split()[-1]
    own, operands = {}, {}
    for line in hlo_text.splitlines():
        m = INSTR.match(line)
        if not m:
            continue
        name, rest = m.groups()
        op = OP_NAME.search(rest)
        own[name] = op.group(1) if op else None
        body = CALLED.sub("", rest.split(", metadata=")[0])
        operands[name] = re.findall(r"%([^\s,(){}]+)", body)
    scopes: dict = {}

    def resolve(name: str, seen: frozenset) -> Optional[str]:
        if name in scopes:
            return scopes[name]
        if own.get(name) is not None:
            return innermost(own[name])
        for o in operands.get(name, ()):
            if o in own and o not in seen:
                s = resolve(o, seen | {name})
                if s is not None:
                    return s
        return None

    for name in own:
        scopes[name] = resolve(name, frozenset())
    return module, scopes


@dataclasses.dataclass
class ProgramView(xplane.TraceView):
    """A :class:`bench.xplane.TraceView` with the program's host spans
    (``serve.*``, ``train.*``) and, per device plane, the intervals of
    the programs that ran (``XLA Modules``, named without their id)."""

    spans: list = dataclasses.field(default_factory=list)
    modules: dict = dataclasses.field(default_factory=dict)

    def module_at(self, dev: str, t: float) -> Optional[str]:
        mods = self.modules.get(dev, [])
        i = bisect.bisect_right(mods, (t, float("inf"), "")) - 1
        return mods[i][2] if i >= 0 and mods[i][1] > t else None


def read(trace_dir: str) -> ProgramView:
    """The harness's view of the newest trace under ``trace_dir``, with
    the program's spans and the module intervals added."""
    from jax.profiler import ProfileData

    base = xplane.read(trace_dir)
    path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    spans, modules = [], {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name in base.ops:
            modules[plane.name] = sorted(
                (ev.start_ns, ev.start_ns + ev.duration_ns,
                 MODULE_ID.sub("", ev.name))
                for line in plane.lines if line.name == "XLA Modules"
                for ev in line.events)
        elif plane.name.startswith("/host:"):
            spans.extend((ev.start_ns, ev.start_ns + ev.duration_ns,
                          ev.name) for line in plane.lines
                         for ev in line.events
                         if ev.name.startswith(SPANS))
    return ProgramView(ops=base.ops, host=base.host, window=base.window,
                       collectives=base.collectives, spans=spans,
                       modules=modules)


def scoped_ops(view: ProgramView, maps: dict, dev: str) -> list:
    """[(seconds of self time, op name, label)] of ``dev``'s operations
    in the window.  The label is the op's innermost scope; ``unscoped``
    for an op of a mapped program without one; ``module:<name>`` for an
    op of a program with no map; ``unmapped`` for an op whose name its
    program's map lacks."""
    out = []
    for s, e, n in view.in_window(dev):
        mod = view.module_at(dev, s)
        if mod not in maps:
            label = f"module:{mod}"
        elif n not in maps[mod]:
            label = "unmapped"
        else:
            label = maps[mod][n] or "unscoped"
        out.append((s, e, (n, label)))
    return [(t, n, label) for t, (n, label) in xplane.self_times(out)]


def span_s(view: ProgramView, name: str) -> float:
    """Seconds inside spans named ``name`` within the window."""
    a, b = view.window
    return sum(max(0, min(e, b) - max(s, a)) for s, e, n in view.spans
               if n == name) * 1e-9


def label_gaps(view: ProgramView, top: int = 10) -> list:
    """The busiest device's longest idle gaps, each named by the
    innermost span (the program's or the harness's) the host was in at
    its midpoint."""
    dev = xplane.busiest(view)
    if dev is None:
        return []
    every = [h for h in view.host + view.spans if h[2] != "bench.window"]
    out = []
    for a, b in xplane.gaps(view.in_window(dev), view.window)[:top]:
        mid = (a + b) / 2
        inside = [h for h in every if h[0] <= mid <= h[1]]
        label = min(inside, key=lambda h: h[1] - h[0])[2] if inside \
            else "no span"
        out.append([label, (b - a) * 1e-9])
    return out


CACHE_MOVING = re.compile(r"(^|_)(copy|dynamic-slice|dynamic-update-slice)"
                          r"(_|\.|$)")


def report(view: ProgramView, maps: dict, units: int) -> dict:
    """Per-unit (tick or step) milliseconds of the window: device self
    time by label on the busiest device, the program's spans, and what
    the per-layer metrics planned on them would read (``metrics``)."""
    dev = xplane.busiest(view)
    per = lambda s: s * 1e3 / units  # noqa: E731
    out: dict = {"units": units, "window_s": view.window_s}
    names = sorted({n for _, _, n in view.spans})
    out["spans_ms"] = {n: per(span_s(view, n)) for n in names}
    if dev is None:
        return out
    ops = scoped_ops(view, maps, dev)
    by: dict = {}
    for t, _, label in ops:
        by[label] = by.get(label, 0.0) + t
    total = sum(by.values())
    out["device"] = dev
    out["busy_ms"] = per(xplane.busy_s(view, dev))
    out["scopes_ms"] = {k: per(v) for k, v in
                        sorted(by.items(), key=lambda kv: -kv[1])}
    scoped = sum(v for k, v in by.items() if k.startswith(SCOPES))
    out["unscoped_share"] = 100.0 * (1 - scoped / total) if total else None
    out["collective_ms"] = per(max(
        (xplane.union_ns(view.in_window(d, "collectives")) * 1e-9
         for d in view.collectives), default=0.0))
    out["cache_moving_ms"] = per(sum(
        t for t, n, _ in ops if CACHE_MOVING.search(re.sub(r"\.\d+$", "", n))))
    acis = sum(v for k, v in by.items() if k.startswith("acis."))
    out["metrics"] = {
        "serve.logits_pull_ms_per_tick": per(span_s(view,
                                                    "serve.logits_pull")),
        "serve.kv_cache_ms_per_tick": per(by.get("decode.kv_cache", 0.0)),
        "serve.transport_ms_per_tick": per(acis),
        # the sync's own ops and the switch-program stages it runs
        "train.sync_ms_per_step": per(by.get("train.grad_sync", 0.0) + acis),
    }
    out["idle_gaps"] = label_gaps(view)
    return out


class Spy:
    """Stands in for a jitted program and keeps the abstract arguments
    of its first call, so that its compiled text can be had later."""

    def __init__(self, fn):
        self.fn, self.args = fn, None
        # the jitted program itself, under a host-side wrapper if any
        self.jitted = fn if hasattr(fn, "lower") else fn.__wrapped__

    def __call__(self, *args):
        if self.args is None:
            import jax
            self.args = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=a.sharding), args)
        return self.fn(*args)

    def text(self) -> Optional[str]:
        if self.args is None:
            return None
        return self.jitted.lower(*self.args).compile().as_text()


@contextlib.contextmanager
def spy_on(loop) -> Iterator[list]:
    """Inside the ``with``, the program that the loop module's ``build``
    makes (the engine's decode, or the train step) is wrapped in a
    :class:`Spy`; yields the list that receives it."""
    spies, build = [], loop.build

    def wrapped(run):
        out = build(run)
        if hasattr(out[0], "_decode"):          # (engine, recorder)
            out[0]._decode = Spy(out[0]._decode)
            spies.append(out[0]._decode)
            return out
        spies.append(Spy(out[0]))               # (step, state, ...)
        return (spies[-1],) + tuple(out[1:])

    loop.build = wrapped
    try:
        yield spies
    finally:
        loop.build = build


def main(argv=None, *, root: Optional[Path] = None,
         require_accelerator: bool = True,
         cache: bool = True) -> Optional[dict]:
    """The report, or None where the run cannot be made here."""
    from bench import run as harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--keep", default=None,
                    help="directory for the raw trace (else one under the "
                         "checkout's .bench_trace/, deleted)")
    args = ap.parse_args(argv)
    root = harness.ROOT if root is None else root
    try:
        cell, devs, peak, log = harness.prepare(args.workload, root,
                                                require_accelerator, cache)
    except harness.Skip as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return None
    from repro import obs          # importable once prepare found src/

    scratch = root / ".bench_trace"
    scratch.mkdir(exist_ok=True)
    with obs.recording() as rec, \
            tempfile.TemporaryDirectory(dir=scratch) as tmp:
        keep = args.keep or str(Path(tmp) / "trace")
        with spy_on(cell.module("loops", cell.traffic["kind"])) as spies:
            run, result = harness.execute(cell, args.seed, args.seconds,
                                          True, devs, peak, log,
                                          keep_trace=keep)
        maps = dict(module_scopes(t) for t in
                    (s.text() for s in spies) if t is not None)
        units = run.stats.get("ticks") or run.stats.get("steps")
        out = report(read(keep), maps, units)
    # the compiles of set-up: those that ended before the window opened
    opened = harness.T_START + run.stats["setup_s"]
    compiles = [f for n, f in rec.events
                if n == "compile" and f["t_end"] <= opened]
    out.update(cell=args.workload, seed=args.seed, correct=result["correct"],
               device=result["device"], per_layer=result["metrics"],
               setup_s=run.stats["setup_s"], programs=sorted(maps),
               compile_s=sum(f["s"] for f in compiles),
               compiles=len(compiles),
               slowest_compiles=sorted(compiles, key=lambda f: -f["s"])[:5])
    print(json.dumps(out), flush=True)
    return out


if __name__ == "__main__":
    sys.exit(0 if main() is not None else 2)
