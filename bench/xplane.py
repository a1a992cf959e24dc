"""Profiler traces: capture around a window, and reduce to intervals.

A :class:`TraceView` holds, per device plane, the intervals of the
device's operations (the ``XLA Ops`` line; operations nest, as a loop
holds its body) and of its collective operations (from that line and
``Async XLA Ops``, whose intervals overlap the compute they hide
behind), each named by its HLO instruction (``fusion.12``,
``fused_pack.1``), and the harness's own host annotations
(``bench.*``), all on the trace's clock in nanoseconds.
The per-layer readers in ``bench/metrics/`` work on it, never on the
raw protobuf, so they can be tested on a view built by hand.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil
from typing import Optional

OPS_LINE, ASYNC_LINE = "XLA Ops", "Async XLA Ops"
HLO_NAME = re.compile(r"%?([^\s=]+) = ")
# the opcode of a collective in an HLO instruction's text, whatever the
# instruction is named (``%psum.3 = bf16[...] all-reduce(...)``)
COLLECTIVE = re.compile(r"\s(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all|send|recv)"
                        r"(-start|-done)?\(")


@dataclasses.dataclass
class TraceView:
    # device plane name -> [(start_ns, end_ns, op name)]
    ops: dict
    # [(start_ns, end_ns, annotation name)] of the harness's host spans
    host: list
    # the traced window (start_ns, end_ns): the ``bench.window`` span
    window: tuple
    # device plane name -> [(start_ns, end_ns, op name)] of the
    # collective operations, synchronous and asynchronous
    collectives: dict = dataclasses.field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def in_window(self, dev: str, line: str = "ops") -> list:
        a, b = self.window
        return [(max(s, a), min(e, b), n)
                for s, e, n in getattr(self, line).get(dev, ())
                if e > a and s < b]


def union_ns(intervals) -> float:
    """Total length covered by ``[(start, end, ...)]``."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e, *_ in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, window) -> list:
    """Idle gaps [(start, end)] of ``window`` not covered by any
    interval, longest first."""
    out, t = [], window[0]
    for s, e, *_ in sorted(intervals):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if window[1] > t:
        out.append((t, window[1]))
    return sorted(out, key=lambda g: g[0] - g[1])


def busy_s(view: TraceView, dev: str) -> float:
    return union_ns(view.in_window(dev)) * 1e-9


def idle_share(view: Optional[TraceView]) -> Optional[float]:
    """Percent of the window with no operation on the busiest device."""
    dev = None if view is None else busiest(view)
    if dev is None:
        return None
    return 100.0 * (1.0 - busy_s(view, dev) / view.window_s)


def busiest(view: TraceView) -> Optional[str]:
    """The device plane with the most busy time in the window."""
    if not view.ops:
        return None
    return max(view.ops, key=lambda d: busy_s(view, d))


def read(trace_dir: str) -> TraceView:
    """Reduce the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    ops, coll, host = {}, {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            mine, theirs = ops.setdefault(plane.name, []), \
                coll.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name not in (OPS_LINE, ASYNC_LINE):
                    continue
                for ev in line.events:
                    iv = (ev.start_ns, ev.start_ns + ev.duration_ns,
                          short_name(ev.name))
                    if line.name == OPS_LINE:
                        mine.append(iv)
                    if COLLECTIVE.search(ev.name):
                        theirs.append(iv)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((ev.start_ns, ev.start_ns + ev.duration_ns,
                             ev.name) for ev in line.events
                            if ev.name.startswith("bench."))
    # planes of the chips that ran operations (a device may have
    # planes with no operation line)
    ops = {d: v for d, v in ops.items() if v}
    coll = {d: coll[d] for d in ops}
    win = [(s, e) for s, e, n in host if n == "bench.window"]
    if not win:
        raise ValueError("trace has no bench.window span")
    return TraceView(ops=ops, host=host, window=win[-1], collectives=coll)


def short_name(name: str) -> str:
    """``fusion.12`` of ``%fusion.12 = bf16[...] fusion(...)``."""
    m = HLO_NAME.match(name)
    return m.group(1) if m else name


def self_times(intervals) -> list:
    """[(seconds not covered by a nested operation, name)] of nested
    ``[(start, end, name)]``: a loop's time less its body's."""
    out, stack = [], []            # stack: [start, end, name, child_ns]

    def close(item):
        s, e, n, child = item
        out.append(((e - s - child) * 1e-9, n))
        if stack:
            stack[-1][3] += e - s

    for s, e, n in sorted(intervals, key=lambda x: (x[0], -x[1])):
        while stack and s >= stack[-1][1]:
            close(stack.pop())
        stack.append([s, e, n, 0])
    while stack:
        close(stack.pop())
    return out


def start(trace_dir: str) -> None:
    """Start the profiler into an emptied ``trace_dir``."""
    import jax
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)


def collect(trace_dir: str, keep=None) -> TraceView:
    """Stop the profiler, reduce its trace, copy the raw trace to
    ``keep`` when given, and delete it."""
    import jax
    jax.profiler.stop_trace()
    view = read(trace_dir)
    if keep:
        shutil.copytree(trace_dir, keep, dirs_exist_ok=True)
    shutil.rmtree(trace_dir, ignore_errors=True)
    return view


def breakdown(view: TraceView, top: int = 10) -> Optional[dict]:
    """The busiest device's costliest operations by self time (by name,
    the instance number dropped) and its longest idle gaps, each named
    by the innermost harness span the host was in at its midpoint."""
    dev = busiest(view)
    if dev is None:
        return None
    evs = view.in_window(dev)
    per: dict = {}
    for t, n in self_times(evs):
        key = re.sub(r"\.\d+$", "", n)
        per[key] = per.get(key, 0.0) + t
    ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
    idle = []
    for a, b in gaps(evs, view.window)[:top]:
        mid = (a + b) / 2
        inside = [(s, e, n) for s, e, n in view.host
                  if s <= mid <= e and n != "bench.window"]
        label = min(inside, key=lambda h: h[1] - h[0])[2] if inside \
            else "no harness span"
        idle.append([label, (b - a) * 1e-9])
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": idle}
