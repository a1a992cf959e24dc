"""repro.obs — unified switch telemetry.

The observability layer the paper's evaluate → map → refine loop (§V)
runs on: what did the switch program actually do, and does the model
still believe it?  Four pieces:

  1. **metrics** (:mod:`repro.obs.metrics`) — process-local counters /
     gauges / histograms behind a :class:`~repro.obs.metrics.Recorder`;
     compiler, executor, simulator, tune, serve and train all emit into
     the module-level recorder (a no-op ``null_recorder`` by default —
     enable with :func:`~repro.obs.metrics.recording`).
  2. **spans** (:mod:`repro.obs.spans`) — the shared stage-record
     schema.  ``tune.trace.StageTrace`` *is* :class:`~repro.obs.spans.
     StageSpan`; the executor's ``instrument`` hook emits it directly.
     Host regions of the jitted paths (the serve tick's phases) use
     :func:`~repro.obs.metrics.span`, which lands in the profiler's
     trace beside the device's operations; device regions carry
     ``jax.named_scope`` names (``decode.*``, ``train.*``,
     ``acis.<kind>.s<i>``).
  3. **timeline** (:mod:`repro.obs.timeline`) — spans (executor *or*
     simulator) exported as Chrome trace-event JSON loadable in
     Perfetto: one lane per axis, wave boundaries as instants.
  4. **drift** (:mod:`repro.obs.drift`) — online measured-vs-model
     ratio tracking that recommends a re-fit (``repro.tune.fit``) when
     the analytic model stops describing reality.

:class:`~repro.obs.report.RunReport` aggregates one run;
``python -m repro.obs`` renders a report or dumps a ``.trace.json``
from a recorded JSONL trace.

``spans``/``metrics``/``timeline`` import nothing but the stdlib at
module level (``metrics`` reaches jax lazily) so ``repro.core`` imports
them without a cycle; ``drift``/``report`` (which
reach into ``repro.core.netmodel`` / ``repro.tune``) load lazily.
"""

from repro.obs import metrics, spans, timeline
from repro.obs.metrics import (NullRecorder, Recorder, current, install,
                               null_recorder, recording, span)
from repro.obs.spans import StageSpan
from repro.obs.timeline import chrome_trace

__all__ = [
    "metrics", "spans", "timeline", "drift", "report",
    "Recorder", "NullRecorder", "null_recorder", "current", "install",
    "recording", "span", "StageSpan", "chrome_trace",
    "DriftWatchdog", "DriftAlert", "DriftVerdict", "RunReport",
]

_LAZY = {
    "drift": "repro.obs.drift",
    "report": "repro.obs.report",
    "DriftWatchdog": "repro.obs.drift",
    "DriftAlert": "repro.obs.drift",
    "DriftVerdict": "repro.obs.drift",
    "RunReport": "repro.obs.report",
}


def __getattr__(name):
    # drift/report import repro.core (netmodel) — deferred so that
    # repro.core.executor can import repro.obs at module level without
    # a circular import through the package __init__
    target = _LAZY.get(name)
    if target is None:
        raise AttributeError(f"module 'repro.obs' has no attribute {name!r}")
    import importlib

    mod = importlib.import_module(target)
    value = mod if name in ("drift", "report") else getattr(mod, name)
    globals()[name] = value
    return value
