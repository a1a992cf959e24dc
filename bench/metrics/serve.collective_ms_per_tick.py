"""Device time of the collective operations per engine tick, on the
device that spends the most on them: the union of their intervals,
synchronous and asynchronous, so a transfer hidden behind compute counts
as long as it lasts."""

from bench import xplane


def read(run):
    view, ticks = run.view, run.stats.get("ticks")
    if view is None or not ticks:
        return None
    most = max((xplane.union_ns(view.in_window(d, "collectives"))
                for d in view.collectives), default=0.0)
    return most * 1e-6 / ticks if most > 0 else None
