"""Share of the traced window in which no operation ran on the busiest
device."""

from bench import xplane


def read(run):
    return xplane.idle_share(run.view)
