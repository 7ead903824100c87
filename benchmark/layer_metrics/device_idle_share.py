"""Device: share of the traced window in which no operation ran on the
chip's compute line, averaged over the chips."""

from benchmark import trace_reduce

UNIT = "%"
DRIVERS = None    # reads only `ev.trace`, which every driver's run has


def read(ev):
    if ev.trace is None:
        return None
    return 100.0 * trace_reduce.idle_share(ev.trace)
