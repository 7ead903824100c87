"""Device: share of the traced window in which no operation ran on the
chip's compute line, averaged over the chips."""

from benchmark import trace_reduce

UNIT = "%"
DRIVERS = ("served_echo", "mesh_exchange")


def read(ev):
    if ev.trace is None:
        return None
    return 100.0 * trace_reduce.idle_share(ev.trace)
