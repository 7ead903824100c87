"""Mesh plane: device time of the all-to-all's operations per exchange,
averaged over the chips."""

from benchmark import trace_reduce

UNIT = "us"
DRIVERS = ("mesh_exchange",)
OPS = r"all-to-all"


def read(ev):
    if ev.trace is None:
        return None
    s = trace_reduce.seconds_per_span(
        ev.trace, trace_reduce.OP_LINE, OPS, "exchange")
    return None if s is None else s * 1e6
