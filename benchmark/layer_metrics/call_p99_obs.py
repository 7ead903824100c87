"""Served path, tail: the 99th percentile of the call times, for a cell
in which it is observed and not bounded: with many calls in flight behind
one client thread it is the generator's round-robin queue that is
measured, and it moves with the host's other load (PERF.md, PR 22)."""

from benchmark import stats

UNIT = "us"
DRIVERS = ("served_echo",)


def read(ev):
    if not stats.supported(len(ev.call_s), 99.0):
        return None
    return stats.tail(ev.call_s, 99.0) * 1e6
