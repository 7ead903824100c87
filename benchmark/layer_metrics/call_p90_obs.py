"""Served path, tail: the 90th percentile of the call times, for cells
whose window completes too few calls for a 99th."""

from benchmark import stats

UNIT = "us"
DRIVERS = ("served_echo",)


def read(ev):
    if not stats.supported(len(ev.call_s), 90.0):
        return None
    return stats.tail(ev.call_s, 90.0) * 1e6
