"""D2H/H2D staging: payload bytes over the median `zerocopy.host_view`
of a request that has never been fetched."""

from benchmark import stats

UNIT = "GB/s"
DRIVERS = ("served_echo",)


def read(ev):
    d2h = ev.spans.durations("d2h", ev.t_open, ev.t_close)
    return ev.bytes_per_call / stats.median(d2h) / 1e9 if d2h else None
