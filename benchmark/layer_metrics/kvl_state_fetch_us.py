"""Transport: from the start of a turn's first fetch round to the poll
that handed its state out (`state_fetch`, an interval inside `fetch`:
the 43 MB state crosses the connection's one-sided window while the
round's first pages ride the ring's frames), the median over the
window's turns."""

from benchmark import stats

UNIT = "us"
DRIVERS = ("kv_prefix_linear",)


def read(ev):
    took = ev.spans.durations("state_fetch", ev.t_open, ev.t_close)
    return stats.median(took) * 1e6 if took else None
