"""Transport: median `Stream.read_block` of one echo that has arrived
(span `read`; the wait for it is the span before, `wait`): a landing
block from the recycled list and the one copy out of the frame."""

from benchmark import stats

UNIT = "us"
DRIVERS = ("stream_echo",)


def read(ev):
    took = ev.spans.durations("read", ev.t_open, ev.t_close)
    return stats.median(took) * 1e6 if took else None
