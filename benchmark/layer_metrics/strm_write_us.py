"""Transport: median `Stream.write` of one chunk whose bytes are on the
host already (span `write`): the credit gate, then the frame's copy into
the shm ring, in band (a stream frame takes neither large-message
path)."""

from benchmark import stats

UNIT = "us"
DRIVERS = ("stream_echo",)


def read(ev):
    took = ev.spans.durations("write", ev.t_open, ev.t_close)
    return stats.median(took) * 1e6 if took else None
