"""D2H/H2D staging: payload bytes over the median `jax.device_put` of a
response ended by `block_until_ready`."""

from benchmark import stats

UNIT = "GB/s"
DRIVERS = ("served_echo",)


def read(ev):
    h2d = ev.spans.durations("h2d", ev.t_open, ev.t_close)
    return ev.bytes_per_call / stats.median(h2d) / 1e9 if h2d else None
