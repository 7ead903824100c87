"""D2H/H2D staging: client-thread time of one `kv.prefix_publish_state`
(`state_publish`: the wait for the new state's 43 MB where its transfer
lands them, the content hash over them, the store taking them where they
lie), the median over the window's turns."""

from benchmark import stats

UNIT = "us"
DRIVERS = ("kv_prefix_linear",)


def read(ev):
    took = ev.spans.durations("state_publish", ev.t_open, ev.t_close)
    return stats.median(took) * 1e6 if took else None
