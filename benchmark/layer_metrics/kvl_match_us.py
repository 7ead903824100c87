"""Channel/Socket/dispatcher: client-thread time of one
`KvClient.match_prefix` in a turn of a hybrid cache (the chain keys of
the prompt's 1,000 and more full pages and the `KvReg.Match` round trip,
whose answer ends at the deepest boundary with a live state and carries
that state's replicas), the median over the window's turns."""

from benchmark import stats

UNIT = "us"
DRIVERS = ("kv_prefix_linear",)


def read(ev):
    match = ev.spans.durations("match", ev.t_open, ev.t_close)
    return stats.median(match) * 1e6 if match else None
