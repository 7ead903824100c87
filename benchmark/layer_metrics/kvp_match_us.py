"""Channel/Socket/dispatcher: client-thread time of one
`KvClient.match_prefix` (the chain keys of the prompt's full pages and
the `KvReg.Match` round trip), the median over the window's turns."""

from benchmark import stats

UNIT = "us"
DRIVERS = ("kv_prefix",)


def read(ev):
    match = ev.spans.durations("match", ev.t_open, ev.t_close)
    return stats.median(match) * 1e6 if match else None
