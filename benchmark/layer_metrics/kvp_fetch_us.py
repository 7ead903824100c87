"""Transport: client-thread time in `KvClient.fetch_prefix_blocks`
(`fetch`: a window of `Kv.FetchPrefix` calls submitted to the node
channel's pipeline until the last has landed), per block the window's
turns restored (counter `kv_prefix_fetch_total`: a block asked for and
answered kv-stale costs its time and counts none)."""

UNIT = "us"
DRIVERS = ("kv_prefix",)


def read(ev):
    blocks = ev.counters.get("kv_prefix_fetch_total")
    if not blocks:
        return None
    return ev.spans.total("fetch", ev.t_open, ev.t_close) / blocks * 1e6
