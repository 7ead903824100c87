"""Transport: client-thread time in `KvClient.fetch_prefix_blocks`
(`fetch`: a window of page fetches in flight on the node channel's
pipeline, the first with the state beside them, until the last has
landed), per page the window's turns restored (counters
`kv_prefix_fetch_total` less `kv_prefix_state_fetch_total`).  A program
without the state counter reads nothing."""

UNIT = "us"
DRIVERS = ("kv_prefix_linear",)


def read(ev):
    if "kv_prefix_state_fetch_total" not in ev.counters:
        return None
    pages = (ev.counters.get("kv_prefix_fetch_total", 0.0)
             - ev.counters["kv_prefix_state_fetch_total"])
    if pages <= 0:
        return None
    return ev.spans.total("fetch", ev.t_open, ev.t_close) / pages * 1e6
