"""Transport: from the submit of a block's 61 `Kv.Fetch` to its last
completion: `KvClient.fetch_page` (`fetch`) less the lookup round trip
inside it (`lookup`), means per block of the window."""

UNIT = "us"
DRIVERS = ("kv_pull",)


def read(ev):
    blocks = len(ev.spans.durations("fetch", ev.t_open, ev.t_close))
    if not blocks:
        return None
    inside = (ev.spans.total("fetch", ev.t_open, ev.t_close)
              - ev.spans.total("lookup", ev.t_open, ev.t_close))
    return inside / blocks * 1e6
