"""D2H/H2D staging: client-thread time to publish one block, per block of
the window: the wait for the page's bytes (`d2h_wait`) and
`kv.publish_page` (`publish`: the copy into the slab, 61 publishes, the
`register_many` round trip)."""

UNIT = "us"
DRIVERS = ("kv_pull",)


def read(ev):
    blocks = len(ev.spans.durations("publish", ev.t_open, ev.t_close))
    if not blocks:
        return None
    inside = sum(ev.spans.total(name, ev.t_open, ev.t_close)
                 for name in ("d2h_wait", "publish"))
    return inside / blocks * 1e6
