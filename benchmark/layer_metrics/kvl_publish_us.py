"""D2H/H2D staging: client-thread time to publish one page a turn
prefilled: the wait for its bytes (`d2h_wait`) and
`kv.publish_prefix_run` (`publish`: the content hash over the bytes
where they lie, the store taking the pages, and the one
`KvReg.PutPrefixMany` that records them with the turn's state), over
the pages offered (counters `kv_prefix_publish_total` +
`kv_prefix_publish_renewed` less `kv_prefix_state_publish_total`).  A
program without the state counter reads nothing."""

UNIT = "us"
DRIVERS = ("kv_prefix_linear",)


def read(ev):
    if "kv_prefix_state_publish_total" not in ev.counters:
        return None
    pages = (ev.counters.get("kv_prefix_publish_total", 0.0)
             + ev.counters.get("kv_prefix_publish_renewed", 0.0)
             - ev.counters["kv_prefix_state_publish_total"])
    if pages <= 0:
        return None
    inside = sum(ev.spans.total(name, ev.t_open, ev.t_close)
                 for name in ("d2h_wait", "publish"))
    return inside / pages * 1e6
