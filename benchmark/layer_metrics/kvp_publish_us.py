"""D2H/H2D staging: client-thread time to publish one page the window's
turns had to prefill: the wait for its bytes (`d2h_wait`) and
`kv.publish_prefix_run` (`publish`: the content hash over the bytes
where they lie, the store taking the block, the `KvReg.PutPrefixMany`
round trip), over the pages offered (counters `kv_prefix_publish_total`
+ `kv_prefix_publish_renewed`)."""

UNIT = "us"
DRIVERS = ("kv_prefix",)


def read(ev):
    pages = (ev.counters.get("kv_prefix_publish_total", 0.0)
             + ev.counters.get("kv_prefix_publish_renewed", 0.0))
    if not pages:
        return None
    inside = sum(ev.spans.total(name, ev.t_open, ev.t_close)
                 for name in ("d2h_wait", "publish"))
    return inside / pages * 1e6
