"""Prefix store: the publisher's time in the content hash, per page
offered (counters `kv_prefix_hash_us` over `kv_prefix_publish_total` +
`kv_prefix_publish_renewed`; since PR 38 a group of a run's pages
hashed side by side counts its time once).  Part of `kvp_publish_us`'s
`publish` span.  A program without the counters reads nothing."""

UNIT = "us"
DRIVERS = ("kv_prefix",)


def read(ev):
    pages = (ev.counters.get("kv_prefix_publish_total", 0.0)
             + ev.counters.get("kv_prefix_publish_renewed", 0.0))
    if not pages or "kv_prefix_hash_us" not in ev.counters:
        return None
    return ev.counters["kv_prefix_hash_us"] / pages
