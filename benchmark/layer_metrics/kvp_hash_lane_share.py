"""Prefix store: the pages offered whose content hash was walked side by
side with others of their run, in a group of two to four (counters
`kv_prefix_hash_lanes` over `kv_prefix_publish_total` +
`kv_prefix_publish_renewed`, x 100).  A program without the lanes
(before PR 38) reads nothing."""

UNIT = "%"
DRIVERS = ("kv_prefix",)


def read(ev):
    pages = (ev.counters.get("kv_prefix_publish_total", 0.0)
             + ev.counters.get("kv_prefix_publish_renewed", 0.0))
    if not pages or "kv_prefix_hash_lanes" not in ev.counters:
        return None
    return 100.0 * ev.counters["kv_prefix_hash_lanes"] / pages
