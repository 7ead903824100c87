"""Prefix store: of the blocks served in the window, the share found
demoted in the heap tier (counters `kv_prefix_cold_hits` over
`kv_prefix_fetch_total`): each is copied back into registered pages
before it is served, and displaces a hot block.  0 where the hot budget
holds the working set; nothing where nothing was served or the counters
are absent."""

UNIT = "%"
DRIVERS = ("kv_prefix",)


def read(ev):
    served = ev.counters.get("kv_prefix_fetch_total")
    if not served or "kv_prefix_cold_hits" not in ev.counters:
        return None
    return 100.0 * ev.counters["kv_prefix_cold_hits"] / served
