"""D2H/H2D staging: share of the bytes of the prefix blocks a window
admitted that the store copied into pages of its own (counters
`kv_prefix_publish_copy_bytes` over that plus
`kv_prefix_publish_in_place_bytes`).  0 where every block was taken in
the landing block its device-to-host transfer had written; 100 where
every source was copied (the CPU rehearsal: dlpack imports every array,
so no block is the host pool's); 0 where nothing was published.  A
program without the counters reads nothing.  `kv_publish_copy_share`'s
arithmetic on the prefix tier's counters."""

UNIT = "%"
DRIVERS = ("kv_prefix",)


def read(ev):
    if ("kv_prefix_publish_copy_bytes" not in ev.counters
            or "kv_prefix_publish_in_place_bytes" not in ev.counters):
        return None
    copied = ev.counters["kv_prefix_publish_copy_bytes"]
    published = copied + ev.counters["kv_prefix_publish_in_place_bytes"]
    if not published:
        return 0.0
    return 100.0 * copied / published
