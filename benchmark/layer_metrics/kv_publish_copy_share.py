"""D2H/H2D staging: share of the bytes of the pages and sequences a
window published that were copied into the slab first (counters
`kv_publish_copy_bytes` over that plus `kv_publish_in_place_bytes`,
noted once a `kv.publish_page` / `publish_sequence`).  0 where every
source was the block its device-to-host transfer had landed in and was
published where it lay; 100 where every source was copied (the CPU
rehearsal: dlpack imports every array, so no view is pending and no
block is the pool's); 0 where nothing was published.  A program without
the counters reads nothing."""

UNIT = "%"
DRIVERS = ("kv_pull", "kv_seq_pull")


def read(ev):
    if ("kv_publish_copy_bytes" not in ev.counters
            or "kv_publish_in_place_bytes" not in ev.counters):
        return None
    copied = ev.counters["kv_publish_copy_bytes"]
    published = copied + ev.counters["kv_publish_in_place_bytes"]
    if not published:
        return 0.0
    return 100.0 * copied / published
