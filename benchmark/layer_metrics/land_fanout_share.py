"""Transport: share of the response bytes whose copy into the caller's
buffer ran on more than one rail (counters `batch_land_fanout_bytes` over
`batch_resp_bytes`): a one-sided response leaves the connection's shm
window cut over the rails that wrote it there, not as one stream on the
completion fiber.  0 where responses land in place or come as no window
span, and for a program without the counter."""

UNIT = "%"
DRIVERS = ("served_echo",)


def read(ev):
    nbytes = ev.counters.get("batch_resp_bytes", 0.0)
    if not nbytes:
        return None
    return 100.0 * ev.counters.get("batch_land_fanout_bytes", 0.0) / nbytes
