"""Transport: share of the response bytes that the completion fiber
copied into the caller's buffer instead of finding them there already
(counters `batch_land_copy_bytes` over `batch_resp_bytes`)."""

UNIT = "%"
DRIVERS = ("served_echo",)


def read(ev):
    nbytes = ev.counters.get("batch_resp_bytes", 0.0)
    if not nbytes:
        return None
    return 100.0 * ev.counters["batch_land_copy_bytes"] / nbytes
