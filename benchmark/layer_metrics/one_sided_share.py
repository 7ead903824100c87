"""Transport: share of the payload bytes that moved one-sided (RMA
windows) or as sender-owned descriptors instead of through a ring or a
socket copy.  Client and server share the process, so requests and
responses both count: 2 x payload per call."""

UNIT = "%"
DRIVERS = ("served_echo",)


def read(ev):
    if not ev.call_s or "rma_tx_bytes" not in ev.counters:
        return None
    moved = 2.0 * ev.bytes_per_call * len(ev.call_s)
    one_sided = (ev.counters["rma_tx_bytes"]
                 + ev.counters["zero_copy_bytes"])
    return 100.0 * one_sided / moved
