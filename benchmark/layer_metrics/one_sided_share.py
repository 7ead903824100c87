"""Transport: of the bytes of the large bodies that were sent in the
window, the share that moved one-sided (written into the peer's RMA
window) instead of being cut into stripe frames and copied through a
ring or a socket.  Client and server share the process, so requests and
responses both count.

Every body above the stripe threshold goes exactly one of the two ways,
and each way adds the body's bytes to a counter of its own at the moment
the send ends (`rma_tx_bytes` in rma.cc, `stripe_tx_bytes` in
stripe.cc), so the share is over one set of sends and cannot pass 100.
Until PR 26 the divisor was the bytes of the calls that *ended* in the
window, a different set: it read 100.07.  A body under the threshold
rides one frame, never one-sided, and no counter holds its bytes: a
window whose calls sent only such bodies reads 0, one without calls
nothing.  The ici transport's sender-owned descriptors
(`zero_copy_bytes`) are counted per frame, inside a striped send as
well; no cell runs that transport, and the share leaves them out."""

UNIT = "%"
DRIVERS = ("served_echo",)


def read(ev):
    if "rma_tx_bytes" not in ev.counters:
        return None
    one_sided = ev.counters["rma_tx_bytes"]
    large = one_sided + ev.counters.get("stripe_tx_bytes", 0.0)
    if large:
        return 100.0 * one_sided / large
    return 0.0 if ev.counters.get("batch_resp_bytes") else None
