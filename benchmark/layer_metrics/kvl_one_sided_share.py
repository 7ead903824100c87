"""Transport: of the bytes the window's turns restored, pages and states
(the driver's `bytes_per_call` times its turns), the share that moved
one-sided (`rma_tx_bytes`: put into the admitting side's receive window
through the connection's RMA session).  A state is over
`trpc_stripe_threshold` and goes so; a 1 MB page is under it and rides
one frame of the ring, counted in neither `rma_tx_bytes` nor
`stripe_tx_bytes`, so `one_sided_share`'s ratio would read 100 here:
this one reads the states' share of the restored bytes, about 4."""

UNIT = "%"
DRIVERS = ("kv_prefix_linear",)


def read(ev):
    restored = ev.bytes_per_call * len(ev.call_s)
    if "rma_tx_bytes" not in ev.counters or not restored:
        return None
    return 100.0 * ev.counters["rma_tx_bytes"] / restored
