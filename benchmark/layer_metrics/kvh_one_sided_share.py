"""Transport: of the bytes of the snapshot records served in the window,
the share that moved one-sided (put into the decode side's registered
landing area through the connection's RMA window) and not cut into
stripe frames through the ring.  `one_sided_share`'s counters
(`rma_tx_bytes` over `rma_tx_bytes + stripe_tx_bytes`): in this cell
only a snapshot record's response is over `trpc_stripe_threshold`; a
page record and every request ride one frame and count in neither."""

from benchmark.layer_metrics import one_sided_share

UNIT = "%"
DRIVERS = ("kv_seq_pull",)
read = one_sided_share.read
