"""Transport: of the bytes of the prefix blocks served in the window, the
share that moved one-sided (put into the admitting side's receive window
through the connection's RMA session) and not cut into stripe frames
through the ring.  `one_sided_share`'s counters (`rma_tx_bytes` over
`rma_tx_bytes + stripe_tx_bytes`): a block is over
`trpc_stripe_threshold`, every request and the registry's round trips
ride one frame and count in neither."""

from benchmark.layer_metrics import one_sided_share

UNIT = "%"
DRIVERS = ("kv_prefix",)
read = one_sided_share.read
