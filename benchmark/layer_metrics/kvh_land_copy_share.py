"""Transport: share of the fetched bytes that the completion copied out
into the caller's landing area instead of finding them there:
`land_copy_share`'s counters (`batch_land_copy_bytes` over
`batch_resp_bytes`) in a cell where only `Kv.Fetch` rides the pipeline.
A page record is under `trpc_stripe_threshold` and is always copied out
of its frame; a snapshot record is over it and its place in the landing
area is registered: 16.0 if every snapshot lands in place, 100.0 if
none does."""

from benchmark.layer_metrics import land_copy_share

UNIT = "%"
DRIVERS = ("kv_seq_pull",)
read = land_copy_share.read
