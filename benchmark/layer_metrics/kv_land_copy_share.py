"""Transport: share of the fetched bytes that the completion fiber copied
out into the caller's landing buffer instead of finding them there:
`land_copy_share`'s counters (`batch_land_copy_bytes` over
`batch_resp_bytes`) in a cell where only `Kv.Fetch` rides the pipeline.
A record is under `trpc_stripe_threshold`, so its landing buffer is never
registered for in-place or one-sided landing (cpp/net/channel.cc)."""

from benchmark.layer_metrics import land_copy_share

UNIT = "%"
DRIVERS = ("kv_pull",)
read = land_copy_share.read
