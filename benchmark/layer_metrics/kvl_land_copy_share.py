"""Transport: share of the fetched bytes that the completion copied out
into the caller's landing area instead of finding them there:
`land_copy_share`'s counters (`batch_land_copy_bytes` over
`batch_resp_bytes`) in a cell where only `Kv.FetchPrefix` rides the
pipeline.  A page comes in a frame of the ring and is copied out; the
state lands in its registered area one-sided, in place where the
connection binds it (`rma_landing_bind`)."""

from benchmark.layer_metrics import land_copy_share

UNIT = "%"
DRIVERS = ("kv_prefix_linear",)
read = land_copy_share.read
