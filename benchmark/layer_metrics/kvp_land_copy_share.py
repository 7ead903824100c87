"""Transport: share of the fetched bytes that the completion copied out
into the caller's landing area instead of finding them there:
`land_copy_share`'s counters (`batch_land_copy_bytes` over
`batch_resp_bytes`) in a cell where only `Kv.FetchPrefix` rides the
pipeline.  A block crosses the connection's one-sided window and is
copied out of its span over the rails unless its row of the landing
area took the direct transfer (`rma_landing_bind` admits one a
registered region at a time)."""

from benchmark.layer_metrics import land_copy_share

UNIT = "%"
DRIVERS = ("kv_prefix",)
read = land_copy_share.read
