"""Transport: mean time of one record's `Kv.Fetch` from just before its
`CallMethod` to the entry of its completion: `call_wire_us`'s counters
(`batch_wire_us` per `batch_calls_polled`).  Only the fetches ride the
batch pipeline: the registry calls go through `Channel.call` and count
in neither."""

from benchmark.layer_metrics import call_wire_us

UNIT = "us"
DRIVERS = ("kv_pull",)
read = call_wire_us.read
