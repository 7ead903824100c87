"""Transport: from the submit of a sequence's `Kv.Fetch`es, the page
records and the snapshot records in one round, to the last completion:
`KvClient.fetch_sequence` (`fetch`) less the lookup round trip inside it
(`lookup`), means per sequence of the window.  `kv_fetch_us`'s
arithmetic on the spans of driver `kv_seq_pull`."""

from benchmark.layer_metrics import kv_fetch_us

UNIT = "us"
DRIVERS = ("kv_seq_pull",)
read = kv_fetch_us.read
