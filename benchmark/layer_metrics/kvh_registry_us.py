"""Channel/Socket/dispatcher: client-thread time in a sequence's three
registry round trips (`register_many`, `lookup_many`, `evict_many`, each
carrying the records of both kinds: the intervals the driver's wrapper
round the registry client records), per sequence of the window.
`kv_registry_us`'s arithmetic on the spans of driver `kv_seq_pull`."""

from benchmark.layer_metrics import kv_registry_us

UNIT = "us"
DRIVERS = ("kv_seq_pull",)
read = kv_registry_us.read
