"""D2H/H2D staging: client-thread time to publish one sequence, per
sequence of the window: the wait for the bytes of its pages and its
states (`d2h_wait`) and `kv.publish_sequence` (`publish`: the copy of
both into the slab, a publish a record, the `register_many` round trip).
`kv_publish_us`'s arithmetic on the spans of driver `kv_seq_pull`."""

from benchmark.layer_metrics import kv_publish_us

UNIT = "us"
DRIVERS = ("kv_seq_pull",)
read = kv_publish_us.read
