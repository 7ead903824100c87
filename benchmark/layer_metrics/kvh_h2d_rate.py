"""D2H/H2D staging: a sequence's bytes over the median `jax.device_put`
of its landed pages and states, ended by `block_until_ready`
(`h2d_rate`'s span)."""

from benchmark.layer_metrics import h2d_rate

UNIT = "GB/s"
DRIVERS = ("kv_seq_pull",)
read = h2d_rate.read
