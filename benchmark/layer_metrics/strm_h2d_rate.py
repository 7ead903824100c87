"""D2H/H2D staging: a chunk's bytes over the median `jax.device_put` of
its echo ended by `block_until_ready` (span `h2d`): `h2d_rate`'s
arithmetic on the spans of driver `stream_echo`."""

from benchmark.layer_metrics import h2d_rate

UNIT = "GB/s"
DRIVERS = ("stream_echo",)
read = h2d_rate.read
