"""Chip roofline constants — published HBM bandwidth per device kind.

Achieved-bandwidth fractions are reported against these (BASELINE.md's
"≥80% of raw link" discipline applied to HBM: a kernel number without its
roofline fraction hides a 3-8x shortfall).

Keys are `jax.Device.device_kind` strings exactly as JAX reports them.
Sources: public Cloud TPU system-architecture docs (cloud.google.com/tpu).
"""

HBM_PEAK_GBPS = {
    "TPU v4": 1228.0,
    "TPU v5 lite": 819.0,   # v5e
    "TPU v5": 2765.0,       # v5p
    "TPU v6 lite": 1640.0,  # v6e (Trillium)
}


def hbm_peak_gbps(device_kind: str) -> float:
    """Peak HBM bandwidth for a jax device_kind.  Exact match only: a
    kind that is not in the table is an error, never another chip's
    peak (a prefix match once gave a v5e the v5p's 2,765 GB/s)."""
    try:
        return HBM_PEAK_GBPS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published HBM peak for device_kind {device_kind!r}; "
            f"known kinds: {sorted(HBM_PEAK_GBPS)}") from None
