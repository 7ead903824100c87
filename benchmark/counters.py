"""The program's counters, read as deltas over the window."""

from __future__ import annotations


def read_native() -> dict[str, float]:
    """Every numeric variable of the native registry, plus the bytes and
    descriptors that rode sender-owned descriptors."""
    from brpc_tpu.rpc import observe, zerocopy

    out = {k: float(v) for k, v in observe.Vars.dump().items()
           if isinstance(v, (int, float))}
    descriptors, nbytes = zerocopy.zero_copy_counters()
    out["zero_copy_descriptors"] = float(descriptors)
    out["zero_copy_bytes"] = float(nbytes)
    return out


def delta(before: dict, after: dict) -> dict[str, float]:
    return {k: after[k] - before.get(k, 0.0) for k in after}
