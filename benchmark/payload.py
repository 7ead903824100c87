"""Payloads made on the device from the seed, and the guard that keeps a
device array from being sent twice."""

from __future__ import annotations

import weakref


def seeded_bits(seed: int, shape, sharding=None):
    """uint32 payload of `shape`, made on the device in one jitted call."""
    import jax
    import jax.numpy as jnp

    def bm_seeded_bits():
        return jax.random.bits(jax.random.key(seed), shape, jnp.uint32)

    return jax.jit(bm_seeded_bits, out_shardings=sharding)()


class ReusedArray(Exception):
    """A device array was offered as a request a second time."""


class SendOnce:
    """JAX keeps an array's host copy after the first fetch, so a request
    sent twice would pay no D2H the second time.  Every request passes
    through `claim`, which refuses one it has seen and one that already
    has a host copy."""

    def __init__(self):
        self._seen: dict[int, weakref.ref] = {}

    def claim(self, array) -> None:
        known = self._seen.get(id(array))
        if known is not None and known() is array:
            raise ReusedArray("this device array was already sent once")
        if getattr(array, "_npy_value", None) is not None:
            raise ReusedArray("this device array already has a host copy")
        key = id(array)
        self._seen[key] = weakref.ref(
            array, lambda _, key=key: self._seen.pop(key, None))
