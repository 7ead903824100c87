"""The plain references: the same semantics, independent of the code
under test, in straightforward jax.numpy with no kernel and no collective
call.  `correct` is decided against these."""

from __future__ import annotations


def echo_reference(request):
    """An echo answers with the bytes it was sent."""
    return request


def exchange_reference(rows, peers: int):
    """N-to-N exchange of `rows` (peers*peers, chunk): row j of peer i
    lands as row i of peer j, a transposition of the (peer, row) grid."""
    chunk = rows.shape[-1]
    return rows.reshape(peers, peers, chunk).transpose(1, 0, 2).reshape(
        rows.shape)


def shard_checksums(rows, peers: int):
    """Per-peer wrapping uint32 sum of what the peer holds."""
    import jax.numpy as jnp

    return jnp.sum(rows.reshape(peers, -1), axis=1, dtype=jnp.uint32)
