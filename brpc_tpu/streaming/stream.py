"""Streaming — ordered chunk pipelines with windowed flow control.

Reference parity: brpc's streaming RPC (/root/reference/src/brpc/stream.cpp:
Create :78, AppendIfNotFull credit check :326, Consume :582) delivers ordered
byte chunks with a credit window so a fast writer can't overrun a slow
reader.  TPU-native, a stream between mesh peers is a ``lax.scan`` whose body
moves one chunk per step with ``ppermute``; ordering is the scan order and
"completion" is dataflow — XLA double-buffers the transfer of chunk k+1
against the consumer compute of chunk k, the overlap brpc's credit machinery
exists to enable.

This is the MESH plane's stream: chips of one XLA program, no connection,
no window object, no C++ under it.  The SERVED path's stream, chunks over
a `Channel`'s connection between two processes' runtimes with a credit
window that holds up to the reading application, is
`brpc_tpu/rpc/stream.py` (the benchmark's `stream_echo` cell and the
inference front door ride that one).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from brpc_tpu.parallel.fabric import Fabric
from brpc_tpu.transport.ici import _ring_perm

__all__ = ["ring_stream", "stream_echo"]


def ring_stream(
    fabric: Fabric,
    axis: str,
    on_chunk: Callable,
    *,
    in_spec=None,
    carry_spec=P(),
    out_spec=None,
    shift: int = 1,
):
    """Build a compiled stream over `axis`: each scan step ppermutes one chunk
    one hop and hands the arrival to ``on_chunk(carry, chunk) -> (carry,
    out)`` on the receiving peer.

    `chunks` must have leading dim = num_chunks; the default specs shard the
    second dim over `axis` (N concurrent streams riding N links — the
    pairwise topology streaming_echo_c++ exercises).  `carry_spec`/`out_spec`
    describe the *global* layout of the scan carry / stacked outputs.
    """
    n = fabric.axis_size(axis)
    perm = _ring_perm(n, shift)
    in_spec = P(None, axis) if in_spec is None else in_spec
    out_spec = P(None, axis) if out_spec is None else out_spec

    def spmd(chunks, carry0):
        def body(carry, chunk):
            arrived = lax.ppermute(chunk, axis, perm)
            return on_chunk(carry, arrived)

        return lax.scan(body, carry0, chunks)

    fn = fabric.spmd(
        spmd, in_specs=(in_spec, carry_spec), out_specs=(carry_spec, out_spec)
    )
    return jax.jit(fn)


def stream_echo(fabric: Fabric, axis: str, num_chunks: int):
    """Bidi stream echo (example/streaming_echo_c++ analogue): every chunk is
    streamed to the right neighbor, checksummed there, and per-chunk sums
    stacked; the carry keeps each receiver's running total (per-peer)."""

    def on_chunk(carry, chunk):
        s = jnp.sum(chunk.astype(jnp.uint32), dtype=jnp.uint32)
        # carry/out are (1,)-shaped per peer so the global view stacks along
        # the stream axis: carry -> (n,), outs -> (num_chunks, n).
        return carry + s[None], s[None]

    return ring_stream(
        fabric,
        axis,
        on_chunk,
        carry_spec=P(axis),
        out_spec=P(None, axis),
    )
