"""Ring attention — sequence-parallel attention with rotating KV blocks.

The long-context primitive SURVEY §5 asks for as a first-class citizen:
sequences too long for one chip shard along the sequence axis, each
device holds one Q/K/V block, and K/V blocks travel the ring (one
``ppermute`` hop per step) while every device folds each arriving block
into its local queries with the online-softmax (flash-attention)
accumulator.  Communication rides ICI exactly like the reference's RDMA
data plane rides ibverbs (/root/reference/src/brpc/rdma/
rdma_endpoint.cpp); "completion" is XLA dataflow, and the scan body only
serializes through the carry so hop k+1's DMA overlaps hop k's matmuls.

Numerics: the per-block update keeps running (max, sum, weighted output)
per query row; merging two blocks rescales both sides by
``exp(m_old - m_new)``.  This is the standard streaming-softmax identity,
so the result equals full attention up to float rounding (checked
against the single-block oracle in tests).

Causal masking is position-aware across the ring: block j's keys carry
global positions ``j*L .. (j+1)*L``, so hops from "future" blocks mask
to -inf entirely and the diagonal block applies the triangular mask.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from brpc_tpu.parallel.fabric import Fabric

__all__ = ["ring_attention", "attention_reference"]

_NEG_INF = -1e30
# The TPU's default matmul precision rounds f32 operands to bf16: on a v5e
# the dry run's outputs were 3e-3 off a float64 oracle at the default and
# 1e-6 off at HIGHEST (chip run, PR 21).  Kernel and oracle both ask for
# full f32 so they can be compared at an f32 tolerance.
_PRECISION = lax.Precision.HIGHEST


def _block_scores(q, k, scale, causal, q_pos, k_pos):
    """Scaled scores of local queries against one KV block (+ causal mask)."""
    # q: [sq, d]  k: [sk, d]  → [sq, sk]; accumulate in f32 on the MXU.
    s = jnp.einsum("qd,kd->qk", q, k, precision=_PRECISION,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        mask = q_pos[:, None] >= k_pos[None, :]
        s = jnp.where(mask, s, _NEG_INF)
    return s


def _fold_block(acc, s, v):
    """Online-softmax fold of one block's scores/values into (m, l, o)."""
    m, l, o = acc
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    # exp() of fully-masked rows underflows to 0 — no NaN path.
    p = jnp.exp(s - m_new[:, None])
    correction = jnp.exp(m - m_new)
    l_new = l * correction + jnp.sum(p, axis=-1)
    o_new = o * correction[:, None] + jnp.einsum(
        "qk,kd->qd", p.astype(v.dtype), v, precision=_PRECISION,
        preferred_element_type=jnp.float32)
    return m_new, l_new, o_new


def ring_attention(fabric: Fabric, axis: str = "link",
                   causal: bool = False):
    """Builds the jitted SPMD ring-attention step over `fabric`.

    Returns ``fn(q, k, v) -> out`` where every array is
    ``[batch*heads, seq, head_dim]`` sharded along ``seq`` on `axis`
    (use ``fabric.sharding(None, axis, None)``); `out` matches `q`.
    """
    n = fabric.axis_size(axis)

    def spmd(q, k, v):
        my_id = lax.axis_index(axis)
        bh, sq, d = q.shape
        scale = 1.0 / (d ** 0.5)
        q_pos = my_id * sq + lax.iota(jnp.int32, sq)

        def fold(acc, kv, owner):
            k_blk, v_blk = kv
            k_pos = owner * sq + lax.iota(jnp.int32, sq)
            s = jax.vmap(lambda qq, kk: _block_scores(
                qq, kk, scale, causal, q_pos, k_pos))(q, k_blk)
            return jax.vmap(_fold_block)(acc, s, v_blk)

        acc0 = (
            jnp.full((bh, sq), _NEG_INF, jnp.float32),
            jnp.zeros((bh, sq), jnp.float32),
            jnp.zeros((bh, sq, d), jnp.float32),
        )
        # Hop 0: the local block, in place.
        acc = fold(acc0, (k, v), my_id)

        perm = [(i, (i + 1) % n) for i in range(n)]

        def body(state, hop):
            kv, acc = state
            # One ring hop: our current block moves right, the left
            # neighbor's lands here — a one-sided ICI put, double-buffered
            # by XLA; the scan carry is the only serialization.
            kv = lax.ppermute(kv, axis, perm)
            owner = lax.rem(my_id - hop + n, n)
            acc = fold(acc, kv, owner)
            return (kv, acc), None

        (kv, acc), _ = lax.scan(body, ((k, v), acc), jnp.arange(1, n))
        m, l, o = acc
        # Fully-masked rows (causal, leading queries see only themselves —
        # l is always ≥ 1 there; guard anyway for degenerate shapes).
        l = jnp.where(l == 0, 1.0, l)
        return (o / l[:, :, None]).astype(q.dtype)

    shard = P(None, axis, None)
    return jax.jit(fabric.spmd(spmd, in_specs=(shard,) * 3,
                               out_specs=shard))


def attention_reference(causal: bool = False):
    """Single-device oracle: plain full softmax attention."""

    @jax.jit
    def fn(q, k, v):
        d = q.shape[-1]
        s = jnp.einsum("bqd,bkd->bqk", q, k, precision=_PRECISION,
                       preferred_element_type=jnp.float32) / (d ** 0.5)
        if causal:
            sq, sk = s.shape[-2], s.shape[-1]
            mask = (lax.iota(jnp.int32, sq)[:, None] >=
                    lax.iota(jnp.int32, sk)[None, :])
            s = jnp.where(mask, s, _NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bqk,bkd->bqd", p.astype(v.dtype), v,
                          precision=_PRECISION,
                          preferred_element_type=jnp.float32).astype(q.dtype)

    return fn
