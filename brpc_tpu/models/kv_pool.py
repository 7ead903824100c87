"""A paged latent KV cache on one device, beside `models/echo.py`: what a
prefill rank and a decode rank each hold in HBM.

A pool is `(pages, layers, page_tokens, width)`: one page holds the
latent cache (MLA: `kv_lora_rank + qk_rope_head_dim` per token) of
`page_tokens` tokens for every layer, so `pool[slot, layer]` is the
record that crosses the wire for one layer (brpc_tpu/rpc/kv.py
`publish_page` / `fetch_page`).  The cache is bf16; the pool holds its
2-byte bit pattern as uint16, because the transfer moves bits and a
wrapping integer add of a producer is exact where a bf16 add rounds.

A model whose cache is of two kinds (latent attention beside linear
attention) holds a second pool of the same form beside it: `(slots,
state layers, rows, 128)`, one slot a sequence, `pool[slot, layer]` the
snapshot of one layer's recurrent state (its float32 matrix and the
short convolution's tail, as 2-byte words in rows of 128, so that a
slot has a page's form).  Every program here, `seeded_pool` too, takes
either pool: none knows what the trailing axes mean.

`read_page` and `write_page` are the two programs that join a pool to
the KV plane; `read_pages` and `write_pages` are the same for the pages
of one sequence, which lie in slots of their own, in one program.  A
write donates the pool: without the donation XLA
copies the whole pool for one page (two pools of 5.76 GB fill a 16 GB
chip: one such copy is out of memory), so the caller's old handle is
dead after the call and the returned pool is the pool.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

DTYPE = jnp.uint16


def kv_read_page(pool, slot):
    """The page at `slot`: `(layers, page_tokens, width)`."""
    return lax.dynamic_index_in_dim(pool, slot, axis=0, keepdims=False)


def kv_write_page(pool, slot, page):
    """The pool with `page` at `slot`."""
    return lax.dynamic_update_index_in_dim(pool, page.astype(pool.dtype),
                                           slot, axis=0)


def kv_read_pages(pool, slots):
    """The pages at `slots` (distinct), in their order."""
    return jax.vmap(kv_read_page, in_axes=(None, 0))(pool, slots)


def kv_write_pages(pool, slots, pages):
    """The pool with `pages[i]` at `slots[i]`, the slots distinct."""
    return lax.fori_loop(
        0, slots.shape[0],
        lambda i, pool: kv_write_page(pool, slots[i], pages[i]), pool)


read_page = jax.jit(kv_read_page)
write_page = jax.jit(kv_write_page, donate_argnums=0)
read_pages = jax.jit(kv_read_pages)
write_pages = jax.jit(kv_write_pages, donate_argnums=0)


def _kv_fill(pool, key):
    def one(slot, pool):
        bits = jax.random.bits(jax.random.fold_in(key, slot),
                               pool.shape[1:], pool.dtype)
        return kv_write_page(pool, slot, bits)

    return lax.fori_loop(0, pool.shape[0], one, pool)


def seeded_pool(seed: int, pages: int, layers: int, page_tokens: int,
                width: int):
    """A pool of random bits made on the (default) device from `seed`, a
    page at a time into a donated buffer: a whole pool's bits at once
    would take the generator's temporaries of the pool's size beside it."""
    shape = (pages, layers, page_tokens, width)
    pool = jax.jit(lambda: jnp.zeros(shape, DTYPE))()
    return jax.jit(_kv_fill, donate_argnums=0)(pool, jax.random.key(seed))
