"""The plain reference of the `kv_disagg` deployment: what both pools
hold after a sequence of blocks went from the prefill pool to the decode
pool, in straightforward jax.numpy indexing and Python integers, with no
program of the system under test, no store, no pipeline, no donation.

The yardstick's rule for a fresh page is here too, because the reference
follows it: the next page is the one before with its checksum, made odd,
added to every 32-bit word, so that no word of a page equals the same
word of the page before (nor of any earlier page, unless the odd numbers
added in between sum to 0 mod 2^32).  A page's 32-bit words pair token t
of the first half of its tokens (high half) with token t + T/2 (low
half): an elementwise pairing, which a flat view of 2-byte elements as
4-byte ones is not on the chip's tiled layout.  A sum over the 2-byte
words themselves would follow n*c too, but only mod 2^16, and a page's
n = 2^13 * 549 of them leaves such a checksum three bits.
"""

from __future__ import annotations

MASK = 0xFFFFFFFF


def page_words(page):
    """(layers, tokens, width) uint16 -> (layers, tokens/2, width) uint32."""
    import jax.numpy as jnp

    half = page.shape[1] // 2
    return ((page[:, :half].astype(jnp.uint32) << 16)
            | page[:, half:].astype(jnp.uint32))


def page_checksum(page):
    """Wrapping uint32 sum of the page's 32-bit words."""
    import jax.numpy as jnp

    return jnp.sum(page_words(page), dtype=jnp.uint32)


def next_page(prev):
    import jax.numpy as jnp

    words = page_words(prev)
    words = words + (jnp.sum(words, dtype=jnp.uint32) | jnp.uint32(1))
    return jnp.concatenate(
        [(words >> 16).astype(prev.dtype), words.astype(prev.dtype)], axis=1)


def kv_disagg_reference(prefill, decode, first, sequence):
    """Whole pools, for a size at which a third and fourth pool fit.
    `sequence`: per block in the order produced, (prefill slot, decode
    slot, handed over); `first` is the page the first block is made
    from.  A block that was not handed over (a record missing, a fetch
    that failed) leaves the decode pool as it was.  Returns the pools."""
    page = first
    for prefill_slot, decode_slot, handed_over in sequence:
        page = next_page(page)
        prefill = prefill.at[prefill_slot].set(page)
        if handed_over:
            decode = decode.at[decode_slot].set(page)
    return prefill, decode


def kv_disagg_reference_checksums(prefill_sums, decode_sums, first_sum: int,
                                  words_per_page: int, sequence):
    """The same at the timed size, where no third pool fits: from the
    initial pools' per-slot checksums and the first page's, follows the
    sequence in integers.  Adding c to each of a page's n words adds n*c
    to its checksum, and a slot holds the checksum of the last page
    written there.  Returns the two lists of per-slot checksums."""
    prefill = [int(x) for x in prefill_sums]
    decode = [int(x) for x in decode_sums]
    checksum = int(first_sum)
    for prefill_slot, decode_slot, handed_over in sequence:
        checksum = (checksum + words_per_page * (checksum | 1)) & MASK
        prefill[prefill_slot] = checksum
        if handed_over:
            decode[decode_slot] = checksum
    return prefill, decode
