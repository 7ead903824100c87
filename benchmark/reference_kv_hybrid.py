"""The plain reference of the `kv_hybrid` deployment: what a rank's two
pools hold, the paged latent cache and the per-sequence recurrent-state
snapshots, after a run of sequences went from the prefill rank to the
decode rank.  Straightforward jax.numpy indexing and Python integers:
no program of the system under test, no layout, no store, no pipeline,
no donation.  A hand-over is "these slots of the decode pools now hold
those arrays", of both kinds or of neither.

The yardstick's rule for a fresh sequence is here too, because the
reference follows it: a sequence's pages are those of the sequence
before with their checksum (over all of them), made odd, added to every
32-bit word, and its states the states before with theirs
(`reference_kv.next_page`, on the pages laid end to end and on the
states).  So no word of a sequence's cache equals the same word of the
sequence before, pages or states, and a stale snapshot, which no
position tells from a fresh one, differs from it in every word.  A
state slot has a page's form, (state layers, rows, width), and the same
pairing into 32-bit words.
"""

from __future__ import annotations

from benchmark.reference_kv import MASK, next_page, page_checksum


def next_sequence(pages, states):
    """pages (n, paged layers, tokens, width), states (state layers,
    rows, width), both uint16 -> the next sequence's."""
    end_to_end = pages.reshape((-1,) + pages.shape[2:])
    return next_page(end_to_end).reshape(pages.shape), next_page(states)


def sequence_checksums(pages, states) -> tuple[list[int], int]:
    """(each page's checksum, the states' checksum)."""
    return [int(page_checksum(page)) for page in pages], int(
        page_checksum(states))


def kv_hybrid_reference(pools: dict, first, sequence):
    """Whole pools, for a size at which a second set fits.  `pools` maps
    `prefill_pages`, `prefill_states`, `decode_pages`, `decode_states`
    to arrays; `first` is the (pages, states) the first sequence is made
    from; `sequence` holds, per sequence in the order produced, (prefill
    page slots, prefill state slot, decode page slots, decode state slot,
    handed over).  A sequence that was not handed over (a record missing
    or short, a snapshot of another boundary) leaves both decode pools as
    they were.  Returns the pools."""
    import jax.numpy as jnp

    pools = dict(pools)
    pages, states = first
    for page_slots, state_slot, to_pages, to_state, handed_over in sequence:
        pages, states = next_sequence(pages, states)
        pools["prefill_pages"] = pools["prefill_pages"].at[
            jnp.asarray(page_slots)].set(pages)
        pools["prefill_states"] = pools["prefill_states"].at[
            state_slot].set(states)
        if handed_over:
            pools["decode_pages"] = pools["decode_pages"].at[
                jnp.asarray(to_pages)].set(pages)
            pools["decode_states"] = pools["decode_states"].at[
                to_state].set(states)
    return pools


def kv_hybrid_reference_checksums(sums: dict, first_sums, words_per_page: int,
                                  words_per_state: int, sequence):
    """The same at the timed size, where no third pool fits: from the
    initial pools' per-slot checksums (`sums`, keyed as the pools) and
    the first sequence's (`sequence_checksums`), follows the run in
    integers.  Adding c to each of a page's n words adds n*c to its
    checksum; c is the sum of the sequence's page checksums made odd,
    for the states their own checksum made odd; a slot holds the
    checksum of what was written there last.  Returns the four lists."""
    sums = {name: [int(x) for x in pool] for name, pool in sums.items()}
    page_sums, state_sum = [int(x) for x in first_sums[0]], int(first_sums[1])
    for page_slots, state_slot, to_pages, to_state, handed_over in sequence:
        add = (sum(page_sums) & MASK) | 1
        page_sums = [(s + words_per_page * add) & MASK for s in page_sums]
        state_sum = (state_sum + words_per_state * (state_sum | 1)) & MASK
        for slot, s in zip(page_slots, page_sums):
            sums["prefill_pages"][slot] = s
        sums["prefill_states"][state_slot] = state_sum
        if handed_over:
            for slot, s in zip(to_pages, page_sums):
                sums["decode_pages"][slot] = s
            sums["decode_states"][to_state] = state_sum
    return sums
