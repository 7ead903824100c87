"""The bytes the pool programs of the `kv_prefix_linear` cell have to
move in HBM, computed from their shapes.  Kept here, in `work.py`'s
form, so that no later change to the program can move
`kvl_pool_roofline`.  P is a page's bytes, S a state's."""

from __future__ import annotations

from benchmark import work_kv_prefix


def produce_hbm_bytes(pages: int, runs: int, page_bytes: int) -> int:
    """`bm_kvl_produce`: `bm_kvp_produce`'s count (the base page once a
    run, each page written into both pools)."""
    return work_kv_prefix.produce_hbm_bytes(pages, runs, page_bytes)


def state_produce_hbm_bytes(pages: int, runs: int, page_bytes: int,
                            state_bytes: int) -> int:
    """`bm_kvl_state_produce`: each run reads its new pages in the
    admitting pool and the session's state, and writes the new state
    into both state pools."""
    return pages * page_bytes + 3 * runs * state_bytes


def read_pages_hbm_bytes(pages: int, page_bytes: int) -> int:
    return work_kv_prefix.read_pages_hbm_bytes(pages, page_bytes)


def write_pages_hbm_bytes(pages: int, page_bytes: int) -> int:
    return work_kv_prefix.write_pages_hbm_bytes(pages, page_bytes)


def read_state_hbm_bytes(states: int, state_bytes: int) -> int:
    """`kv_pool.read_page` on the state pool: the slot read, the state
    written out."""
    return 2 * states * state_bytes


def write_state_hbm_bytes(states: int, state_bytes: int) -> int:
    """`kv_pool.write_page` into the donated state pool: the landed
    state read, its slot written."""
    return 2 * states * state_bytes
