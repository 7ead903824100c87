"""The bytes the pool programs of the `kv_prefix` cell have to move in
HBM, computed from their shapes.  Kept here, in `work.py`'s form, so
that no later change to the program can move `kvp_pool_roofline`."""

from __future__ import annotations


def produce_hbm_bytes(pages: int, runs: int, block_bytes: int) -> int:
    """`bm_kvp_produce`: each run reads the base page once and writes its
    pages into the slots of both pools; the pages in between need not
    touch HBM, and the count is of what must."""
    return (runs + 2 * pages) * block_bytes


def read_pages_hbm_bytes(pages: int, block_bytes: int) -> int:
    """`kv_pool.read_pages`: the slots read, the pages written out."""
    return 2 * pages * block_bytes


def write_pages_hbm_bytes(pages: int, block_bytes: int) -> int:
    """`kv_pool.write_pages` into a donated pool: the landed pages read,
    their slots written."""
    return 2 * pages * block_bytes
