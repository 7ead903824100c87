"""A run's content hashes walked four pages at a time (PR 38):
`kv_content_hash_lanes` gives every page the key `kv_content_hash`
gives it alone, through the C ABI and through `kv.publish_prefix_run`,
whose one call hashes the run in groups of four and admits its pages in
order; `kv_prefix_hash_lanes` counts the pages grouped.  The golden key
pins the fleet's dedup key to the parent commit's."""

import ctypes

import numpy as np
import pytest

from brpc_tpu.rpc import kv, observe, zerocopy
from brpc_tpu.rpc._lib import load_library

# The parent commit's kv.content_hash of golden_page() (ec6ed2e).
GOLDEN = {
    "tokens": (14640576220011521506, 4767723862699552667),
    "no_tokens": (79452613361668218, 17023112332349740944),
    "tail": (15478459764412775864, 6846606557438701581),
}


def golden_page() -> np.ndarray:
    """36,864 B from a fixed formula, not a generator's stream."""
    words = (np.arange(1, 36864 // 8 + 1, dtype=np.uint64)
             * np.uint64(0x9E3779B97F4A7C15)) ^ np.uint64(38)
    return words.view(np.uint8)


def golden_tokens() -> list[int]:
    return [7 * t + 38 for t in range(64)]


def counted() -> dict:
    return {k: v for k, v in observe.Vars.dump().items()
            if k.startswith("kv_prefix_") and isinstance(v, (int, float))}


def hash_lanes(pages, spans) -> list[tuple[int, int]]:
    """trpc_kv_content_hash_lanes over `pages` (equal lengths)."""
    lib = load_library()
    n = len(pages)
    arrays = [(ctypes.c_uint64 * max(len(s), 1))(*s) for s in spans]
    hi, lo = (ctypes.c_uint64 * n)(), (ctypes.c_uint64 * n)()
    lib.trpc_kv_content_hash_lanes(
        (ctypes.c_void_p * n)(*(p.ctypes.data for p in pages)),
        pages[0].nbytes,
        (ctypes.POINTER(ctypes.c_uint64) * n)(
            *(ctypes.cast(a, ctypes.POINTER(ctypes.c_uint64))
              for a in arrays)),
        (ctypes.c_uint64 * n)(*(len(s) for s in spans)), n, hi, lo)
    return list(zip(hi, lo))


def distinct_pages(n: int, nbytes: int, seed: int) -> list[np.ndarray]:
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, nbytes, dtype=np.uint8) for _ in range(n)]


@pytest.fixture
def store():
    kv.reset()
    yield
    kv.reset()


def test_the_golden_key_is_the_parent_commits():
    page, toks = golden_page(), golden_tokens()
    assert kv.content_hash(page, toks) == GOLDEN["tokens"]
    assert kv.content_hash(page, []) == GOLDEN["no_tokens"]
    assert kv.content_hash(page[:36861], toks[:5]) == GOLDEN["tail"]
    assert hash_lanes([page, page[:36864]], [toks, []]) == [
        GOLDEN["tokens"], GOLDEN["no_tokens"]]


@pytest.mark.parametrize("n", [1, 2, 3, 4])
@pytest.mark.parametrize("nbytes", [36864, 36861, 13, 8, 5])
def test_each_lane_is_the_pages_own_hash(n, nbytes):
    """Lengths with a tail that is not a word, spans empty and not."""
    pages = distinct_pages(n, nbytes, seed=n * 1000 + nbytes)
    spans = [list(range(j, j + 3 * j)) for j in range(n)]
    assert hash_lanes(pages, spans) == [
        kv.content_hash(p, s) for p, s in zip(pages, spans)]


@pytest.mark.parametrize("n", [2, 3, 4])
@pytest.mark.parametrize("differ", ["last_word", "tail_byte", "tokens"])
def test_pages_that_differ_in_one_place_part_in_their_lanes(n, differ):
    nbytes = 36861 if differ == "tail_byte" else 36864
    base = distinct_pages(1, nbytes, seed=7)[0]
    pages = [base.copy() for _ in range(n)]
    spans = [[1, 2, 3] for _ in range(n)]
    for j in range(1, n):
        if differ == "tokens":
            spans[j] = [1, 2, 3 + j]
        else:
            pages[j][-1] ^= j
    got = hash_lanes(pages, spans)
    assert got == [kv.content_hash(p, s) for p, s in zip(pages, spans)]
    assert len(set(got)) == n


@pytest.mark.parametrize("n", [1, 2, 3, 4, 5, 6, 16, 17])
def test_a_run_publishes_under_each_pages_own_key(store, n):
    """One call of the store for the run: every key is kv.content_hash's
    (the dedup contract), the pages admitted in order at their depths,
    and the pages hashed in a group of two or more counted."""
    nbytes = 36864
    pages = np.concatenate(distinct_pages(n, nbytes, seed=100 + n))
    spans = [list(range(j * 16, (j + 1) * 16)) if j % 3 else []
             for j in range(n)]
    keys = [(500 + n, j + 1) for j in range(n)]
    before = counted()
    out = kv.publish_prefix_run(keys, 3, pages, spans, lease_ms=60000)
    got = {k: v - before.get(k, 0) for k, v in counted().items()}
    assert [fresh for _, fresh in out] == [True] * n
    for j, (meta, _) in enumerate(out):
        block = pages[j * nbytes:(j + 1) * nbytes]
        assert meta.hash == kv.content_hash(block, spans[j])
        assert meta.key == keys[j] and meta.depth == 3 + j
        assert meta.length == nbytes and meta.generation >= 1
    grouped = n - (n % 4 == 1)
    assert got["kv_prefix_hash_lanes"] == grouped
    assert got["kv_prefix_publish_total"] == n
    assert got["kv_prefix_publish_bytes"] == n * nbytes
    # Offered again, the run renews every page: the same keys, nothing
    # admitted, the same pages grouped.
    before = counted()
    again = kv.publish_prefix_run(keys, 3, pages, spans, lease_ms=60000)
    got = {k: v - before.get(k, 0) for k, v in counted().items()}
    assert [fresh for _, fresh in again] == [False] * n
    assert [m.hash for m, _ in again] == [m.hash for m, _ in out]
    assert got["kv_prefix_publish_renewed"] == n
    assert got["kv_prefix_hash_lanes"] == grouped


def test_a_page_published_alone_dedups_against_its_run(store):
    """prefix_publish is a run of one: same key, no lane counted."""
    nbytes = 36864
    pages = np.concatenate(distinct_pages(5, nbytes, seed=11))
    spans = [[j] for j in range(5)]
    out = kv.publish_prefix_run([(9, j + 1) for j in range(5)], 0, pages,
                                spans, lease_ms=60000)
    before = counted()
    meta, fresh = kv.prefix_publish((9, 5), 4, pages[4 * nbytes:], [4],
                                    lease_ms=60000)
    got = {k: v - before.get(k, 0) for k, v in counted().items()}
    assert not fresh and meta.hash == out[4][0].hash
    assert meta.generation == out[4][0].generation
    assert got["kv_prefix_hash_lanes"] == 0
    assert got["kv_prefix_publish_renewed"] == 1


def test_a_run_in_a_landing_block_is_taken_in_place_page_by_page(store):
    nbytes = 1 << 18
    landed = zerocopy.landing_block(5 * nbytes)
    landed[:] = np.concatenate(distinct_pages(5, nbytes, seed=12))
    before = counted()
    out = kv.publish_prefix_run([(10, j + 1) for j in range(5)], 0, landed,
                                [[j] for j in range(5)], lease_ms=60000)
    got = {k: v - before.get(k, 0) for k, v in counted().items()}
    assert got["kv_prefix_publish_in_place_bytes"] == 5 * nbytes
    assert got["kv_prefix_publish_copy_bytes"] == 0
    for j, (meta, _) in enumerate(out):
        assert meta.hash == kv.content_hash(
            landed[j * nbytes:(j + 1) * nbytes], [j])
        kv.prefix_withdraw(meta.hash)


def test_a_page_the_store_cannot_take_ends_the_run(store):
    """A key of zero fails its page (-1): MemoryError, the pages before
    it published, the pages after it not."""
    nbytes = 36864
    pages = np.concatenate(distinct_pages(6, nbytes, seed=13))
    keys = [(11, 1), (11, 2), (0, 0), (11, 4), (11, 5), (11, 6)]
    before = counted()
    with pytest.raises(MemoryError):
        kv.publish_prefix_run(keys, 0, pages, [[j] for j in range(6)],
                              lease_ms=60000)
    got = {k: v - before.get(k, 0) for k, v in counted().items()}
    assert got["kv_prefix_publish_total"] == 2
    assert kv.prefix_store_count() == 2
