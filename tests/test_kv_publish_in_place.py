"""A page or a sequence is published out of the block its device-to-host
transfer landed in (brpc_tpu/rpc/kv.py `_publish_records`, the host pool
of cpp/capi/hostpool_capi.cc): no byte is copied on the host, and the
block stays out of the pool's idle list until the last record published
from it is gone, whatever became of the view and the array.

`Landed` stands in for a TPU-resident array as `test_host_pool.py`'s
`LandingArray` does: its transfer allocates the destination through
numpy's current handler, so a `PendingView` of it lands in a pooled
block.  Small sizes; nothing here is a measurement.
"""

import ctypes
import gc
import glob
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from brpc_tpu.rpc import (Channel, RmaBuffer, RpcError, Server, kv, observe,
                          zerocopy)
from brpc_tpu.rpc._lib import load_library
from test_host_pool import LandingArray

MB = 1 << 20
LAYERS, RECORD = 8, 131072          # a page of 8 records: one 1 MB block
LEASE = 600000
COUNTERS = ("kv_publish_in_place_bytes", "kv_publish_copy_bytes",
            "host_pool_hit_bytes", "host_pool_miss_bytes")
# A cache of two kinds: 4 paged layers, 3 snapshot layers.
LAYOUT = kv.KvCacheLayout(
    (kv.PAGED, kv.SNAPSHOT, kv.PAGED, kv.PAGED, kv.SNAPSHOT, kv.PAGED,
     kv.SNAPSHOT),
    (RECORD, 3 * RECORD, RECORD, RECORD, 3 * RECORD, RECORD, 3 * RECORD))
SEQ_PAGES = 2                       # 8 page records, 1 MB; 3 states, 1.125 MB


class Landed(LandingArray):
    """A device array whose bytes are `data`'s, landed when the transfer
    is started."""

    def __init__(self, data: np.ndarray):
        super().__init__(data.nbytes)
        self.shape = data.shape
        self._data = data

    def copy_to_host_async(self) -> None:
        super().copy_to_host_async()
        self.host[:] = self._data.reshape(-1).view(np.uint8)


def _vars() -> dict:
    dump = observe.Vars.dump()
    return {name: dump.get(name, 0) for name in COUNTERS}


def _moved(before: dict) -> dict:
    return {name: value - before[name] for name, value in _vars().items()}


def _bytes(rng, *shape) -> np.ndarray:
    return rng.integers(0, 256, size=shape, dtype=np.uint8)


def _view(data: np.ndarray):
    """(view, array, address of the block it landed in)."""
    view, array = zerocopy.host_view(Landed(data))
    assert isinstance(view, zerocopy.PendingView)
    return view, array, view.resolve().ctypes.data


def _lands_at(nbytes: int) -> int:
    """Where the next transfer of `nbytes` lands; the block is let go."""
    flat, owner = zerocopy.host_bytes(LandingArray(nbytes))
    where = flat.ctypes.data
    del flat, owner
    return where


@pytest.fixture
def plane():
    """One in-process node (store and registry) with a registry client
    and a decode-side client over shm, and the host pool empty."""
    lib = load_library()
    lib.trpc_host_pool_idle_bytes.restype = ctypes.c_size_t
    lib.trpc_host_pool_trim.restype = ctypes.c_size_t
    kv.reset()
    gc.collect()
    lib.trpc_host_pool_trim()
    srv = Server()
    srv.enable_kv_store()
    srv.enable_kv_registry()
    srv.start(0)
    addr = f"127.0.0.1:{srv.port}"
    reg = kv.KvRegistryClient(Channel(addr, timeout_ms=10000),
                              owns_channel=True)
    cli = kv.KvClient(addr, use_shm=True, timeout_ms=10000)
    yield addr, reg, cli, lib
    cli.close()
    reg.close()
    srv.stop()
    kv.reset()
    gc.collect()
    lib.trpc_host_pool_trim()


def _fetched_page(cli, block_id: int) -> np.ndarray:
    return cli.fetch_page(block_id, np.zeros((LAYERS, RECORD), np.uint8))


def _fetched_sequence(cli, seq_id: int):
    return cli.fetch_sequence(
        seq_id, LAYOUT, np.zeros((SEQ_PAGES, 4, RECORD), np.uint8),
        np.zeros((3, 3 * RECORD), np.uint8))


def test_a_page_is_published_out_of_the_block_its_view_landed_in(plane):
    addr, reg, cli, _lib = plane
    data = _bytes(np.random.default_rng(1), LAYERS, RECORD)
    view, _array, where = _view(data)
    before = _vars()
    with RmaBuffer(data.nbytes) as slab:
        metas = kv.publish_page(3, view, slab, lease_ms=LEASE, node=addr,
                                registry=reg)
        assert _moved(before)["kv_publish_in_place_bytes"] == data.nbytes
        assert _moved(before)["kv_publish_copy_bytes"] == 0
        # Each record where it lies in the block: a real rkey, not the
        # slab's, and the slab untouched.
        (rkey,) = {m.rkey for m in metas}
        assert rkey not in (0, slab.rkey)
        assert [m.off for m in metas] == [i * RECORD for i in range(LAYERS)]
        assert load_library().trpc_host_pool_holds(where, data.nbytes)
        assert not np.frombuffer(slab.view, np.uint8).any()
        assert np.array_equal(_fetched_page(cli, 3), data)
        kv.withdraw_page(3, LAYERS, registry=reg)
    assert kv.store_count() == 0


@pytest.mark.parametrize("from_views", [
    (True, True), (True, False), (False, True), (False, False)],
    ids=["both_views", "states_numpy", "pages_numpy", "both_numpy"])
def test_a_sequence_of_two_kinds_is_published_source_by_source(
        plane, from_views):
    """Each source where it can be observed to lie: a view's block is
    published in place, a numpy array of the caller's goes through the
    slab at its place among the sequence's bytes."""
    addr, reg, cli, _lib = plane
    rng = np.random.default_rng(2)
    pages = _bytes(rng, SEQ_PAGES, 4, RECORD)
    states = _bytes(rng, 3, 3 * RECORD)
    held = []

    def source(data, as_view):
        if not as_view:
            return data
        held.append(_view(data))
        return held[-1][0]

    before = _vars()
    with RmaBuffer(MB + LAYOUT.sequence_bytes(SEQ_PAGES)) as slab:
        metas = kv.publish_sequence(
            31, LAYOUT, source(pages, from_views[0]),
            source(states, from_views[1]), slab, offset=MB, lease_ms=LEASE,
            node=addr, registry=reg)
        in_place = sum(data.nbytes for data, as_view in
                       zip((pages, states), from_views) if as_view)
        moved = _moved(before)
        assert moved["kv_publish_in_place_bytes"] == in_place
        assert moved["kv_publish_copy_bytes"] == (
            pages.nbytes + states.nbytes - in_place)
        assert len(metas) == SEQ_PAGES * 4 + 3
        slab_bytes = np.frombuffer(slab.view, np.uint8)
        assert not slab_bytes[:MB].any()
        at_pages = slab_bytes[MB:MB + pages.nbytes]
        at_states = slab_bytes[MB + pages.nbytes:]
        assert np.array_equal(at_pages, pages.reshape(-1)) != from_views[0]
        assert at_pages.any() != from_views[0]
        assert np.array_equal(at_states, states.reshape(-1)) != from_views[1]
        assert at_states.any() != from_views[1]
        got_pages, got_states = _fetched_sequence(cli, 31)
        assert np.array_equal(got_pages, pages)
        assert np.array_equal(got_states, states)
        kv.withdraw_sequence(31, LAYOUT, SEQ_PAGES, registry=reg)
    assert kv.store_count() == 0


def test_a_published_block_is_no_other_transfers_until_it_is_withdrawn(
        plane):
    """The view and the array are dropped at once: the store keeps the
    block, and the pool hands it to nobody until the page is withdrawn."""
    addr, reg, cli, lib = plane
    rng = np.random.default_rng(3)
    first, second = _bytes(rng, LAYERS, RECORD), _bytes(rng, LAYERS, RECORD)
    view, array, where = _view(first)
    with RmaBuffer(first.nbytes) as slab:
        kv.publish_page(5, view, slab, lease_ms=LEASE, node=addr,
                        registry=reg)
        del view, array
        gc.collect()
        assert lib.trpc_host_pool_idle_bytes() == 0     # parked, not idle
        assert lib.trpc_host_pool_holds(where, first.nbytes)
        before = _vars()
        other_view, other_array, elsewhere = _view(second)
        assert elsewhere != where
        assert _moved(before)["host_pool_miss_bytes"] == second.nbytes
        assert np.array_equal(_fetched_page(cli, 5), first)
        del other_view, other_array
        assert lib.trpc_host_pool_idle_bytes() == second.nbytes
        assert lib.trpc_host_pool_trim() == second.nbytes   # not the page's
        assert np.array_equal(_fetched_page(cli, 5), first)
        kv.withdraw_page(5, LAYERS, registry=reg)
        # The last record gone, the block is the pool's again, and the
        # next transfer of its size lands in it.
        before = _vars()
        assert _lands_at(first.nbytes) == where
        assert _moved(before)["host_pool_hit_bytes"] == first.nbytes
        assert lib.trpc_host_pool_idle_bytes() == first.nbytes


def test_a_sequences_two_blocks_come_back_when_it_is_withdrawn(plane):
    addr, reg, cli, lib = plane
    rng = np.random.default_rng(4)
    pages = _bytes(rng, SEQ_PAGES, 4, RECORD)
    states = _bytes(rng, 3, 3 * RECORD)
    pages_view, pages_array, pages_at = _view(pages)
    states_view, states_array, states_at = _view(states)
    with RmaBuffer(LAYOUT.sequence_bytes(SEQ_PAGES)) as slab:
        kv.publish_sequence(9, LAYOUT, pages_view, states_view, slab,
                            lease_ms=LEASE, node=addr, registry=reg)
        del pages_view, pages_array, states_view, states_array
        gc.collect()
        assert lib.trpc_host_pool_idle_bytes() == 0
        assert _lands_at(pages.nbytes) != pages_at
        assert _lands_at(states.nbytes) != states_at
        lib.trpc_host_pool_trim()
        got_pages, got_states = _fetched_sequence(cli, 9)
        assert np.array_equal(got_pages, pages)
        assert np.array_equal(got_states, states)
        kv.withdraw_sequence(9, LAYOUT, SEQ_PAGES, registry=reg)
        assert lib.trpc_host_pool_idle_bytes() == pages.nbytes + states.nbytes
        assert _lands_at(pages.nbytes) == pages_at
        assert _lands_at(states.nbytes) == states_at


def test_a_numpy_source_goes_through_the_slab_and_is_counted_there(plane):
    addr, reg, cli, _lib = plane
    data = _bytes(np.random.default_rng(5), LAYERS, RECORD)
    before = _vars()
    with RmaBuffer(2 * data.nbytes) as slab:
        metas = kv.publish_page(7, data, slab, offset=data.nbytes,
                                lease_ms=LEASE, node=addr, registry=reg)
        assert _moved(before)["kv_publish_copy_bytes"] == data.nbytes
        assert _moved(before)["kv_publish_in_place_bytes"] == 0
        assert {m.rkey for m in metas} == {slab.rkey}
        assert [m.off for m in metas] == [
            data.nbytes + i * RECORD for i in range(LAYERS)]
        # The caller's array is the caller's again at once.
        data[:] = 0
        assert _fetched_page(cli, 7).any()
        kv.withdraw_page(7, LAYERS, registry=reg)


def test_a_view_under_the_pools_size_line_goes_through_the_slab(plane):
    """A transfer under 1 MB lands in libc's memory, which the store
    cannot serve: copied, as any source outside the pool is."""
    addr, reg, cli, lib = plane
    data = _bytes(np.random.default_rng(6), LAYERS, RECORD // 2)
    view, _array, where = _view(data)
    assert not lib.trpc_host_pool_holds(where, data.nbytes)
    before = _vars()
    with RmaBuffer(data.nbytes) as slab:
        kv.publish_page(8, view, slab, lease_ms=LEASE, node=addr,
                        registry=reg)
        assert _moved(before)["kv_publish_copy_bytes"] == data.nbytes
        got = cli.fetch_page(8, np.zeros((LAYERS, RECORD // 2), np.uint8))
        assert np.array_equal(got, data)
        kv.withdraw_page(8, LAYERS, registry=reg)


@pytest.mark.parametrize("live", ["first_record", "last_record"])
def test_a_refused_publish_leaves_the_live_record_and_its_block(plane, live):
    """KvExistsError: the live record keeps its bytes and its block, the
    records the refused call had published are taken back, and the block
    it came with is held by nobody."""
    addr, reg, cli, lib = plane
    rng = np.random.default_rng(7)
    first, second = _bytes(rng, LAYERS, RECORD), _bytes(rng, LAYERS, RECORD)
    view, array, where = _view(first)
    layer = 0 if live == "first_record" else LAYERS - 1
    with RmaBuffer(first.nbytes) as slab:
        kv.publish_page(11, view, slab, lease_ms=LEASE, node=addr,
                        registry=reg)
        # All of the page but one record withdrawn: the next publish
        # meets the live one first, or after LAYERS - 1 of its own.
        for other in range(LAYERS):
            if other != layer:
                kv.withdraw(kv.page_record_id(11, other))
        del view, array
        gc.collect()
        before = _vars()
        again, again_array, elsewhere = _view(second)
        with pytest.raises(kv.KvExistsError):
            kv.publish_page(11, again, slab, lease_ms=LEASE, node=addr)
        assert kv.store_count() == 1
        assert _moved(before)["kv_publish_in_place_bytes"] == 0
        assert _moved(before)["kv_publish_copy_bytes"] == 0
        record = np.zeros(RECORD, np.uint8)
        cli.fetch(kv.page_record_id(11, layer), resp_buf=record)
        assert np.array_equal(record, first[layer])
        # The refused block is free the moment its array is dropped; the
        # live record's is not.
        del again, again_array
        assert lib.trpc_host_pool_idle_bytes() == second.nbytes
        assert _lands_at(second.nbytes) == elsewhere
        assert lib.trpc_host_pool_holds(where, first.nbytes)
        kv.withdraw(kv.page_record_id(11, layer))
        assert lib.trpc_host_pool_idle_bytes() == 2 * first.nbytes


@pytest.mark.parametrize("how", ["reset", "lease_lapsed_and_replaced",
                                 "lease_lapsed_and_asked_for"])
def test_a_block_comes_back_however_its_records_go(plane, how):
    addr, _reg, cli, lib = plane
    rng = np.random.default_rng(8)
    first, second = _bytes(rng, LAYERS, RECORD), _bytes(rng, LAYERS, RECORD)
    view, array, where = _view(first)
    with RmaBuffer(first.nbytes) as slab:
        kv.publish_page(13, view, slab, node=addr,
                        lease_ms=LEASE if how == "reset" else 50)
        del view, array
        gc.collect()
        assert lib.trpc_host_pool_idle_bytes() == 0
        if how == "reset":
            kv.reset()
        else:
            time.sleep(0.1)
            if how == "lease_lapsed_and_replaced":
                again, _again_array, elsewhere = _view(second)
                assert elsewhere != where
                kv.publish_page(13, again, slab, lease_ms=LEASE, node=addr)
            else:
                for layer in range(LAYERS):
                    with pytest.raises(RpcError):
                        cli._node_channel(addr).call(
                            kv.FETCH_METHOD,
                            kv._req(kv.page_record_id(13, layer),
                                    generation=1))
        assert lib.trpc_host_pool_idle_bytes() == first.nbytes
        assert _lands_at(first.nbytes) == where


def test_threads_that_publish_and_land_at_once_never_share_a_block(plane):
    """More threads than cores' worth of publishers, each dropping its
    view at once and landing another page of the same size while the
    first is still published: no fetch ever returns another page's
    bytes, and every block comes back."""
    addr, _reg, _cli, lib = plane
    threads, seconds = 6, 2.0
    errors, rounds = [], [0] * threads

    def worker(me: int) -> None:
        cli = kv.KvClient(addr, use_shm=True, timeout_ms=10000)
        try:
            deadline = time.monotonic() + seconds
            while time.monotonic() < deadline:
                rounds[me] += 1
                tag = (me * 31 + rounds[me]) % 251 + 1
                page = np.full((LAYERS, RECORD), tag, np.uint8)
                view, array, _ = _view(page)
                with RmaBuffer(page.nbytes) as slab:
                    kv.publish_page(100 + me, view, slab, lease_ms=LEASE,
                                    node=addr, registry=cli.registry)
                    del view, array
                    other = _view(np.zeros((LAYERS, RECORD), np.uint8))
                    got = _fetched_page(cli, 100 + me)
                    if not (got == tag).all():
                        errors.append((me, rounds[me], int(got.max())))
                    del other
                    kv.withdraw_page(100 + me, LAYERS,
                                     registry=cli.registry)
                    for layer in range(LAYERS):
                        cli.invalidate(kv.page_record_id(100 + me, layer))
        except Exception as e:  # noqa: BLE001 - reported by the assert below
            errors.append((me, rounds[me], repr(e)))
        finally:
            cli.close()

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        workers = [threading.Thread(target=worker, args=(i,))
                   for i in range(threads)]
        for t in workers:
            t.start()
        for t in workers:
            t.join(seconds + 60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in workers)
    assert not errors, errors[:5]
    assert min(rounds) >= 2
    gc.collect()
    # Every block is the pool's again: two a thread at most were made.
    assert 0 < lib.trpc_host_pool_idle_bytes() <= 2 * threads * MB
    assert kv.store_count() == 0


def test_a_process_that_staged_and_published_leaves_no_shm_name_behind():
    """Its pool blocks are shm regions: the idle one, the one a record is
    still served from and the one numpy still holds are all unlinked
    when the process ends normally, as its slab is by its `free`."""
    code = (
        "import os, sys, glob, numpy as np\n"
        "sys.path.insert(0, 'tests')\n"
        "from brpc_tpu.rpc import RmaBuffer, kv, zerocopy\n"
        "from test_host_pool import LandingArray\n"
        "MB = 1 << 20\n"
        "idle = zerocopy.host_bytes(LandingArray(3 * MB))\n"
        "del idle\n"
        "kept = zerocopy.host_bytes(LandingArray(2 * MB))\n"
        "page = LandingArray(MB)\n"
        "page.shape = (8, MB // 8)\n"
        "view, page = zerocopy.host_view(page)\n"
        "slab = RmaBuffer(MB)\n"
        "kv.publish_page(1, view, slab)\n"
        "del view, page\n"
        "mine = glob.glob(f'/dev/shm/trpc_*_{os.getpid()}_*')\n"
        "assert len(mine) == 4, mine\n"
        "slab.free()\n"
        "print(os.getpid())\n")
    done = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]
    pid = int(done.stdout.strip())
    assert glob.glob(f"/dev/shm/trpc_*_{pid}_*") == []
