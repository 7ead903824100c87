"""Where a device-to-host transfer lands (brpc_tpu/rpc/zerocopy.py,
cpp/capi/hostpool_capi.cc): a block the process has touched before.

`LandingArray` stands in for a TPU-resident array as jaxlib 0.9 fetches
one: `copy_to_host_async` allocates the destination as a numpy array,
through numpy's current data-memory handler, on the calling thread.
A block of 1 MB to 1 GB is a registered shm region (PR 34: the KV store
publishes from it where the bytes landed), anything else libc's; a
region's pages fault in on first touch as libc's fresh pages do.  The
counts are page faults, not times.
"""

import ctypes
import gc
import mmap
import resource
import subprocess
import sys
import threading

import numpy as np
import pytest
from numpy._core.multiarray import get_handler_name

from brpc_tpu.rpc import Server, observe, zerocopy
from brpc_tpu.rpc._lib import load_library

MB = 1 << 20
MB64 = 64 << 20
POOLED = "trpc_host_pool"


class LandingArray:
    def __init__(self, nbytes: int):
        self.nbytes = nbytes
        self.shape = (nbytes,)
        self.host = None

    def copy_to_host_async(self) -> None:
        self.host = np.empty(self.nbytes, dtype=np.uint8)

    def __array__(self, dtype=None, copy=None):
        return self.host


@pytest.fixture
def pool():
    lib = load_library()
    lib.trpc_host_pool_idle_bytes.restype = ctypes.c_size_t
    lib.trpc_host_pool_trim.restype = ctypes.c_size_t
    gc.collect()    # what an earlier test left to the collector
    lib.trpc_host_pool_trim()
    yield lib
    lib.trpc_host_pool_trim()


def _faults() -> int:
    return resource.getrusage(resource.RUSAGE_SELF).ru_minflt


def _fetch_and_write(nbytes: int) -> tuple[int, int, str]:
    """One transfer's block written page by page and let go: (the faults
    that cost, the block's address, the handler that allocated it)."""
    before = _faults()
    flat, owner = zerocopy.host_bytes(LandingArray(nbytes))
    flat[::4096] = 1
    cost = _faults() - before
    return cost, flat.ctypes.data, get_handler_name(owner.host)


def _on(where: str, fn, *args):
    if where == "main":
        return fn(*args)
    out = []
    t = threading.Thread(target=lambda: out.append(fn(*args)))
    t.start()
    t.join(60)
    assert not t.is_alive() and out, "the worker thread did not finish"
    return out[0]


@pytest.mark.parametrize("first, second", [
    ("main", "main"), ("worker", "worker"), ("main", "worker"),
    ("worker", "main")])
def test_the_second_fetch_lands_in_the_first_ones_pages(pool, first, second):
    """A non-main glibc arena maps a 64 MB block afresh whatever the
    process's malloc policy says; the list is the handler's own, so the
    thread that starts the transfer (the client's, or the stager's for a
    deferred one) does not matter."""
    fresh, where, handler = _on(first, _fetch_and_write, MB64)
    assert handler == POOLED
    assert pool.trpc_host_pool_idle_bytes() == MB64
    again, where_again, handler = _on(second, _fetch_and_write, MB64)
    assert handler == POOLED and where_again == where
    assert fresh > 0 and again < 0.02 * fresh
    assert pool.trpc_host_pool_idle_bytes() == MB64


def test_jaxlibs_own_fetch_lands_in_a_pooled_block(pool):
    """The one array the CPU backend does not hand over by reference is a
    sub-byte one: jaxlib fetches it as it fetches a TPU's, into a numpy
    array it allocates inside `copy_to_host_async`."""
    import jax.numpy as jnp

    def fetch():
        page = jnp.ones(2 << 20, dtype=jnp.int4) * 2
        view, _ = zerocopy.host_view(page)
        assert isinstance(view, zerocopy.PendingView)
        flat = view.resolve()
        assert flat.size == 2 << 20 and flat[0] == 2 and flat[-1] == 2
        return get_handler_name(np.asarray(page)), flat.ctypes.data

    handler, where = fetch()
    assert handler == POOLED
    gc.collect()             # a jax array is let go by the collector
    assert pool.trpc_host_pool_idle_bytes() == 2 << 20
    assert _on("worker", fetch) == (POOLED, where)


def test_only_the_transfers_own_call_sees_the_handler(pool):
    assert get_handler_name() == "default_allocator"
    view, owner = zerocopy.host_view(LandingArray(1 << 20))
    assert get_handler_name(owner.host) == POOLED
    # Back on this thread at once, and never set on another.
    assert get_handler_name() == "default_allocator"
    assert get_handler_name(np.empty(1 << 20, np.uint8)) == \
        "default_allocator"
    assert _on("worker", get_handler_name) == "default_allocator"
    # A transfer that raises puts it back too.
    class Broken(LandingArray):
        def copy_to_host_async(self):
            raise RuntimeError("the device went away")
    with pytest.raises(RuntimeError):
        zerocopy.host_view(Broken(1 << 20))
    assert get_handler_name() == "default_allocator"
    del view, owner


def test_the_handler_is_made_once(pool):
    zerocopy.host_view(LandingArray(4096))
    made = zerocopy._landing
    zerocopy.host_view(LandingArray(4096))
    assert zerocopy._landing is made
    assert zerocopy._landing_handler() is made


def test_a_process_that_stages_nothing_loads_nothing():
    code = (
        "import sys, numpy as np\n"
        "from numpy._core.multiarray import get_handler_name\n"
        "from brpc_tpu.rpc import zerocopy\n"
        "flat, _ = zerocopy.host_view(np.arange(8, dtype=np.uint32))\n"
        "assert flat.size == 32\n"
        "assert zerocopy._landing is None\n"
        "assert get_handler_name() == 'default_allocator'\n"
        "assert 'brpc_tpu.rpc._lib' not in sys.modules or "
        "sys.modules['brpc_tpu.rpc._lib']._lib is None\n"
        "print('ok')\n")
    done = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]
    assert done.stdout.strip() == "ok"


def test_a_small_block_never_enters_the_list(pool):
    _, _, handler = _fetch_and_write((1 << 20) - 4096)
    assert handler == POOLED
    assert pool.trpc_host_pool_idle_bytes() == 0


def test_a_block_is_handed_only_to_a_request_of_its_size(pool):
    _, where, _ = _fetch_and_write(2 << 20)
    flat, owner = zerocopy.host_bytes(LandingArray(3 << 20))
    assert flat.ctypes.data != where
    assert pool.trpc_host_pool_idle_bytes() == 2 << 20
    del flat, owner
    assert pool.trpc_host_pool_idle_bytes() == 5 << 20
    assert _fetch_and_write(2 << 20)[1] == where


def test_eight_views_made_together_hold_eight_blocks_and_give_them_back(pool):
    class Filled(LandingArray):
        def __init__(self, value: int):
            super().__init__(1 << 20)
            self.value = value

        def copy_to_host_async(self) -> None:
            self.host = np.full(self.nbytes, self.value, dtype=np.uint8)

    arrays = [Filled(i + 1) for i in range(8)]
    views = [zerocopy.host_view(a)[0] for a in arrays]
    # Eight transfers on their way before anyone waits for one.
    assert all(isinstance(v, zerocopy.PendingView) and not v.landed
               for v in views)
    assert all(get_handler_name(a.host) == POOLED for a in arrays)
    assert len({a.host.ctypes.data for a in arrays}) == 8
    assert pool.trpc_host_pool_idle_bytes() == 0
    for i in reversed(range(8)):
        flat = views[i].resolve()
        assert flat.ctypes.data == arrays[i].host.ctypes.data
        assert flat.size == 1 << 20 and (flat == i + 1).all()
    del flat, views, arrays
    assert pool.trpc_host_pool_idle_bytes() == 8 << 20


def test_the_idle_list_is_bounded_and_the_oldest_block_goes(pool):
    # 17 blocks of 64 MB, none written: address space, not memory.
    held = [zerocopy.host_bytes(LandingArray(MB64)) for _ in range(17)]
    addresses = [flat.ctypes.data for flat, _ in held]
    while held:
        held.pop(0)
    assert pool.trpc_host_pool_idle_bytes() == 1 << 30
    # Newest first; the first one given back is the one that went.
    got = [zerocopy.host_bytes(LandingArray(MB64)) for _ in range(16)]
    assert [flat.ctypes.data for flat, _ in got] == addresses[:0:-1]
    del got
    assert pool.trpc_host_pool_trim() == 1 << 30
    assert pool.trpc_host_pool_idle_bytes() == 0


def test_a_pooled_block_is_a_registered_region_and_no_other_is(pool):
    """From the size line to the idle bound a landing block is memory
    the KV store can publish from; a smaller one, and one numpy asked
    zeroed (calloc), are libc's."""
    regions = int(pool.trpc_rma_region_count())
    block = zerocopy.landing_block(2 * MB)
    where = block.ctypes.data
    assert int(pool.trpc_rma_region_count()) == regions + 1
    assert pool.trpc_host_pool_holds(where, 2 * MB)
    assert pool.trpc_host_pool_holds(where + MB, MB)
    assert not pool.trpc_host_pool_holds(where + MB, MB + 1)
    assert not pool.trpc_host_pool_holds(where - 1, 2)
    small = zerocopy.landing_block(MB - 4096)
    assert get_handler_name(small) == POOLED
    assert not pool.trpc_host_pool_holds(small.ctypes.data, 1)
    zeroed = zerocopy._landing.set_handler(zerocopy._landing.capsule)
    try:
        cleared = np.zeros(2 * MB, dtype=np.uint8)
    finally:
        zerocopy._landing.set_handler(zeroed)
    assert get_handler_name(cleared) == POOLED
    assert not pool.trpc_host_pool_holds(cleared.ctypes.data, 1)
    del small, cleared
    assert pool.trpc_host_pool_idle_bytes() == 0
    # Idle, the region stays; trimmed, it goes.
    del block
    assert pool.trpc_host_pool_idle_bytes() == 2 * MB
    assert pool.trpc_host_pool_holds(where, 2 * MB)
    assert int(pool.trpc_rma_region_count()) == regions + 1
    assert pool.trpc_host_pool_trim() == 2 * MB
    assert not pool.trpc_host_pool_holds(where, 1)
    assert int(pool.trpc_rma_region_count()) == regions


@pytest.mark.parametrize("before, after", [
    (2 * MB, 3 * MB), (3 * MB, 2 * MB), (2 * MB, MB // 2), (MB // 2, 2 * MB),
    (2 * MB, 2 * MB)], ids=["grown", "shrunk", "under_the_line",
                            "over_the_line", "the_same"])
def test_realloc_keeps_the_bytes_and_each_kind_its_own(pool, before, after):
    """numpy's in-place resize goes through the handler's realloc: a
    region moves to a block of the new size (a region again where that is
    pooled) and goes back to the list; libc's block stays libc's."""
    block = zerocopy.landing_block(before)
    block[:] = np.arange(before, dtype=np.uint32).view(np.uint8)[:before]
    where, was_held = block.ctypes.data, before >= MB
    assert bool(pool.trpc_host_pool_holds(where, before)) == was_held
    block.resize(after, refcheck=False)
    kept = min(before, after)
    assert np.array_equal(
        block[:kept], np.arange(before, dtype=np.uint32).view(np.uint8)[:kept])
    assert get_handler_name(block) == POOLED
    # A region's successor is pooled by its size; libc's is realloc's.
    assert bool(pool.trpc_host_pool_holds(block.ctypes.data, after)) == (
        was_held and after >= MB)
    if was_held and after != before:
        assert pool.trpc_host_pool_idle_bytes() == before
        assert _fetch_and_write(before)[1] == where
    elif after == before:
        assert block.ctypes.data == where
    now_held = was_held and after >= MB
    del block
    assert pool.trpc_host_pool_idle_bytes() == (
        (before if was_held and after != before else 0)
        + (after if now_held else 0))


def test_the_idle_bound_frees_the_oldest_region(pool):
    regions = int(pool.trpc_rma_region_count())
    held = [zerocopy.landing_block(MB64) for _ in range(17)]
    assert int(pool.trpc_rma_region_count()) == regions + 17
    oldest = held[0].ctypes.data
    while held:
        held.pop(0)
    assert pool.trpc_host_pool_idle_bytes() == 1 << 30
    assert int(pool.trpc_rma_region_count()) == regions + 16
    assert not pool.trpc_host_pool_holds(oldest, 1)
    assert pool.trpc_host_pool_trim() == 1 << 30
    assert int(pool.trpc_rma_region_count()) == regions


def test_process_faults_minor_counts_fresh_pages():
    srv = Server()           # a serving process exposes its process vars
    srv.register_native_echo("Echo.Echo")
    srv.start(0)
    try:
        before = observe.Vars.dump()["process_faults_minor"]
        with mmap.mmap(-1, MB64) as fresh:
            fresh.madvise(mmap.MADV_NOHUGEPAGE)
            np.frombuffer(fresh, dtype=np.uint8)[::4096] = 1
            after = observe.Vars.dump()["process_faults_minor"]
        assert after - before >= 16000
    finally:
        srv.stop()
