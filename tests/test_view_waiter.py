"""Who waits for a transfer that `zerocopy.host_view` started
(brpc_tpu/rpc/zerocopy.py's waiter thread, the counters of
cpp/capi/hostpool_capi.cc): the program itself, from the moment it
starts, unless the view is under the landing pool's size line or its
`resolve()` was entered first.

`SlowArray` stands in for a TPU-resident array: its transfer allocates
the landing block when it is started (through numpy's current handler,
as jaxlib does) and "lands" when the test lets it, or after a fixed time;
`__array__` blocks until then, as `np.asarray` of a device array does.
Times here are the fake's own; nothing is a measurement.
"""

import ctypes
import gc
import sys
import threading
import time

import numpy as np
import pytest

from brpc_tpu.rpc import observe, zerocopy
from brpc_tpu.rpc._lib import load_library

MB = 1 << 20
VIEW_COUNTERS = ("host_view_bytes", "host_view_ahead_bytes",
                 "host_view_wait_us", "host_view_transfer_us")
POOL_COUNTERS = ("host_pool_hit_bytes", "host_pool_miss_bytes")


class SlowArray:
    def __init__(self, nbytes: int, takes_s: float | None = None,
                 fails: bool = False):
        self.nbytes = nbytes
        self.shape = (nbytes,)
        self.takes_s = takes_s      # None: until release()
        self.fails = fails
        self.host = None
        self.fetches = 0            # how often somebody waited for it
        self.fetched = threading.Event()
        self._go = threading.Event()
        self._started = 0.0

    def copy_to_host_async(self) -> None:
        self.host = np.empty(self.nbytes, dtype=np.uint8)
        self._started = time.monotonic()

    def release(self) -> None:
        self._go.set()

    def __array__(self, dtype=None, copy=None):
        self.fetches += 1
        if self.takes_s is None:
            assert self._go.wait(30), "the test never released this fetch"
        else:
            time.sleep(max(0.0, self._started + self.takes_s
                           - time.monotonic()))
        if self.fails:
            raise RuntimeError("the device went away")
        self.fetched.set()
        return self.host


@pytest.fixture
def pool():
    lib = load_library()
    lib.trpc_host_pool_idle_bytes.restype = ctypes.c_size_t
    lib.trpc_host_pool_trim.restype = ctypes.c_size_t
    lib.trpc_host_pool_min_bytes.restype = ctypes.c_size_t
    _waiter_gone()
    gc.collect()    # what an earlier test left to the collector lands in
    lib.trpc_host_pool_trim()   # the list now, not during this test
    yield lib
    lib.trpc_host_pool_trim()


def _read(names=VIEW_COUNTERS + POOL_COUNTERS) -> dict:
    dump = observe.Vars.dump()
    return {name: dump.get(name, 0) for name in names}


def _moved(before: dict) -> dict:
    return {name: value - before[name]
            for name, value in _read(tuple(before)).items()}


def _waiters() -> list:
    return [t for t in threading.enumerate() if t.name == "trpc-view-waiter"]


def _waiter_gone(within_s: float = 10.0) -> None:
    deadline = time.monotonic() + within_s
    while _waiters() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert not _waiters(), "the idle waiter did not end"


def _until(condition, within_s: float = 10.0) -> bool:
    deadline = time.monotonic() + within_s
    while not condition() and time.monotonic() < deadline:
        time.sleep(0.002)
    return condition()


def test_the_size_line_is_the_landing_pools(pool):
    assert pool.trpc_host_pool_min_bytes() == MB


@pytest.mark.parametrize("nbytes", [MB, 3 * MB + 4096])
def test_a_large_view_lands_with_nobody_asking_and_counts_as_ahead(
        pool, nbytes):
    array = SlowArray(nbytes, takes_s=0.0)
    before = _read()
    view, owner = zerocopy.host_view(array)
    assert isinstance(view, zerocopy.PendingView) and owner is array
    assert array.fetched.wait(10), "nobody waited for the transfer"
    assert not view.landed            # no caller has taken the bytes yet
    flat = view.resolve()
    assert view.landed and flat.ctypes.data == array.host.ctypes.data
    assert view.resolve() is flat and array.fetches == 1
    moved = _moved(before)
    assert moved["host_view_bytes"] == nbytes
    assert moved["host_view_ahead_bytes"] == nbytes


@pytest.mark.parametrize("nbytes", [1024, MB - 1])
def test_a_view_under_the_line_is_never_queued(pool, nbytes):
    array = SlowArray(nbytes, takes_s=0.0)
    before = _read()
    view, _ = zerocopy.host_view(array)
    time.sleep(0.1)
    assert array.fetches == 0 and not _waiters()
    assert view.resolve().nbytes == nbytes
    moved = _moved(before)
    # Counted all the same, by its caller's own wait: not ahead.
    assert moved["host_view_bytes"] == nbytes
    assert moved["host_view_ahead_bytes"] == 0


@pytest.mark.parametrize("entered_by", ["resolve", "waited_for"])
def test_a_view_somebody_already_waits_for_is_left_alone(pool, entered_by):
    """The first view keeps the waiter busy, so the second is still in
    its queue when its caller comes: the waiter then skips it."""
    busy, mine = SlowArray(MB), SlowArray(2 * MB)
    before = _read()
    busy_view, _ = zerocopy.host_view(busy)
    assert _until(lambda: busy.fetches == 1)    # the waiter is in its wait
    view, _ = zerocopy.host_view(mine)
    if entered_by == "resolve":
        caller = threading.Thread(target=view.resolve)
        caller.start()
        assert _until(lambda: mine.fetches == 1)
    else:
        view.waited_for()
    busy.release()
    assert busy.fetched.wait(10)
    time.sleep(0.05)                # the waiter has been past `mine` now
    assert mine.fetches == (1 if entered_by == "resolve" else 0)
    mine.release()
    if entered_by == "resolve":
        caller.join(10)
        assert not caller.is_alive()
    view.resolve()
    assert mine.fetches == 1
    moved = _moved(before)
    assert moved["host_view_bytes"] == 2 * MB       # `busy` was never asked for
    assert moved["host_view_ahead_bytes"] == 0


def test_a_caller_that_comes_while_the_waiter_waits_returns_when_it_ends(
        pool):
    array = SlowArray(MB)
    before = _read()
    view, _ = zerocopy.host_view(array)
    assert _until(lambda: array.fetches == 1)
    got = []
    caller = threading.Thread(target=lambda: got.append(view.resolve()))
    caller.start()
    time.sleep(0.05)
    assert caller.is_alive()            # blocked on the waiter's wait
    array.release()
    caller.join(10)
    assert not caller.is_alive() and got[0].nbytes == MB
    assert array.fetches == 1           # one wait, one host copy
    moved = _moved(before)
    assert moved["host_view_ahead_bytes"] == 0
    assert moved["host_view_wait_us"] >= 40_000


def test_a_fetch_that_raises_does_so_from_the_callers_resolve(pool):
    lost = SlowArray(MB, takes_s=0.0, fails=True)
    view, _ = zerocopy.host_view(lost)
    assert _until(lambda: lost.fetches >= 1)
    with pytest.raises(RuntimeError, match="the device went away"):
        view.resolve()
    assert not view.landed
    # The waiter is alive and sees the next one through.
    after = SlowArray(MB, takes_s=0.0)
    after_view, _ = zerocopy.host_view(after)
    assert after.fetched.wait(10) and not after_view.landed


def test_views_are_waited_for_in_the_order_they_were_started(pool):
    arrays = [SlowArray(MB) for _ in range(4)]
    views = [zerocopy.host_view(a)[0] for a in arrays]
    for i, array in enumerate(arrays):
        assert _until(lambda: array.fetches == 1)
        assert [a.fetches for a in arrays[i + 1:]] == [0] * (3 - i)
        array.release()
    assert all(a.fetched.wait(10) for a in arrays)
    assert [v.resolve().nbytes for v in views] == [MB] * 4


def test_the_waiter_lets_go_of_a_view_that_has_landed(pool):
    """The landing block goes back to the recycled list when the caller
    drops the view and the array, exactly as without a waiter."""
    array = SlowArray(2 * MB, takes_s=0.0)
    view, owner = zerocopy.host_view(array)
    assert array.fetched.wait(10)
    assert _until(lambda: sys.getrefcount(view) == 2)   # `view` and the call's
    assert pool.trpc_host_pool_idle_bytes() == 0
    del view, owner, array
    gc.collect()
    assert pool.trpc_host_pool_idle_bytes() == 2 * MB


def test_a_view_its_caller_dropped_is_not_waited_for(pool):
    """The waiter's queue holds a view weakly: nobody will ask for these
    bytes, and the block is free for the next transfer at once."""
    busy, dropped = SlowArray(MB), SlowArray(2 * MB)
    busy_view, _ = zerocopy.host_view(busy)
    assert _until(lambda: busy.fetches == 1)
    zerocopy.host_view(dropped)         # queued behind `busy`, let go
    busy.release()
    assert busy.fetched.wait(10)
    time.sleep(0.05)
    assert dropped.fetches == 0
    del dropped
    gc.collect()
    assert pool.trpc_host_pool_idle_bytes() == 2 * MB
    assert busy_view.resolve().nbytes == MB


def test_the_thread_ends_when_idle_and_the_next_view_starts_another(
        pool, monkeypatch):
    monkeypatch.setattr(zerocopy, "_WAITER_IDLE_S", 0.05)
    first = SlowArray(MB, takes_s=0.0)
    first_view, _ = zerocopy.host_view(first)
    assert first.fetched.wait(10)
    (thread,) = _waiters()
    _waiter_gone(5)
    again = SlowArray(MB, takes_s=0.0)
    again_view, _ = zerocopy.host_view(again)
    assert again.fetched.wait(10)
    (other,) = _waiters()
    assert other is not thread


def test_the_pool_counts_a_fresh_and_a_recycled_block(pool):
    before = _read(POOL_COUNTERS)
    flat, owner = zerocopy.host_bytes(SlowArray(5 * MB, takes_s=0.0))
    assert _moved(before) == {"host_pool_hit_bytes": 0,
                              "host_pool_miss_bytes": 5 * MB}
    del flat, owner
    gc.collect()
    flat, owner = zerocopy.host_bytes(SlowArray(5 * MB, takes_s=0.0))
    assert _moved(before) == {"host_pool_hit_bytes": 5 * MB,
                              "host_pool_miss_bytes": 5 * MB}
    # Under the line the pool is not asked, and counts nothing.
    zerocopy.host_bytes(SlowArray(4096, takes_s=0.0))
    assert _moved(before) == {"host_pool_hit_bytes": 5 * MB,
                              "host_pool_miss_bytes": 5 * MB}


def test_the_transfer_time_is_the_landing_not_the_first_resolve(pool):
    array = SlowArray(MB, takes_s=0.02)
    before = _read()
    view, _ = zerocopy.host_view(array)
    time.sleep(0.15)                    # the caller comes back much later
    view.resolve()
    moved = _moved(before)
    assert 20_000 <= moved["host_view_transfer_us"] < 100_000
    assert moved["host_view_wait_us"] < 20_000
    assert moved["host_view_ahead_bytes"] == MB


def test_many_callers_of_one_view_count_it_once_and_get_one_copy(pool):
    """More callers than cores on one view and a short switch interval:
    one wait, one count, the same bytes to everyone."""
    array = SlowArray(MB)
    before = _read()
    view, _ = zerocopy.host_view(array)
    got, errors = [], []

    def caller():
        try:
            got.append(view.resolve().ctypes.data)
        except Exception as e:  # noqa: BLE001 - reported by the assert
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        callers = [threading.Thread(target=caller) for _ in range(32)]
        for t in callers:
            t.start()
        time.sleep(0.02)
        array.release()
        for t in callers:
            t.join(10)
    finally:
        sys.setswitchinterval(interval)
    assert not errors and not any(t.is_alive() for t in callers)
    assert got == [array.host.ctypes.data] * 32 and array.fetches == 1
    assert _moved(before)["host_view_bytes"] == MB


# ---- the form in which an array crosses (zerocopy._crossing_form) ----


def _u16_pattern(shape, dtype):
    import jax
    import jax.numpy as jnp

    n = int(np.prod(shape))
    words = (jnp.arange(n, dtype=jnp.uint32) * jnp.uint32(2654435761)) >> 7
    return jax.lax.bitcast_convert_type(
        words.astype(jnp.uint16), dtype).reshape(shape)


@pytest.mark.parametrize("dtype", ["uint16", "int16", "bfloat16", "float16"])
@pytest.mark.parametrize("shape", [(4, 8480, 128), (4, 2, 128, 576)])
def test_a_16_bit_array_crosses_as_flat_words_with_the_same_bytes(
        pool, dtype, shape):
    """On the CPU dlpack would import such an array; `PendingView` is
    made of it directly, as `host_view` does where dlpack refuses."""
    x = _u16_pattern(shape, dtype)
    view = zerocopy.PendingView(x)
    assert view.shape == shape and view.nbytes == x.nbytes
    assert view._crossing.shape == (x.size // 2,)
    assert view._crossing.dtype == np.uint32
    got = view.resolve()
    want = np.asarray(x).reshape(-1).view(np.uint8)
    assert got.dtype == np.uint8 and got.nbytes == x.nbytes
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("make", [
    lambda jnp: jnp.arange(MB, dtype=jnp.uint32),             # flat words
    lambda jnp: jnp.zeros((1024, 512), dtype=jnp.uint32),     # 32-bit
    lambda jnp: jnp.zeros((MB,), dtype=jnp.uint16),           # one dimension
    lambda jnp: jnp.zeros((1024, 1023), dtype=jnp.uint16),    # odd minor
    lambda jnp: jnp.zeros((16, 128, 128), dtype=jnp.uint16),  # under 1 MB
    lambda jnp: np.zeros((1024, 1024), dtype=np.uint16),      # host memory
    lambda jnp: SlowArray(2 * MB),                            # no dtype
], ids=["flat_u32", "u32_2d", "u16_1d", "odd_minor", "small", "numpy",
        "fake"])
def test_every_other_array_crosses_as_it_is(pool, make):
    import jax.numpy as jnp

    zerocopy.host_bytes(SlowArray(8, takes_s=0.0))   # the landing facts
    x = make(jnp)
    assert zerocopy._crossing_form(x) is x
