"""The batch pipeline's stager (brpc_tpu/rpc/batch.py): a request whose
bytes are still on their way to the host (`zerocopy.PendingView`) is
submitted at once and issued when they land, in submit order.

On the CPU every JAX array is host-visible, so nothing is ever pending by
itself.  `HeldArray` stands in for a TPU-resident array: dlpack import
fails on it, it can start its own transfer (`copy_to_host_async`), and the
fetch (`__array__`) blocks until the test lets it go.
"""

import errno
import sys
import threading
import time
import weakref

import jax.numpy as jnp
import numpy as np
import pytest

from brpc_tpu.rpc import Channel, Server, observe, zerocopy
from brpc_tpu.rpc.batch import pinned_requests


class HeldArray:
    """An array the way `host_view` sees a TPU-resident one."""

    def __init__(self, values: np.ndarray, held: bool = True,
                 fails: bool = False):
        self._values = values
        self.nbytes = values.nbytes
        self.started = False
        self.fails = fails
        self._go = threading.Event()
        if not held:
            self._go.set()

    def copy_to_host_async(self) -> None:
        self.started = True

    def release(self) -> None:
        self._go.set()

    def __array__(self, dtype=None, copy=None):
        assert self._go.wait(30), "the test never released this fetch"
        if self.fails:
            raise RuntimeError("the device went away")
        return self._values


def _payload(i: int, n: int = 4096) -> np.ndarray:
    return np.full(n, i + 1, dtype=np.uint8)


@pytest.fixture
def echo():
    arrivals = []

    def recorded(call, req):
        arrivals.append(bytes(req[:1]))
        call.respond(req)

    srv = Server()
    srv.register_native_echo("Echo.Echo")
    srv.register("Echo.Recorded", recorded)
    srv.start(0)
    ch = Channel(f"127.0.0.1:{srv.port}", timeout_ms=10000)
    pipe = ch.pipeline()
    try:
        yield ch, pipe, arrivals
    finally:
        pipe.close()
        ch.close()
        srv.stop()


def _drain(pipe, n: int) -> dict:
    done = {}
    deadline = time.monotonic() + 15
    while len(done) < n and time.monotonic() < deadline:
        for c in pipe.poll(timeout_ms=2000):
            assert c.token not in done, "a call completed twice"
            done[c.token] = c
    assert len(done) == n
    return done


def _no_pins_left() -> None:
    deadline = time.monotonic() + 10
    while pinned_requests() and time.monotonic() < deadline:
        time.sleep(0.005)
    assert zerocopy.live_sends() == 0


def _stagers() -> list:
    return [t for t in threading.enumerate()
            if t.name == "trpc-batch-stager"]


def _staged_calls() -> int:
    return observe.Vars.dump()["batch_staged_calls"]


def test_host_view_of_an_array_that_is_not_host_visible_returns_pending():
    values = np.arange(1024, dtype=np.uint32)
    held = HeldArray(values)
    view, owner = zerocopy.host_view(held)
    assert isinstance(view, zerocopy.PendingView)
    assert owner is held and held.started
    assert view.nbytes == 4096 and not view.landed
    held.release()
    flat = view.resolve()
    assert view.landed and flat.dtype == np.uint8 and flat.size == 4096
    # The array's own host copy, and the same view to every caller.
    assert flat.ctypes.data == values.ctypes.data
    assert view.resolve() is flat
    assert np.asarray(view) is flat


def test_every_transfer_starts_when_its_view_is_made():
    order = []

    class Logged(HeldArray):
        def copy_to_host_async(self) -> None:
            super().copy_to_host_async()
            order.append(self)

    arrays = [Logged(_payload(i)) for i in range(8)]
    views = [zerocopy.host_view(a)[0] for a in arrays]
    # None has landed or been waited for, and all eight are on their way.
    assert order == arrays and not any(v.landed for v in views)
    # Dropped unresolved: the module kept nothing of it to give back.
    dropped = weakref.ref(views[0])
    views[0] = None
    assert dropped() is None
    # Whatever its size, and whatever is still held in front of it.
    big = Logged(_payload(8, n=1 << 16), held=False)
    big_view = zerocopy.host_view(big)[0]
    assert order[-1] is big and big_view.resolve().size == 1 << 16
    for a in arrays:
        a.release()
    for i, view in enumerate(views[1:], start=1):
        assert view.resolve().tobytes() == _payload(i).tobytes()


def test_host_bytes_waits_for_the_transfer():
    values = np.arange(256, dtype=np.uint32)
    flat, owner = zerocopy.host_bytes(HeldArray(values, held=False))
    assert isinstance(flat, np.ndarray)
    assert flat.ctypes.data == values.ctypes.data
    assert isinstance(owner, HeldArray)


@pytest.mark.parametrize("resp_in_caller_buffer", [True, False])
def test_tokens_come_back_before_the_fetch_ends_and_the_echo_is_exact(
        echo, resp_in_caller_buffer):
    _, pipe, _ = echo
    n = 4
    arrays = [HeldArray(_payload(i)) for i in range(n)]
    views = [zerocopy.host_view(a)[0] for a in arrays]
    landing = ([np.zeros(4096, dtype=np.uint8) for _ in range(n)]
               if resp_in_caller_buffer else None)
    before = _staged_calls()
    t0 = time.monotonic()
    tokens = pipe.submit("Echo.Echo", views, resp_bufs=landing)
    assert time.monotonic() - t0 < 1.0
    assert len(set(tokens)) == n
    assert pipe.outstanding == n and pipe.inflight == n
    assert pipe.poll(timeout_ms=0) == []
    assert pipe.poll(timeout_ms=50) == []      # parks and times out
    for a in arrays:
        a.release()
    done = _drain(pipe, n)
    assert pipe.outstanding == 0
    for i, token in enumerate(tokens):
        c = done[token]
        assert c.ok and c.resp_len == 4096
        if resp_in_caller_buffer:
            assert c.in_caller_buffer
            np.testing.assert_array_equal(landing[i], _payload(i))
        else:
            assert c.tobytes() == _payload(i).tobytes()
            c.data.release()
    assert _staged_calls() - before == n
    _no_pins_left()


@pytest.mark.parametrize("release_order", ["forward", "backward"])
def test_wire_order_is_submit_order_with_pending_and_ready_mixed(
        echo, monkeypatch, release_order):
    """Issue order is wire order on one connection (one issuing fiber, one
    FIFO queue over all submits), so what has to hold here is that calls
    cross into the native submit in submit order, whatever order their
    bytes land in, and that nothing ready overtakes a held request.  (A
    server's handlers start on fibers of their own: the order in which
    they run says nothing about two requests that arrived together.)"""
    _, pipe, arrivals = echo
    crossed = []
    native = pipe._lib.trpc_batch_submit_staged

    def spy(batch, method, reqs, lens, rb, rc, n, timeout, deleter, ctxs,
            stages):
        crossed.append([stages[i].token for i in range(n.value)])
        return native(batch, method, reqs, lens, rb, rc, n, timeout,
                      deleter, ctxs, stages)

    monkeypatch.setattr(pipe._lib, "trpc_batch_submit_staged", spy)
    # pending, ready, pending | ready, pending: two submits.
    a0, a2, a4 = (HeldArray(_payload(i)) for i in (0, 2, 4))
    v0, v2, v4 = (zerocopy.host_view(a)[0] for a in (a0, a2, a4))
    tokens = pipe.submit("Echo.Recorded", [v0, _payload(1), v2])
    tokens += pipe.submit("Echo.Recorded", [_payload(3).tobytes(), v4])
    assert tokens == sorted(tokens) and pipe.outstanding == 5
    held = [a0, a2, a4]
    for a in (held if release_order == "forward" else held[::-1]):
        if a is a0:
            time.sleep(0.05)
            assert arrivals == [] and crossed == []   # nothing overtook it
            assert pipe.poll(timeout_ms=0) == []
        a.release()
        time.sleep(0.02)
    done = _drain(pipe, 5)
    assert all(done[t].ok for t in tokens)
    assert sorted(arrivals) == [bytes([i + 1]) for i in range(5)]
    assert [t for group in crossed for t in group] == tokens
    # The first submit's three once its two fetches have ended, with the
    # ready 3 queued behind them; then 4.
    assert [len(group) for group in crossed] == [4, 1]
    for c in done.values():
        c.data.release()
    _no_pins_left()


def test_a_ready_submit_with_nothing_ahead_of_it_never_sees_the_stager(echo):
    _, pipe, _ = echo
    before = _staged_calls()
    held = HeldArray(_payload(0), held=False)
    tokens = pipe.submit("Echo.Echo", [zerocopy.host_view(held)[0]])
    _drain(pipe, 1)
    assert _staged_calls() - before == 1
    # The queue is empty again: plain bytes go straight to the native call.
    tokens = pipe.submit("Echo.Echo", [b"x" * 64] * 3)
    done = _drain(pipe, 3)
    assert all(done[t].ok for t in tokens)
    assert _staged_calls() - before == 1
    # A view whose bytes have landed already is as ready as bytes are.
    landed = zerocopy.host_view(HeldArray(_payload(5), held=False))[0]
    landed.resolve()
    tokens = pipe.submit("Echo.Echo", [landed])
    done = _drain(pipe, 1)
    assert done[tokens[0]].tobytes() == _payload(5).tobytes()
    assert _staged_calls() - before == 1


def test_a_host_backed_jax_array_is_never_staged(echo):
    _, pipe, _ = echo
    x = jnp.arange(8192, dtype=jnp.uint32)
    before = _staged_calls()
    flat, _owner = zerocopy.host_view(x)
    assert isinstance(flat, np.ndarray)
    assert flat.ctypes.data == np.from_dlpack(x).ctypes.data
    landing = np.zeros(flat.size, dtype=np.uint8)
    (token,) = pipe.submit("Echo.Echo", [flat], resp_bufs=[landing])
    done = _drain(pipe, 1)
    assert done[token].ok
    np.testing.assert_array_equal(landing.view(np.uint32), np.asarray(x))
    assert _staged_calls() == before
    assert not _stagers()
    _no_pins_left()


def test_a_fetch_that_raises_completes_its_call_with_a_status(echo):
    _, pipe, _ = echo
    good = HeldArray(_payload(0), held=False)
    bad = HeldArray(_payload(1), held=False, fails=True)
    landing = [np.zeros(4096, dtype=np.uint8) for _ in range(3)]
    tokens = pipe.submit(
        "Echo.Echo",
        [zerocopy.host_view(good)[0], zerocopy.host_view(bad)[0],
         _payload(2)],
        resp_bufs=landing)
    done = _drain(pipe, 3)
    assert done[tokens[0]].ok and done[tokens[2]].ok
    failed = done[tokens[1]]
    assert failed.status == errno.EIO
    assert "the device went away" in failed.error
    np.testing.assert_array_equal(landing[2], _payload(2))
    assert not landing[1].any()
    assert pipe.outstanding == 0
    assert not pipe._resp_pins          # the landing buffer is let go
    _no_pins_left()


@pytest.mark.parametrize("which", ["being_fetched", "queued_behind"])
def test_cancel_of_a_staged_call_completes_it_once_with_ecanceled(
        echo, which):
    _, pipe, _ = echo
    a0, a1 = HeldArray(_payload(0)), HeldArray(_payload(1))
    tokens = pipe.submit("Echo.Echo", [zerocopy.host_view(a0)[0],
                                       zerocopy.host_view(a1)[0]])
    victim = tokens[0] if which == "being_fetched" else tokens[1]
    time.sleep(0.02)                     # the stager is inside a0's fetch
    assert pipe.cancel(victim) is True
    done = _drain(pipe, 1)               # at once, the fetches still held
    assert done[victim].status == errno.ECANCELED
    assert pipe.cancel(victim) is False  # settled: nothing left to cancel
    assert pipe.outstanding == 1
    a0.release()
    a1.release()
    (survivor,) = set(tokens) - {victim}
    done = _drain(pipe, 1)
    assert done[survivor].ok
    done[survivor].data.release()
    assert pipe.poll(timeout_ms=50) == []
    _no_pins_left()


@pytest.mark.parametrize("how", ["close", "channel_close"])
def test_close_with_fetches_outstanding_settles_and_leaves_no_thread(
        echo, how):
    ch, pipe, _ = echo
    arrays = [HeldArray(_payload(i)) for i in range(3)]
    pipe.submit("Echo.Echo", [zerocopy.host_view(a)[0] for a in arrays],
                resp_bufs=[np.zeros(4096, dtype=np.uint8)
                           for _ in arrays])
    time.sleep(0.02)
    assert len(_stagers()) == 1
    # The fetch in progress cannot be interrupted: it ends 50 ms from now.
    timer = threading.Timer(0.05, arrays[0].release)
    timer.start()
    if how == "close":
        pipe.close()
    else:
        ch.close()                       # quiesces its pipelines
        assert pipe.inflight == 0
        done = _drain(pipe, 3)           # still drainable after quiesce
        # The one whose bytes landed was handed over and may have made
        # it; the two never fetched were canceled.
        statuses = sorted(c.status for c in done.values())
        assert statuses in ([0] + [errno.ECANCELED] * 2,
                            [errno.ECANCELED] * 3)
        with pytest.raises(RuntimeError):
            pipe.submit("Echo.Echo",
                        [zerocopy.host_view(HeldArray(_payload(9)))[0]])
        pipe.close()
    timer.join(5)
    assert not _stagers()
    assert not pipe._resp_pins
    _no_pins_left()


def test_an_idle_stager_ends_itself_and_the_next_pending_submit_restarts_it(
        echo, monkeypatch):
    from brpc_tpu.rpc import batch

    _, pipe, _ = echo
    monkeypatch.setattr(batch, "_STAGER_IDLE_S", 0.05)
    for i in range(2):
        held = HeldArray(_payload(i), held=False)
        (token,) = pipe.submit("Echo.Echo", [zerocopy.host_view(held)[0]])
        done = _drain(pipe, 1)
        assert done[token].tobytes() == _payload(i).tobytes()
        deadline = time.monotonic() + 5
        while _stagers() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert not _stagers()


def test_the_request_stays_pinned_until_the_runtime_drops_it(echo):
    _, pipe, _ = echo
    held = HeldArray(_payload(0, n=1 << 16), held=False)
    view = zerocopy.host_view(held)[0]
    (token,) = pipe.submit("Echo.Echo", [view])
    del view, held                       # the pipeline's pin is the last
    done = _drain(pipe, 1)
    assert done[token].tobytes() == _payload(0, n=1 << 16).tobytes()
    _no_pins_left()


def test_two_pipelines_stage_independently(echo):
    """A fetch that one pipeline waits for holds back neither the start
    nor the completion of another pipeline's: they share no queue."""
    ch, pipe, _ = echo
    other = ch.pipeline()
    try:
        stuck = HeldArray(_payload(0, n=1 << 16))
        (stuck_token,) = pipe.submit("Echo.Echo",
                                     [zerocopy.host_view(stuck)[0]])
        time.sleep(0.02)                 # pipe's stager is inside the fetch
        free = [HeldArray(_payload(i, n=1 << 16), held=False)
                for i in (1, 2, 3)]
        tokens = other.submit("Echo.Echo",
                              [zerocopy.host_view(a)[0] for a in free])
        assert all(a.started for a in free)
        done = _drain(other, 3)
        for i, token in enumerate(tokens, start=1):
            assert done[token].tobytes() == _payload(i, n=1 << 16).tobytes()
            done[token].data.release()
        assert pipe.poll(timeout_ms=0) == [] and pipe.outstanding == 1
        stuck.release()
        done = _drain(pipe, 1)
        assert done[stuck_token].ok
        done[stuck_token].data.release()
    finally:
        other.close()
    _no_pins_left()


def test_many_submitters_one_poller_every_call_completes_exactly_once(echo):
    """Pending and ready requests from more threads than cores, a poller
    and a canceller on one pipeline, the interpreter switching threads
    every 10 us: no token is lost or handed out twice, every echo is its
    own request, and nothing stays pinned, queued or counted in flight."""
    _, pipe, _ = echo
    threads, rounds, per_submit = 12, 20, 3
    total = threads * rounds * per_submit
    sent: dict[int, bytes] = {}
    sent_lock = threading.Lock()
    got: dict[int, tuple] = {}
    errors: list = []

    def submitter(k: int) -> None:
        try:
            for r in range(rounds):
                requests, bodies = [], []
                for j in range(per_submit):
                    body = bytes([k, r, j]) * 171        # 513 bytes
                    values = np.frombuffer(body, dtype=np.uint8)
                    pending = (k + r + j) % 3 != 0
                    requests.append(
                        zerocopy.host_view(HeldArray(values, held=False))[0]
                        if pending else values)
                    bodies.append(body)
                tokens = pipe.submit("Echo.Echo", requests)
                with sent_lock:
                    sent.update(zip(tokens, bodies))
                if r % 7 == 3:
                    pipe.cancel(tokens[-1])
        except Exception as e:  # noqa: BLE001 — reported by the assert below
            errors.append(e)

    def poller() -> None:
        deadline = time.monotonic() + 60
        while len(got) < total and time.monotonic() < deadline:
            for c in pipe.poll(max_n=16, timeout_ms=200):
                if c.token in got:
                    errors.append(AssertionError(f"{c.token} twice"))
                got[c.token] = (c.status, c.tobytes())
                if c.data is not None:
                    c.data.release()

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        workers = [threading.Thread(target=submitter, args=(k,))
                   for k in range(threads)] + [threading.Thread(target=poller)]
        for t in workers:
            t.start()
        for t in workers:
            t.join(90)
        assert not any(t.is_alive() for t in workers)
    finally:
        sys.setswitchinterval(interval)
    assert not errors
    assert set(got) == set(sent) and len(got) == total
    for token, (status, body) in got.items():
        assert status in (0, errno.ECANCELED)
        if status == 0:
            assert body == sent[token]
    assert sum(1 for status, _ in got.values() if status == 0) >= total - 36
    assert pipe.outstanding == 0 and not pipe._staged_by_token
    _no_pins_left()
