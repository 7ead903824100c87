"""The landing copy of a one-sided response (cpp/net/rma.cc `rma_land`,
called by `on_call_done` of cpp/capi/batch_capi.cc).

A response over the stripe threshold on an shm connection arrives as a
span of the connection's window.  When the caller's `resp_buf` is plain
memory the span is copied out, cut over the connection's rails the way
the sender cut the put; `batch_land_fanout_bytes` counts the bytes whose
copy ran on more than one rail.  What decides is what the response is:
nothing here sets an option to switch the fan-out on.

Counters are read as deltas around one call on CPU loopback; the
registry and the windows are the process's, so each test starts from no
span in use and settles its own call before it reads.
"""

import errno
import time
from typing import NamedTuple

import numpy as np
import pytest
from test_batch_phase_vars import PHASES, _drain

from brpc_tpu.rpc import Channel, RmaBuffer, Server, observe
from brpc_tpu.rpc._lib import load_library
from brpc_tpu.rpc.flags import get_flag, set_flag

THRESHOLD = int(get_flag("trpc_stripe_threshold"))
CHUNK = int(get_flag("trpc_stripe_chunk_bytes"))
COUNTERS = PHASES + ("batch_stage_us", "batch_calls_polled",
                     "batch_calls_failed", "batch_resp_bytes",
                     "batch_land_copy_bytes", "batch_land_fanout_bytes")


def _spans_in_use() -> int:
    return int(load_library().trpc_rma_spans_in_use())


def _no_span_in_use(within_s: float = 5.0) -> bool:
    deadline = time.monotonic() + within_s
    while _spans_in_use() and time.monotonic() < deadline:
        time.sleep(0.01)
    return _spans_in_use() == 0


@pytest.fixture
def server():
    srv = Server()
    srv.register_native_echo("Echo.Echo")
    srv.start(0)
    assert _no_span_in_use()
    try:
        yield srv
    finally:
        srv.set_faults("")
        srv.stop()


def _pipeline(srv, **options):
    ch = Channel(f"127.0.0.1:{srv.port}", timeout_ms=30000, **options)
    assert ch.call("Echo.Echo", b"warm") == b"warm"
    return ch, ch.pipeline()


def _read() -> dict:
    dump = observe.Vars.dump()
    return {name: dump[name] for name in COUNTERS}


def _pattern(n: int) -> np.ndarray:
    return (np.arange(n, dtype=np.uint64) * 2654435761 >> 13).astype(np.uint8)


class Landed(NamedTuple):
    done: object          # the call's Completion
    request: np.ndarray
    got: np.ndarray       # the landing buffer's first `size` bytes
    moved: dict           # the counters' movement around the call
    interval_us: float    # submit to polled


def _echo_once(pipe, size: int, landing=None, **submit_options) -> Landed:
    """One call of `size` bytes landing in `landing` (default: a fresh
    numpy array)."""
    request = _pattern(size)
    if landing is None:
        landing = np.zeros(size, dtype=np.uint8)
    before = _read()
    t0 = time.perf_counter()
    pipe.submit("Echo.Echo", [request], resp_bufs=[landing],
                **submit_options)
    (done,) = _drain(pipe, 1)
    interval_us = (time.perf_counter() - t0) * 1e6
    after = _read()
    moved = {name: after[name] - before[name] for name in COUNTERS}
    got = np.frombuffer(landing, dtype=np.uint8)[:size]
    return Landed(done, request, got, moved, interval_us)


def _landed_whole(call: Landed) -> dict:
    """The call's response is in its buffer, byte-exact, and counted once;
    returns the counters' movement."""
    size = call.request.nbytes
    done, moved = call.done, call.moved
    assert done.ok and done.in_caller_buffer and done.resp_len == size
    assert np.array_equal(call.got, call.request)
    assert moved["batch_calls_polled"] == 1
    assert moved["batch_calls_failed"] == 0
    assert moved["batch_resp_bytes"] == size
    # queue + wire + land + ready is polled - entered of the one call
    # (nothing was staged), which lies inside submit ... poll.
    assert moved["batch_stage_us"] == 0
    assert all(moved[phase] >= 0 for phase in PHASES)
    assert 0 < sum(moved[phase] for phase in PHASES) <= call.interval_us + 1
    # The span's slots went when the copy had joined, before the poll.
    assert _spans_in_use() == 0
    return moved


@pytest.mark.parametrize("size", [
    pytest.param(THRESHOLD, id="threshold"),
    pytest.param(THRESHOLD + 1, id="threshold+1"),
    pytest.param(CHUNK + 1, id="chunk+1"),
    pytest.param(8 << 20, id="8MiB"),
    pytest.param((9 << 20) + 3, id="9MiB+3"),
])
def test_a_span_over_one_chunk_is_copied_out_by_the_rails(server, size):
    ch, pipe = _pipeline(server, use_shm=True)
    try:
        assert ch.transport == "shm_ring"
        moved = _landed_whole(_echo_once(pipe, size))
        # Private memory is never in place: every byte is copied, as ever.
        assert moved["batch_land_copy_bytes"] == size
        # At or under the threshold the body rides the ring and is no
        # span; above it the span holds two chunks or more.
        over = size > THRESHOLD and size > CHUNK
        assert moved["batch_land_fanout_bytes"] == (size if over else 0)
    finally:
        pipe.close()
        ch.close()


def test_a_span_of_one_chunk_is_the_plain_copy(server):
    # A lower threshold makes a span of less than one chunk possible.
    set_flag("trpc_stripe_threshold", str(1 << 20))
    try:
        ch, pipe = _pipeline(server, use_shm=True)
        try:
            size = (1 << 20) + (1 << 19)
            assert size < CHUNK
            tx0 = observe.Vars.dump()["rma_tx_msgs"]
            moved = _landed_whole(_echo_once(pipe, size))
            assert observe.Vars.dump()["rma_tx_msgs"] == tx0 + 2   # a span
            assert moved["batch_land_copy_bytes"] == size
            assert moved["batch_land_fanout_bytes"] == 0
        finally:
            pipe.close()
            ch.close()
    finally:
        set_flag("trpc_stripe_threshold", str(THRESHOLD))


def test_one_rail_is_the_plain_copy(server):
    rails = get_flag("trpc_shm_rails")
    set_flag("trpc_shm_rails", "1")
    try:
        ch, pipe = _pipeline(server, use_shm=True)
        try:
            moved = _landed_whole(_echo_once(pipe, 8 << 20))
            assert moved["batch_land_copy_bytes"] == 8 << 20
            assert moved["batch_land_fanout_bytes"] == 0
        finally:
            pipe.close()
            ch.close()
    finally:
        set_flag("trpc_shm_rails", rails)


def test_a_tcp_response_has_no_span_to_fan_out(server):
    ch, pipe = _pipeline(server)
    try:
        moved = _landed_whole(_echo_once(pipe, 8 << 20))
        # Striped chunks land in the caller's buffer as they arrive.
        assert moved["batch_land_copy_bytes"] == 0
        assert moved["batch_land_fanout_bytes"] == 0
    finally:
        pipe.close()
        ch.close()


def test_a_response_put_into_an_rma_buffer_is_in_place(server):
    ch, pipe = _pipeline(server, use_shm=True)
    try:
        size = 8 << 20
        with RmaBuffer(size) as land:
            moved = _landed_whole(_echo_once(pipe, size, landing=land.view))
        assert moved["batch_land_copy_bytes"] == 0
        assert moved["batch_land_fanout_bytes"] == 0
        assert moved["batch_land_us"] == 0
    finally:
        pipe.close()
        ch.close()


def test_a_call_that_times_out_starts_no_rails_and_fails_whole(server):
    ch, pipe = _pipeline(server, use_shm=True)
    try:
        size = 8 << 20
        server.set_faults("svr_delay=1:400")   # every dispatch parks 400 ms
        landing = np.full(size, 0xEE, dtype=np.uint8)
        done, _, got, moved, _ = _echo_once(pipe, size, landing=landing,
                                            timeout_ms=60)
        # The request's span lies in the server's window while the handler
        # is parked; the caller has its answer and its buffer untouched.
        assert done.status == errno.ETIMEDOUT and not done.in_caller_buffer
        assert _spans_in_use() > 0
        assert moved["batch_calls_failed"] == 1
        for name in COUNTERS:
            if name != "batch_calls_failed":
                assert moved[name] == 0, name
        server.set_faults("")
        # The late response finds no call to land in: its span and the
        # request's are given back, and nothing is written after the fact.
        settled = _read()
        assert _no_span_in_use()
        assert _read() == settled
        assert np.all(got == 0xEE)
        # The connection carries the next call, which fans out.
        moved = _landed_whole(_echo_once(pipe, size))
        assert moved["batch_land_fanout_bytes"] == size
    finally:
        pipe.close()
        ch.close()
