"""The served path's stream through its Python surface
(brpc_tpu/rpc/stream.py over cpp/capi/stream_capi.cc over
cpp/net/stream.cc): a chunk of any width goes on a window of any width,
the window holds at the Python boundary (a chunk's bytes go back to the
writer when the application has read it), what `write` and `read_into`
copy is what the counters say, a device array goes in and comes out, and
the native stream echo (`Server.register_native_stream_echo`) keeps
order.  The system is compared with the plain reference
(benchmark/reference_stream.py) at small sizes.  A chunk over the
large-message threshold on the shm ring rides the connection's one-sided
window (PR 36): what goes that way and what falls back in band, in what
order it arrives, what a lost transfer does to the stream, and what is
left allocated afterwards.  Nothing here is a measurement.
"""

import contextlib
import gc
import os
import threading
import time

import numpy as np
import pytest

from benchmark import reference_stream
from brpc_tpu.rpc import (Channel, RpcError, Server, StreamChunkTooLargeError,
                          StreamClosedError, StreamTimeoutError, fault, flags,
                          observe, open_stream, stream, zerocopy)
from brpc_tpu.rpc._lib import load_library

KB, MB = 1 << 10, 1 << 20
METHOD = "Echo.Stream"
COPIES = ("stream_capi_write_copy_bytes", "stream_capi_read_copy_bytes")


def _wait(cond, timeout_s: float = 5.0) -> bool:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.005)
    return cond()


def _counters(*names) -> dict:
    dumped = observe.Vars.dump()
    return {name: dumped[name] for name in names}


@pytest.fixture(params=["tcp", "shm"])
def native_echo(request):
    """(a Channel to a server whose `METHOD` is the native stream echo,
    the transport's name)."""
    srv = Server()
    srv.register_native_stream_echo(METHOD)
    port = srv.start()
    ch = Channel(f"127.0.0.1:{port}", timeout_ms=10000,
                 use_shm=request.param == "shm")
    try:
        yield ch
    finally:
        ch.close()
        srv.close()


@contextlib.contextmanager
def _python_peer(use_shm: bool):
    srv = Server()
    accepted = []
    windows = {"next": 0}

    def handler(call, _req):
        accepted.append(call.accept_stream(window_bytes=windows["next"]))
        call.respond(b"ok")

    srv.register(METHOD, handler)
    port = srv.start()
    ch = Channel(f"127.0.0.1:{port}", timeout_ms=10000, use_shm=use_shm)
    try:
        yield ch, accepted, windows
    finally:
        for peer in accepted:
            peer.destroy()
        ch.close()
        srv.close()


@pytest.fixture
def python_peer():
    """(a Channel, the list the server's Python handler puts its accepted
    end of each stream into, the window the next accept grants): a reader
    the test controls."""
    with _python_peer(use_shm=False) as peer:
        yield peer


@pytest.fixture
def shm_peer():
    """`python_peer` on the shm ring, whose connection has a one-sided
    session."""
    with _python_peer(use_shm=True) as peer:
        yield peer


def test_a_chunk_wider_than_the_window_goes_and_the_next_waits_for_its_read(
        python_peer):
    """4 MB on the 2 MB default window (BASELINE's chunk, upstream's and
    this program's default `max_buf_size`): admitted at once, because the
    window is not exhausted; the second is held until the application has
    READ the first, not until the runtime queued it."""
    ch, accepted, _windows = python_peer
    st, _ = open_stream(ch, METHOD)
    assert _wait(lambda: len(accepted) == 1)
    peer = accepted[0]
    first = np.full(4 * MB, 1, dtype=np.uint8)
    second = np.full(4 * MB, 2, dtype=np.uint8)
    t0 = time.monotonic()
    st.write(first)
    assert time.monotonic() - t0 < 1.0
    assert _wait(lambda: peer.pending() == 1)
    written = threading.Event()

    def write_second():
        st.write(second)
        written.set()

    writer = threading.Thread(target=write_second)
    writer.start()
    assert not written.wait(0.4)        # queued unread: no credit yet
    assert peer.pending() == 1
    landed = np.empty(4 * MB, dtype=np.uint8)
    assert peer.read_into(landed, timeout_ms=3000) == 4 * MB
    assert written.wait(3.0)            # the read gave the bytes back
    writer.join(3.0)
    assert not writer.is_alive()
    assert np.array_equal(landed, first)
    assert peer.read_into(landed, timeout_ms=3000) == 4 * MB
    assert np.array_equal(landed, second)
    # What lay unread never passed window + one chunk.
    assert peer.unread_high_water == 4 * MB < 2 * MB + 4 * MB
    st.destroy()


def test_a_reader_that_never_reads_holds_the_writer_after_window_plus_a_chunk(
        python_peer):
    ch, accepted, windows = python_peer
    windows["next"] = 256 * KB
    chunk = bytes(100 * KB)
    st, _ = open_stream(ch, METHOD)
    assert _wait(lambda: len(accepted) == 1)
    peer = accepted[0]
    count = {"written": 0}

    def write_eight():
        for _ in range(8):
            st.write(chunk)
            count["written"] += 1

    writer = threading.Thread(target=write_eight)
    writer.start()
    # 256 KB admit three chunks of 100 KB: the third overruns the window.
    assert _wait(lambda: peer.pending() == 3)
    time.sleep(0.4)
    assert count["written"] == 3 and peer.pending() == 3
    bound = reference_stream.unread_bound(256 * KB, 100 * KB)
    assert peer.unread_high_water == 300 * KB <= bound
    assert observe.Vars.dump()["stream_unread_high_water_bytes"] >= 300 * KB
    # The application reads: the writer goes on, and never further ahead.
    for _ in range(8):
        assert len(peer.read(max_bytes=100 * KB, timeout_ms=3000)) == 100 * KB
        assert peer.pending() <= 3
    writer.join(3.0)
    assert not writer.is_alive() and count["written"] == 8
    assert peer.unread_high_water <= bound
    st.destroy()


class HeldArray:
    """Stands in for a TPU-resident array: dlpack refuses it, so
    `zerocopy.host_view` starts a transfer and returns a `PendingView`."""

    def __init__(self, host: np.ndarray):
        self._host = host
        self.nbytes, self.shape = host.nbytes, host.shape
        self.dtype, self.ndim = host.dtype, host.ndim

    def copy_to_host_async(self) -> None:
        pass

    def __array__(self, dtype=None, copy=None):
        return self._host


@pytest.mark.parametrize("kind", ["bytes_under_the_line", "bytes_wide",
                                  "numpy_words", "host_view",
                                  "pending_view"])
def test_write_and_read_into_copy_what_the_counters_say_and_no_more(
        native_echo, kind):
    rng = np.random.default_rng(5)
    wide = stream.WRITE_BY_REFERENCE_FROM * 4
    if kind == "bytes_under_the_line":
        sent = rng.integers(0, 256, 1000, dtype=np.uint8).tobytes()
    elif kind == "bytes_wide":
        sent = rng.integers(0, 256, wide, dtype=np.uint8).tobytes()
    else:
        sent = rng.integers(0, 1 << 32, wide // 4, dtype=np.uint32)
    expected = np.frombuffer(sent, dtype=np.uint8).copy()
    if kind == "host_view":
        payload, _owner = zerocopy.host_view(sent)
        assert isinstance(payload, np.ndarray)
    elif kind == "pending_view":
        payload, _owner = zerocopy.host_view(HeldArray(sent))
        assert isinstance(payload, zerocopy.PendingView)
    else:
        payload = sent
    st, _ = open_stream(native_echo, METHOD, window_bytes=MB)
    pins = zerocopy.live_sends()
    before = _counters(*COPIES)
    st.write(payload)
    landed = np.zeros(expected.size + 8, dtype=np.uint8)
    assert st.read_into(landed, timeout_ms=5000) == expected.size
    after = _counters(*COPIES)
    assert np.array_equal(landed[:expected.size], expected)
    assert not landed[expected.size:].any()
    copied_in = expected.size if kind == "bytes_under_the_line" else 0
    assert after[COPIES[0]] - before[COPIES[0]] == copied_in
    assert after[COPIES[1]] - before[COPIES[1]] == expected.size
    # What was wrapped is let go of once the frame has been written.
    assert _wait(lambda: zerocopy.live_sends() == pins)
    st.destroy()


def test_read_into_refuses_a_buffer_it_cannot_fill_and_read_sits_on_it(
        native_echo):
    st, _ = open_stream(native_echo, METHOD)
    with pytest.raises(StreamTimeoutError):
        st.next_len(timeout_ms=0)
    st.write(b"x" * 32)
    assert st.next_len(timeout_ms=5000) == 32
    with pytest.raises(ValueError, match="writable"):
        st.read_into(b"y" * 64, timeout_ms=1000)
    with pytest.raises(ValueError, match="contiguous"):
        st.read_into(np.zeros((8, 8), dtype=np.uint8)[:, ::2])
    with pytest.raises(StreamChunkTooLargeError) as too_small:
        st.read_into(bytearray(16), timeout_ms=1000)
    assert too_small.value.needed == 32 and too_small.value.cap == 16
    with pytest.raises(StreamChunkTooLargeError):
        st.read(max_bytes=31, timeout_ms=1000)
    assert st.pending() == 1                # nothing was consumed
    assert st.read(timeout_ms=1000) == b"x" * 32
    st.write(b"")                           # an empty chunk is a chunk
    assert st.read(timeout_ms=5000) == b""
    with pytest.raises(ValueError, match="contiguous"):
        st.write(np.zeros((8, 8), dtype=np.uint8)[:, ::2])
    st.destroy()


@pytest.mark.parametrize("shape,dtype", [((256,), np.uint32),
                                         ((1 << 18,), np.uint32),
                                         ((64, 1024), np.uint16),
                                         ((3, 5, 7), np.float32)])
def test_a_device_array_in_is_the_device_array_out(native_echo, shape, dtype):
    import jax
    import jax.numpy as jnp

    device = jax.devices()[0]
    host = np.random.default_rng(9).integers(
        0, 1 << 16, shape).astype(dtype)
    sent = jax.device_put(jnp.asarray(host), device)
    st, _ = open_stream(native_echo, METHOD, window_bytes=2 * MB)
    before = _counters(*COPIES)
    st.write_array(sent)
    back = st.read_array(dtype=dtype, shape=shape, device=device,
                         timeout_ms=5000)
    after = _counters(*COPIES)
    assert isinstance(back, jax.Array) and back.devices() == {device}
    assert back.dtype == sent.dtype and back.shape == sent.shape
    assert bool(jnp.array_equal(back, sent))
    assert after[COPIES[1]] - before[COPIES[1]] == host.nbytes
    assert after[COPIES[0]] - before[COPIES[0]] == (
        host.nbytes if host.nbytes < stream.WRITE_BY_REFERENCE_FROM else 0)
    st.destroy()


def test_a_wide_chunk_lands_in_a_recycled_block(native_echo):
    """`read_block` takes its block from the host pool's recycled list:
    the second chunk of a width lands in the pages the first left."""
    st, _ = open_stream(native_echo, METHOD, window_bytes=8 * MB)
    chunk = np.arange(MB // 2, dtype=np.uint32)       # 2 MB: over the line
    pool = ("host_pool_hit_bytes", "host_pool_miss_bytes")
    st.write(chunk)
    first = st.read_block(timeout_ms=5000)
    assert first.dtype == np.uint8 and first.nbytes == chunk.nbytes
    assert np.array_equal(first.view(np.uint32), chunk)
    address = first.ctypes.data
    del first
    gc.collect()
    before = _counters(*pool)
    st.write(chunk)
    second = st.read_block(timeout_ms=5000)
    after = _counters(*pool)
    assert second.ctypes.data == address
    assert after[pool[0]] - before[pool[0]] == chunk.nbytes
    assert after[pool[1]] == before[pool[1]]
    assert np.array_equal(second.view(np.uint32), chunk)
    st.destroy()


def test_the_native_echo_keeps_order_over_1000_chunks_of_mixed_widths(
        native_echo):
    """Widths from one byte to 200 KB on a 1 MB window, both sides of the
    line between a copied and a wrapped write; the writer is a thread of
    its own, so the reader's pace is the only brake."""
    rng = np.random.default_rng(33)
    widths = rng.integers(1, 200 * KB, 1000)
    widths[::97] = 1
    source = rng.integers(0, 256, 200 * KB + 1000, dtype=np.uint8)
    st, _ = open_stream(native_echo, METHOD, window_bytes=MB)
    failed = []

    def write_all():
        try:
            for i, width in enumerate(widths):
                st.write(source[i:i + width])
        except RpcError as e:
            failed.append(e)

    before = _counters("stream_chunks_written", "stream_chunks_consumed")
    writer = threading.Thread(target=write_all)
    writer.start()
    landed = np.empty(200 * KB, dtype=np.uint8)
    for i, width in enumerate(widths):
        assert st.read_into(landed, timeout_ms=10000) == width, i
        assert np.array_equal(landed[:width], source[i:i + width]), i
    writer.join(5.0)
    assert not writer.is_alive() and not failed
    assert st.pending() == 0
    after = _counters("stream_chunks_written", "stream_chunks_consumed")
    # Each chunk was written twice (there and back) and given back twice.
    assert after["stream_chunks_written"] - before[
        "stream_chunks_written"] == 2000
    assert _wait(lambda: _counters("stream_chunks_consumed")[
        "stream_chunks_consumed"] - before["stream_chunks_consumed"] == 2000)
    assert st.unread_high_water <= reference_stream.unread_bound(
        MB, int(widths.max()))
    st.destroy()


def test_the_native_echo_refuses_a_request_that_offers_no_stream(native_echo):
    with pytest.raises(RpcError, match="offered no stream"):
        native_echo.call(METHOD, b"plain")


@pytest.mark.parametrize("seed", [7, 2**31 + 11, 3300000033])
def test_the_system_delivers_what_the_reference_does(native_echo, seed):
    """The benchmark's loop at small sizes (write while fewer than
    `chunks_open` are open, else read one) against the plain reference's
    two FIFOs: the same chunks in the same order, the same running
    checksum, and no more unread at either end than the reference's
    bound."""
    rng = np.random.default_rng(seed)
    first = rng.integers(0, 1 << 32, 32 * KB, dtype=np.uint32)
    widths = [int(w) for w in rng.integers(1, 32 * KB, 60)]
    # Three chunks of at most 128 KB open: over half a window, so the
    # credit gate closes now and then (an ACK waits for half a window),
    # and under what the two windows hold whatever the ACKs' lag.
    window, chunks_open = 512 * KB, 3
    expected, running_expected, (at_server, at_client) = (
        reference_stream.stream_echo_reference(
            first, widths, window, chunks_open))
    bound = reference_stream.unread_bound(window, 4 * max(widths))
    assert max(at_server, at_client) <= bound

    st, _ = open_stream(native_echo, METHOD, window_bytes=window)
    whole, written, delivered, running = first, 0, [], 0
    while len(delivered) < len(widths):
        if written < len(widths) and written - len(delivered) < chunks_open:
            whole = reference_stream.next_chunk(whole)
            st.write(whole[:widths[written]].copy())
            written += 1
        else:
            block = st.read_block(timeout_ms=5000)
            delivered.append(block.view(np.uint32))
            running = reference_stream.fold(
                running, reference_stream.chunk_checksum(delivered[-1]))
    assert len(delivered) == len(expected)
    for got, want in zip(delivered, expected):
        assert np.array_equal(got, want)
    assert running == running_expected
    assert st.unread_high_water <= bound
    st.destroy()


# ---- a wide chunk rides the connection's one-sided window (PR 36) ---------

ONE_SIDED = ("stream_one_sided_bytes", "stream_bytes_written", "rma_tx_bytes",
             "rma_window_full", "rma_rejected")


def _threshold() -> int:
    return int(flags.get_flag("trpc_stripe_threshold"))


def _own_shm_names() -> set:
    return {name for name in os.listdir("/dev/shm")
            if name.startswith((f"trpc_rma_{os.getpid()}_",
                                f"trpc_{os.getpid()}_"))}


@contextlib.contextmanager
def _flag(name: str, value):
    old = flags.get_flag(name)
    flags.set_flag(name, str(value))
    try:
        yield
    finally:
        flags.set_flag(name, old)


def test_chunks_on_both_sides_of_the_threshold_arrive_in_the_order_written(
        native_echo):
    """4 MB chunks between 1 KB and 1 MB ones, six open against 16 MB
    windows.  Over shm every chunk over the threshold goes through the
    window, there and back, and the unary plane's counter rises by the
    same bytes; over tcp there is no session and none does.  Either way
    what comes back is what was written, in that order."""
    rng = np.random.default_rng(36)
    widths = [4 * MB, KB, MB, 4 * MB, 4 * MB, KB, 3 * MB, MB, 4 * MB, 1,
              _threshold(), _threshold() + 4, KB, 4 * MB]
    chunks = [rng.integers(0, 256, w, dtype=np.uint8) for w in widths]
    wide = sum(w for w in widths if w > _threshold())
    st, _ = open_stream(native_echo, METHOD, window_bytes=16 * MB)
    over_shm = native_echo.transport == "shm_ring"
    before = _counters(*ONE_SIDED)
    landed = np.empty(4 * MB, dtype=np.uint8)
    written = 0
    for i, chunk in enumerate(chunks):
        while written < len(chunks) and written - i < 6:
            st.write(chunks[written])
            written += 1
        assert st.read_into(landed, timeout_ms=10000) == chunk.size, i
        assert np.array_equal(landed[:chunk.size], chunk), i
    after = _counters(*ONE_SIDED)
    delta = {name: after[name] - before[name] for name in ONE_SIDED}
    assert delta["stream_bytes_written"] == 2 * sum(widths)
    assert delta["stream_one_sided_bytes"] == (2 * wide if over_shm else 0)
    assert delta["rma_tx_bytes"] == delta["stream_one_sided_bytes"]
    assert delta["rma_window_full"] == delta["rma_rejected"] == 0
    assert st.unread_high_water <= reference_stream.unread_bound(
        16 * MB, 4 * MB)
    st.destroy()


def test_with_every_chunk_one_sided_the_unread_stay_under_window_plus_a_chunk(
        shm_peer):
    """The credit is taken before the put and the unread bytes are counted
    when the descriptor's frame arrives, so the bound is the in-band
    one: what changes is where the unread bytes lie."""
    ch, accepted, windows = shm_peer
    windows["next"] = 8 * MB
    chunk = np.arange(3 * MB // 4, dtype=np.uint32)            # 3 MB
    st, _ = open_stream(ch, METHOD)
    assert _wait(lambda: len(accepted) == 1)
    peer = accepted[0]
    before = _counters(*ONE_SIDED)
    count = {"written": 0}

    def write_eight():
        for _ in range(8):
            st.write(chunk)
            count["written"] += 1

    writer = threading.Thread(target=write_eight)
    writer.start()
    # 8 MB admit three chunks of 3 MB: the third overruns the window.
    assert _wait(lambda: peer.pending() == 3)
    time.sleep(0.3)
    assert count["written"] == 3 and peer.pending() == 3
    bound = reference_stream.unread_bound(8 * MB, chunk.nbytes)
    assert peer.unread_high_water == 3 * chunk.nbytes <= bound
    landed = np.empty(chunk.nbytes, dtype=np.uint8)
    for _ in range(8):
        assert peer.read_into(landed, timeout_ms=5000) == chunk.nbytes
        assert np.array_equal(landed.view(np.uint32), chunk)
        assert peer.pending() <= 3
    writer.join(5.0)
    assert not writer.is_alive() and count["written"] == 8
    assert peer.unread_high_water <= bound
    after = _counters(*ONE_SIDED)
    assert after["stream_one_sided_bytes"] - before[
        "stream_one_sided_bytes"] == 8 * chunk.nbytes
    assert after["rma_window_full"] == before["rma_window_full"]
    st.destroy()


def test_a_receive_window_too_small_for_the_chunks_unread_sends_the_rest_in_band():
    """`trpc_rma_window_bytes` at its 16 MB minimum is 64 slots of 256 KB:
    three unread 4 MB chunks (17 slots each with their span header) fill
    it, and the chunks behind them go in band, as a unary body does on a
    full window; the order holds across the two ways, and once the spans
    have been read the window takes chunks again."""
    rng = np.random.default_rng(37)
    chunks = [rng.integers(0, 256, 4 * MB, dtype=np.uint8) for _ in range(7)]
    with _flag("trpc_rma_window_bytes", 16 * MB), _python_peer(
            use_shm=True) as (ch, accepted, windows):
        windows["next"] = 32 * MB
        st, _ = open_stream(ch, METHOD)
        assert _wait(lambda: len(accepted) == 1)
        peer = accepted[0]
        before = _counters(*ONE_SIDED)
        for chunk in chunks[:5]:
            st.write(chunk)
        assert _wait(lambda: peer.pending() == 5)
        held = _counters(*ONE_SIDED)
        assert held["stream_one_sided_bytes"] - before[
            "stream_one_sided_bytes"] == 3 * 4 * MB
        assert held["rma_window_full"] - before["rma_window_full"] == 2
        landed = np.empty(4 * MB, dtype=np.uint8)
        for chunk in chunks[:5]:
            assert peer.read_into(landed, timeout_ms=5000) == 4 * MB
            assert np.array_equal(landed, chunk)
        for chunk in chunks[5:]:
            st.write(chunk)
            assert peer.read_into(landed, timeout_ms=5000) == 4 * MB
            assert np.array_equal(landed, chunk)
        after = _counters(*ONE_SIDED)
        assert after["stream_one_sided_bytes"] - before[
            "stream_one_sided_bytes"] == 5 * 4 * MB
        assert after["stream_bytes_written"] - before[
            "stream_bytes_written"] == 7 * 4 * MB
        assert after["rma_window_full"] - before["rma_window_full"] == 2
        assert after["rma_rejected"] == before["rma_rejected"]
        st.destroy()


def test_a_chunk_whose_transfer_does_not_verify_closes_the_stream(shm_peer):
    """One chunk of the put is dropped, its completion bit stays clear and
    `rma_resolve` refuses the descriptor's frame.  A unary call would time
    out alone; a stream cannot lose ONE chunk and keep its order, so the
    reader gets what arrived before, then the close, never the chunk
    behind the lost one, and the writer's next write fails."""
    ch, accepted, windows = shm_peer
    windows["next"] = 16 * MB
    st, _ = open_stream(ch, METHOD)
    assert _wait(lambda: len(accepted) == 1)
    peer = accepted[0]
    st.write(b"first")
    assert _wait(lambda: peer.pending() == 1)
    before = _counters(*ONE_SIDED)
    fault.set_schedule("seed=36;drop=1.0;max=1")
    try:
        st.write(np.full(4 * MB, 7, dtype=np.uint8))    # lost on its way
    finally:
        fault.set_schedule("")
    assert _wait(lambda: _counters("rma_rejected")["rma_rejected"]
                 == before["rma_rejected"] + 1)

    def write_fails():
        try:
            st.write(b"behind the lost one")
        except RpcError:
            return True
        return False

    assert _wait(write_fails)
    assert peer.read(timeout_ms=5000) == b"first"
    with pytest.raises(StreamClosedError):
        peer.read(timeout_ms=5000)
    assert peer.pending() == 0
    # The faulted span went back to the window with the refusal.
    assert _wait(lambda: load_library().trpc_rma_spans_in_use() == 0)
    st.destroy()


@pytest.mark.parametrize("ending", ["the_reader_closes", "the_writer_closes"])
def test_chunks_left_unread_in_the_window_give_their_slots_back(ending):
    """Three 4 MB chunks lie unread in the reader's receive window (two
    slots each).  However the stream ends, each span's deleter runs when
    its chunk is dropped, and nothing of the connection is left in
    /dev/shm."""
    lib = load_library()
    names = _own_shm_names()
    assert lib.trpc_rma_spans_in_use() == 0
    chunk = np.full(4 * MB, 9, dtype=np.uint8)
    with _python_peer(use_shm=True) as (ch, accepted, windows):
        windows["next"] = 16 * MB
        st, _ = open_stream(ch, METHOD)
        assert _wait(lambda: len(accepted) == 1)
        peer = accepted[0]
        for _ in range(3):
            st.write(chunk)
        assert _wait(lambda: peer.pending() == 3)
        assert lib.trpc_rma_spans_in_use() == 6
        assert _own_shm_names() > names
        if ending == "the_reader_closes":
            peer.destroy()
        else:
            st.destroy()
            # The chunks stay readable behind the close; the application
            # lets go of them with its end.
            landed = np.empty(4 * MB, dtype=np.uint8)
            assert peer.read_into(landed, timeout_ms=5000) == 4 * MB
            assert lib.trpc_rma_spans_in_use() == 4
            peer.destroy()
        assert _wait(lambda: lib.trpc_rma_spans_in_use() == 0)
        st.destroy()
    assert _wait(lambda: _own_shm_names() == names)


def test_a_dead_connection_with_echoes_unread_leaves_nothing_behind():
    """The native echo has put three 4 MB echoes into the client's receive
    window and the client has read none when the server goes away: what
    arrived stays readable, and once the client lets go of its end neither
    a window slot nor a name in /dev/shm is left of the connection."""
    lib = load_library()
    names = _own_shm_names()
    chunk = np.full(4 * MB, 5, dtype=np.uint8)
    srv = Server()
    srv.register_native_stream_echo(METHOD)
    ch = Channel(f"127.0.0.1:{srv.start()}", timeout_ms=10000, use_shm=True)
    try:
        st, _ = open_stream(ch, METHOD, window_bytes=16 * MB)
        for _ in range(3):
            st.write(chunk)
        assert _wait(lambda: st.pending() == 3)
        assert _wait(lambda: lib.trpc_rma_spans_in_use() == 6)
        srv.close()
        landed = np.empty(4 * MB, dtype=np.uint8)
        assert st.read_into(landed, timeout_ms=5000) == 4 * MB
        assert np.array_equal(landed, chunk)
        assert st.pending() == 2
        st.destroy()                        # two echoes never read
        assert _wait(lambda: lib.trpc_rma_spans_in_use() == 0)
    finally:
        ch.close()
        srv.close()
    assert _wait(lambda: _own_shm_names() == names)
