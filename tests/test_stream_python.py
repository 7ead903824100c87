"""The served path's stream through its Python surface
(brpc_tpu/rpc/stream.py over cpp/capi/stream_capi.cc over
cpp/net/stream.cc): a chunk of any width goes on a window of any width,
the window holds at the Python boundary (a chunk's bytes go back to the
writer when the application has read it), what `write` and `read_into`
copy is what the counters say, a device array goes in and comes out, and
the native stream echo (`Server.register_native_stream_echo`) keeps
order.  The system is compared with the plain reference
(benchmark/reference_stream.py) at small sizes.  Nothing here is a
measurement.
"""

import gc
import threading
import time

import numpy as np
import pytest

from benchmark import reference_stream
from brpc_tpu.rpc import (Channel, RpcError, Server, StreamChunkTooLargeError,
                          StreamTimeoutError, observe, open_stream, stream,
                          zerocopy)

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


@pytest.fixture
def python_peer():
    """(a Channel, the list the server's Python handler puts its accepted
    end of each stream into): a reader the test controls."""
    srv = Server()
    accepted = []
    windows = {"next": 0}

    def handler(call, _req):
        accepted.append(call.accept_stream(window_bytes=windows["next"]))
        call.respond(b"ok")

    srv.register(METHOD, handler)
    port = srv.start()
    ch = Channel(f"127.0.0.1:{port}", timeout_ms=10000)
    try:
        yield ch, accepted, windows
    finally:
        for peer in accepted:
            peer.destroy()
        ch.close()
        srv.close()


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
