"""Ordered byte-chunk streams with credit flow control (parity:
cpp/net/stream.h over capi/stream_capi.cc).

This is the SERVED path's stream: chunks cross a `Channel`'s connection
(tcp or the shm ring) between two processes' runtimes, and it is the one
the benchmark's `stream_echo` cell and the inference front door use.
`brpc_tpu/streaming/stream.py` is the MESH plane's: a `lax.scan` of
`ppermute`s between the chips of one program, with no connection, no
window and no C++ under it.

A stream rides an ordinary RPC: the client OFFERS one with
``open_stream(channel, method, request)`` (StreamCreate before
CallMethod); the server handler ACCEPTS it via ``Call.accept_stream()``
before responding (or natively: ``Server.register_native_stream_echo``).
After the response both ends hold an established Stream and exchange
ordered chunks, each delivered whole, exactly once, byte-exact — writes
park while the peer's credit window is exhausted (the GIL is released,
so other Python threads run), reads block on a plain condition variable
fed by the consume fiber.

The window (`window_bytes`, upstream's `max_buf_size`) is what an end
lets lie unread before its writer stops.  A chunk is admitted whenever
the window is not exhausted, whatever its width, and its bytes go back to
the writer when THIS end's application has read it (`read_into` and
everything on top of it), not when the runtime queued it: an end that
stops reading stops its writer after window + one chunk, and holds no
more than that (`unread_high_water`).  One thread that writes and reads
the same echoed stream must therefore keep fewer bytes open than the two
windows hold together, or it parks in `write` against its own unread
echoes.

What a chunk costs at this boundary: `write` of 64 KB or more wraps the
caller's memory (no copy; the object is kept alive until the frame is
written), a smaller one is copied once; `read_into` copies once, out of
the frame the chunk arrived in, into the caller's buffer.  The native
counters `stream_capi_write_copy_bytes` / `stream_capi_read_copy_bytes`
say so.  `write_array` / `read_array` are the same two calls with the
device on either side.

Where a wide chunk travels: over the runtime's large-message threshold
(`trpc_stripe_threshold`, 2 MB) on a connection with a one-sided session
(the shm ring), the chunk's bytes are put straight into the peer's
receive window and only a descriptor is framed, in the chunk's place in
the stream's order; the reader's one copy is then out of that window,
over the connection's rails.  Under the threshold, over tcp, or when the
window is full, the chunk is one in-band frame as before.  Nothing at
this surface changes; `stream_one_sided_bytes` counts the bytes that went
that way.

Thousands of logical streams multiplex over ONE connection: a StreamId
is a runtime handle, not a socket, which is how the inference front door
(brpc_tpu/rpc/infer.py) holds 100k+ token streams under a 20k fd cap.
"""

from __future__ import annotations

import ctypes

import numpy as np

from brpc_tpu.rpc import zerocopy
from brpc_tpu.rpc._lib import IOBuf, load_library
from brpc_tpu.rpc.client import RpcError, make_rpc_error

# From this width a written chunk is wrapped, below it copied: the pin and
# the deleter's call back into Python cost more than copying 64 KB.
WRITE_BY_REFERENCE_FROM = 1 << 16


class StreamClosedError(RpcError):
    """The peer closed (or the connection died) and every buffered chunk
    has been drained — raised by read()/read_exactly() instead of
    returning data.  Writes after this surface EPIPE via RpcError."""

    def __init__(self, stream_id: int):
        super().__init__(0, f"stream {stream_id} closed and drained")
        self.stream_id = stream_id


class StreamTimeoutError(RpcError):
    """read() hit its timeout with no chunk buffered and the stream
    still open.  The stream remains usable — retry the read."""

    def __init__(self, stream_id: int, timeout_ms: int):
        super().__init__(
            0, f"stream {stream_id} read timed out after {timeout_ms}ms")
        self.stream_id = stream_id


class StreamChunkTooLargeError(RpcError):
    """The next buffered chunk is larger than read()'s max_bytes.
    NOTHING was consumed or truncated — the chunk stays queued; retry
    with max_bytes >= .needed (silently dropping the tail would
    desynchronize framed readers without any error)."""

    def __init__(self, stream_id: int, needed: int, cap: int):
        super().__init__(
            0, f"stream {stream_id} next chunk is {needed} bytes but the "
               f"read buffer holds only {cap}")
        self.stream_id = stream_id
        self.needed = needed
        self.cap = cap


class Stream:
    """One end of an established stream.  Wraps the capi handle; close()
    is graceful (buffered chunks stay readable on the peer), __del__
    frees the native handle."""

    def __init__(self, lib, handle):
        self._lib = lib
        self._handle = handle

    @property
    def id(self) -> int:
        """The runtime StreamId (diagnostics; matches /streams dump)."""
        return int(self._lib.trpc_stream_id(self._handle))

    def next_len(self, timeout_ms: int = -1) -> int:
        """Length of the next chunk, waiting up to timeout_ms for one
        (< 0 forever, 0 not at all); nothing is consumed.  Raises
        StreamClosedError once the stream is closed and drained and
        StreamTimeoutError when none came in time."""
        if self._handle is None:
            raise StreamClosedError(0)
        n = self._lib.trpc_stream_next_len(self._handle, timeout_ms)
        if n == -1:
            raise StreamClosedError(self.id)
        if n == -2:
            raise StreamTimeoutError(self.id, timeout_ms)
        return int(n)

    def read_into(self, buffer, timeout_ms: int = -1) -> int:
        """One ordered chunk (chunks never coalesce, split, or truncate)
        copied once, out of the frame it arrived in, into `buffer` (any
        writable C-contiguous buffer-protocol object); returns its
        length.  The chunk's bytes go back to the writer's window when
        the copy is done.  Raises as `read` does; a chunk larger than
        the buffer stays queued (StreamChunkTooLargeError)."""
        if self._handle is None:
            raise StreamClosedError(0)
        dest = np.frombuffer(buffer, dtype=np.uint8)
        if not dest.flags.writeable:
            raise ValueError("read_into needs a writable buffer")
        n = self._lib.trpc_stream_read(self._handle, dest.ctypes.data,
                                       dest.size, timeout_ms)
        if n == -1:
            raise StreamClosedError(self.id)
        if n == -2:
            raise StreamTimeoutError(self.id, timeout_ms)
        if n == -3:
            raise StreamChunkTooLargeError(
                self.id, self.next_len(0), dest.size)
        return int(n)

    def read(self, max_bytes: int = 65536, timeout_ms: int = -1) -> bytes:
        """One ordered chunk as `bytes`: `read_into` a buffer of the
        chunk's own length, and one more copy into the object returned
        (for wide chunks use `read_into` / `read_block`).  timeout_ms
        < 0 waits forever.  Raises StreamClosedError once the stream is
        closed and drained, StreamTimeoutError on timeout, and
        StreamChunkTooLargeError when the next chunk exceeds max_bytes —
        the chunk stays queued, so retry with max_bytes >= the error's
        .needed."""
        needed = self.next_len(timeout_ms)
        if needed > max_bytes:
            raise StreamChunkTooLargeError(self.id, needed, max_bytes)
        buf = bytearray(needed)
        self.read_into(buf, timeout_ms=0)
        return bytes(buf)

    def read_block(self, timeout_ms: int = -1) -> np.ndarray:
        """One ordered chunk in a uint8 block of its own length taken
        from the recycled landing blocks (`zerocopy.landing_block`: pages
        already faulted in, given back when the array dies): where a wide
        chunk lands on its way to the device.  Still one copy: a chunk that
        came through the one-sided window is copied out of its span over
        the connection's rails, and the span goes back to the window."""
        block = zerocopy.landing_block(self.next_len(timeout_ms))
        self.read_into(block, timeout_ms=0)
        return block

    def read_array(self, dtype=np.uint8, shape=None, device=None,
                   timeout_ms: int = -1):
        """One ordered chunk as a device array: `read_block`, then
        `jax.device_put` of the block seen as `dtype` (and `shape`) onto
        `device` (None: the default).  Not waited for."""
        import jax

        host = self.read_block(timeout_ms).view(dtype)
        if shape is not None:
            host = host.reshape(shape)
        return jax.device_put(host, device)

    def write(self, data) -> None:
        """Ordered write of one chunk; parks while the peer's credit
        window is exhausted (GIL released).  `data` is any C-contiguous
        buffer-protocol object (bytes, a numpy array of any dtype) or a
        `zerocopy.PendingView`, whose transfer is waited for here.  From
        WRITE_BY_REFERENCE_FROM bytes on, the chunk is `data`'s own
        memory, kept alive until the frame has been written and not to be
        changed until then; below it the bytes are copied.  A chunk over
        the large-message threshold on the shm ring is put into the peer's
        one-sided receive window from that memory before `write` returns,
        and its frame carries the descriptor alone.  Raises on a closed
        stream or dead connection (EPIPE/EINVAL as RpcError)."""
        if self._handle is None:
            raise StreamClosedError(0)
        if isinstance(data, zerocopy.PendingView):
            flat = data.resolve()
        else:
            flat = np.frombuffer(data, dtype=np.uint8)
        if flat.size < WRITE_BY_REFERENCE_FROM:
            rc = self._lib.trpc_stream_write(
                self._handle, flat.ctypes.data, flat.size)
        else:
            # The deleter runs exactly once, also when the write fails.
            rc = self._lib.trpc_stream_write_user(
                self._handle, flat.ctypes.data, flat.size,
                ctypes.cast(zerocopy.release_cb, ctypes.c_void_p),
                zerocopy.pin(data, flat))
        if rc != 0:
            raise make_rpc_error(self._lib, rc,
                                 f"stream write failed (errno {rc})")

    def write_array(self, array) -> None:
        """One device array as one chunk: `zerocopy.host_view` (no copy
        where the bytes are host-visible, else exactly one device-to-host
        transfer, landing in a recycled block), then `write`."""
        view, _owner = zerocopy.host_view(array)
        self.write(view)

    @property
    def unread_high_water(self) -> int:
        """The most bytes this end has held received and unread: under
        its window plus one chunk.  0 once the stream is gone."""
        if self._handle is None:
            return 0
        return int(self._lib.trpc_stream_unread_high_water(self._handle))

    def pending(self) -> int:
        """Chunks buffered locally, readable without blocking."""
        if self._handle is None:
            return 0
        return int(self._lib.trpc_stream_pending(self._handle))

    def close(self) -> None:
        """Graceful close of this end (idempotent).  The peer reads any
        in-flight chunks, then its reads raise StreamClosedError."""
        if self._handle is not None:
            self._lib.trpc_stream_close(self._handle)

    def destroy(self) -> None:
        """Close and free the native handle.  The stream's callbacks
        hold their own reference, so a consume batch mid-delivery
        finishes safely."""
        handle, self._handle = self._handle, None
        if handle is not None:
            self._lib.trpc_stream_destroy(handle)

    def __enter__(self) -> "Stream":
        return self

    def __exit__(self, *exc) -> None:
        self.destroy()

    def __del__(self):
        try:
            self.destroy()
        except Exception:
            pass


def open_stream(channel, method: str, request: bytes = b"",
                timeout_ms: int = 0, window_bytes: int = 0,
                tenant: str = "", priority: int = 0):
    """Offers a stream on `method`'s request over `channel` (a
    client.Channel) and returns ``(Stream, response_bytes)`` once the
    server accepts.  window_bytes = 0 keeps the flag default credit
    window (trpc_stream_window_bytes); tenant/priority override the
    channel's QoS for this call only.  Raises the typed RpcError when
    the call fails (the offered stream is torn down server-side)."""
    lib = load_library()
    resp = IOBuf()
    err_code = ctypes.c_int(0)
    err = ctypes.create_string_buffer(256)
    handle = lib.trpc_stream_open(
        channel._ptr, method.encode(), request, len(request), timeout_ms,
        window_bytes, tenant.encode(), int(priority), resp._ptr,
        ctypes.byref(err_code), err, 256)
    if not handle:
        raise make_rpc_error(lib, err_code.value,
                             err.value.decode(errors="replace"))
    return Stream(lib, handle), resp.to_bytes()
