"""The plain reference of the `stream_echo` deployment: what a client
reads back from an echoed stream, and how much may lie unread on the way,
in straightforward numpy and Python integers.  No program of the system
under test: no runtime, no frame, no credit counter, no ACK; a direction
of the stream is a FIFO with a byte window, and the echo is a second
FIFO fed from the first.

The semantics (apache/brpc docs/en/streaming_rpc.md): chunks are
delivered in the order written, each exactly once, whole and byte-exact;
a writer may write while the bytes it has written and its reader has not
yet taken are under the reader's window (`max_buf_size`), whatever the
width of the chunk it is about to write, so what lies unread at a reader
never passes its window plus one chunk.

The yardstick's rule for a fresh chunk is here too, because the
reference follows it: a chunk is the one before with its checksum (the
wrapping sum of its 32-bit words), made odd, added to every word, so that
no word of a chunk equals the same word of the chunk before.  The
running checksum folds the chunks' checksums in the order they were read
back, `running * MIX + checksum` mod 2^32 with an odd MIX: two chunks
swapped, one left out or one read twice change it, which a plain sum of
checksums would not notice.
"""

from __future__ import annotations

import collections

MASK = 0xFFFFFFFF
MIX = 0x9E3779B1


def next_chunk(prev):
    """uint32 words (numpy or jax.numpy) -> the next chunk's."""
    return prev + (prev.sum(dtype=prev.dtype) | prev.dtype.type(1))


def chunk_checksum(chunk) -> int:
    return int(chunk.sum(dtype=chunk.dtype)) & MASK


def fold(running: int, checksum: int) -> int:
    return (running * MIX + checksum) & MASK


def unread_bound(window_bytes: int, chunk_bytes: int) -> int:
    """The most a reader with `window_bytes` may hold unread when the
    widest chunk is `chunk_bytes`: the last chunk admitted found at least
    one byte of the window open."""
    return window_bytes + chunk_bytes - 1


class WindowedFifo:
    """One direction of a stream: chunks in the order written, and the
    bytes written that the reader has not yet taken."""

    def __init__(self, window_bytes: int):
        self.window_bytes = window_bytes
        self.chunks: collections.deque = collections.deque()
        self.unread_bytes = 0
        self.high_water = 0

    def admits(self) -> bool:
        return self.unread_bytes < self.window_bytes

    def write(self, chunk) -> None:
        if not self.admits():
            raise BufferError("the window is exhausted: the writer waits")
        self.chunks.append(chunk)
        self.unread_bytes += chunk.nbytes
        self.high_water = max(self.high_water, self.unread_bytes)

    def read(self):
        chunk = self.chunks.popleft()
        self.unread_bytes -= chunk.nbytes
        return chunk


def stream_echo_reference(first, widths, window_bytes: int,
                          chunks_open: int):
    """A client writes len(widths) chunks on an echoed stream, keeping at
    most `chunks_open` written and not read back, and reads every echo.
    Chunk i is the first `widths[i]` words of the i-th successor of
    `first` (uint32 words, numpy).  Both directions grant
    `window_bytes`.  Returns (the chunks as the client reads them back,
    the running checksum after the last, the most bytes that lay unread
    at the server and at the client)."""
    there, back = WindowedFifo(window_bytes), WindowedFifo(window_bytes)
    whole, written, delivered, running = first, 0, [], 0
    while len(delivered) < len(widths):
        # The echo is a party of its own: it moves what it may.
        while there.chunks and back.admits():
            back.write(there.read())
        # The client is one thread: it writes while it has fewer than
        # `chunks_open` open, else it reads one.
        if written < len(widths) and written - len(delivered) < chunks_open:
            if not there.admits():
                raise RuntimeError(
                    f"{chunks_open} chunks open against two windows of "
                    f"{window_bytes} B: the one client parks in its write "
                    "against its own unread echoes")
            whole = next_chunk(whole)
            there.write(whole[:widths[written]].copy())
            written += 1
        else:
            chunk = back.read()
            delivered.append(chunk)
            running = fold(running, chunk_checksum(chunk))
    return delivered, running, (there.high_water, back.high_water)


def running_checksum_after(first_checksum: int, words: int,
                           chunks: int) -> int:
    """The same running checksum at the timed size, for chunks of one
    width read back whole, followed in integers: adding c to each of a
    chunk's n words adds n*c to its checksum."""
    checksum, running = int(first_checksum), 0
    for _ in range(chunks):
        checksum = (checksum + words * (checksum | 1)) & MASK
        running = fold(running, checksum)
    return running
