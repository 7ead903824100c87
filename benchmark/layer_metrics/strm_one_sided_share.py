"""Transport: the share of the stream's payload bytes, both directions,
whose chunk was put into the peer's one-sided receive window and framed
as a descriptor alone (counter `stream_one_sided_bytes` over
`stream_bytes_written`, both of `cpp/net/stream.cc`).  100.0 when every
chunk is over the large-message threshold on a connection with a
one-sided session and the window never filled; 0.0 over tcp, under the
threshold, or where the program has no such counter."""

UNIT = "%"
DRIVERS = ("stream_echo",)


def read(ev):
    nbytes = ev.counters.get("stream_bytes_written", 0.0)
    if not nbytes:
        return None
    return 100.0 * ev.counters.get("stream_one_sided_bytes", 0.0) / nbytes
