"""Channel/Socket/dispatcher: time a writer of the stream spent parked on
an exhausted window, per chunk written (counters `stream_credit_wait_us`
over `stream_chunks_written`: both ends of the stream are in the process,
so the client's writes and the echo's).  0 while the credit gate never
closes; with more chunks open than a window holds it closes in every
cycle, and this is what the writers pay for the bound on memory."""

UNIT = "us"
DRIVERS = ("stream_echo",)


def read(ev):
    chunks = ev.counters.get("stream_chunks_written", 0.0)
    if not chunks:
        return None
    return ev.counters.get("stream_credit_wait_us", 0.0) / chunks
