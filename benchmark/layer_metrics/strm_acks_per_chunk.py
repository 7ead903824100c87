"""Channel/Socket/dispatcher: ACK (feedback) frames sent per chunk whose
bytes were given back (counters `stream_acks_sent` over
`stream_chunks_consumed`, both ends): a reader acknowledges once half its
window has gathered, so 0.5 with a window of four chunks, 1.0 with a
window under two."""

UNIT = "acks"
DRIVERS = ("stream_echo",)


def read(ev):
    chunks = ev.counters.get("stream_chunks_consumed", 0.0)
    if not chunks:
        return None
    return ev.counters.get("stream_acks_sent", 0.0) / chunks
