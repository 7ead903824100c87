"""Python/C-ABI boundary: host bytes copied at the boundary over the
payload bytes that crossed it, both directions (counters
`stream_capi_write_copy_bytes` + `stream_capi_read_copy_bytes` over
`stream_bytes_written`: what the client wrote and what the echo wrote
back for it to read).  50.0 when a write wraps the caller's memory and a
read copies once out of the frame; 100.0 when both copy."""

UNIT = "%"
DRIVERS = ("stream_echo",)


def read(ev):
    nbytes = ev.counters.get("stream_bytes_written", 0.0)
    if not nbytes:
        return None
    copied = (ev.counters.get("stream_capi_write_copy_bytes", 0.0)
              + ev.counters.get("stream_capi_read_copy_bytes", 0.0))
    return 100.0 * copied / nbytes
