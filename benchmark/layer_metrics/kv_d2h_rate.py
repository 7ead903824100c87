"""D2H/H2D staging: a block's bytes over the client thread's time to get
them to the host: `zerocopy.host_view` (which starts the transfer) and
the wait for the bytes before the publish, means per block.  The page
does not go through the pipeline's stager (its requests are 112-byte
wires), so the spans are what there is; with transfers started ahead
the thread waits for little of them and the rate is what the thread
sees, not the link's."""

UNIT = "GB/s"
DRIVERS = ("kv_pull",)


def read(ev):
    took = sum(ev.spans.total(name, ev.t_open, ev.t_close)
               for name in ("d2h", "d2h_wait"))
    blocks = len(ev.spans.durations("d2h_wait", ev.t_open, ev.t_close))
    if not blocks or not took:
        return None
    return ev.bytes_per_call * blocks / took / 1e9
