"""D2H/H2D staging: mean time from `zerocopy.host_view` starting a
request's transfer to the entry of the native submit that carried the
call (counter `batch_stage_us` per `batch_calls_polled`): the phase in
front of queue, wire, land and ready.  A program without the counter
reads nothing."""

UNIT = "us"
DRIVERS = ("served_echo",)


def read(ev):
    calls = ev.counters.get("batch_calls_polled", 0.0)
    if not calls or "batch_stage_us" not in ev.counters:
        return None
    return ev.counters["batch_stage_us"] / calls
