"""Transport: mean time from just before a call's `CallMethod` to the
entry of its completion: request out, the server's handler, response in
and parsed (counter `batch_wire_us` per `batch_calls_polled`).  Its sum
over the window, divided by the window, is the mean number of calls on
the wire."""

UNIT = "us"
DRIVERS = ("served_echo",)


def read(ev):
    calls = ev.counters.get("batch_calls_polled", 0.0)
    if not calls:
        return None
    return ev.counters["batch_wire_us"] / calls
