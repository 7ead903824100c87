"""Served path, tail: mean time a finished call lay in the done-ring
until the client thread's poll handed it out (counter `batch_ready_us`
per `batch_calls_polled`)."""

UNIT = "us"
DRIVERS = ("served_echo",)


def read(ev):
    calls = ev.counters.get("batch_calls_polled", 0.0)
    if not calls:
        return None
    return ev.counters["batch_ready_us"] / calls
