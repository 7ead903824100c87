"""Transport: mean time the completion fiber (the connection's dispatch
fiber) spent copying a response into the caller's buffer; 0 for a body
that landed in place (counter `batch_land_us` per `batch_calls_polled`)."""

UNIT = "us"
DRIVERS = ("served_echo",)


def read(ev):
    calls = ev.counters.get("batch_calls_polled", 0.0)
    if not calls:
        return None
    return ev.counters["batch_land_us"] / calls
