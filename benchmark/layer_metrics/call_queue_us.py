"""Channel/Socket/dispatcher: mean time from the entry of a call's
`trpc_batch_submit` to just before its `CallMethod`: the wait for the
issuing fiber and, on a single connection where one fiber issues the
calls of a submit in turn, for the `CallMethod`s before it, each of which
starts its request's write (counter `batch_queue_us` per
`batch_calls_polled`)."""

UNIT = "us"
DRIVERS = ("served_echo",)


def read(ev):
    calls = ev.counters.get("batch_calls_polled", 0.0)
    if not calls:
        return None
    return ev.counters["batch_queue_us"] / calls
