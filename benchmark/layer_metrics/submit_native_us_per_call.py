"""Python/C-ABI boundary: time inside `trpc_batch_submit` itself, entry
to return, per call polled in the window (counter `batch_submit_us` per
`batch_calls_polled`): the native side of `submit_us_per_call`, whose
rest is ctypes marshalling and the non-waiting poll."""

UNIT = "us"
DRIVERS = ("served_echo",)


def read(ev):
    calls = ev.counters.get("batch_calls_polled", 0.0)
    if not calls:
        return None
    return ev.counters["batch_submit_us"] / calls
