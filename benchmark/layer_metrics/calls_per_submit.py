"""Python/C-ABI boundary: calls per `trpc_batch_submit` crossing over
the window (counters `batch_calls_polled` over `batch_submits`)."""

UNIT = "calls"
DRIVERS = ("served_echo",)


def read(ev):
    calls = ev.counters.get("batch_calls_polled", 0.0)
    submits = ev.counters.get("batch_submits", 0.0)
    if not calls or not submits:
        return None
    return calls / submits
