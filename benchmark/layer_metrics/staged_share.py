"""D2H/H2D staging: share of the window's calls whose request went
through the pipeline's stager, i.e. left the client thread before its
bytes were on the host (counter `batch_staged_calls` per
`batch_calls_polled`).  A program without the counter reads nothing."""

UNIT = "%"
DRIVERS = ("served_echo",)


def read(ev):
    calls = ev.counters.get("batch_calls_polled", 0.0)
    if not calls or "batch_staged_calls" not in ev.counters:
        return None
    return 100.0 * ev.counters["batch_staged_calls"] / calls
