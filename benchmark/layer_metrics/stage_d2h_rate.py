"""D2H/H2D staging: request bytes the stager waited for over the time it
was blocked waiting (counters `batch_stage_fetch_bytes` over
`batch_stage_fetch_us`): the rate of the fetch stream as the one thread
that waits for it sees it.  0 where the stager fetched nothing; a program
without the counters reads nothing."""

UNIT = "GB/s"
DRIVERS = ("served_echo",)


def read(ev):
    if "batch_stage_fetch_us" not in ev.counters:
        return None
    waited_us = ev.counters["batch_stage_fetch_us"]
    if not waited_us:
        return 0.0
    return ev.counters.get("batch_stage_fetch_bytes", 0.0) / waited_us / 1e3
