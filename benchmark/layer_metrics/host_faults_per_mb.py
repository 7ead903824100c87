"""D2H/H2D staging: minor page faults of the process per MB of request
bytes the stager waited for (counter `process_faults_minor` over
`batch_stage_fetch_bytes` / 1e6).  A fetch that lands in host memory the
process has never touched costs 244 faults a MB (4 KB pages); what is
left where fetches land in recycled blocks says where else the process
touches new pages.  0 where the stager fetched nothing; a program
without either counter reads nothing."""

UNIT = "faults/MB"
DRIVERS = ("served_echo",)


def read(ev):
    if ("process_faults_minor" not in ev.counters
            or "batch_stage_fetch_bytes" not in ev.counters):
        return None
    staged_mb = ev.counters["batch_stage_fetch_bytes"] / 1e6
    if not staged_mb:
        return 0.0
    return ev.counters["process_faults_minor"] / staged_mb
