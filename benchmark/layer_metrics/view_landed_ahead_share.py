"""D2H/H2D staging: share of the bytes of device-to-host views that had
already landed when their caller first asked for them (counters
`host_view_ahead_bytes` over `host_view_bytes`, noted by
`zerocopy.PendingView.resolve`): how often a transfer that was started
ahead was really hidden behind the caller's other work.  100 where every
view's bytes were there; 0 where every first `resolve()` found the
transfer still on its way, and where no view was asked for (the CPU
rehearsal: dlpack imports every array).  A program without the counters
reads nothing."""

UNIT = "%"
DRIVERS = ("kv_pull", "kv_seq_pull")


def read(ev):
    if ("host_view_bytes" not in ev.counters
            or "host_view_ahead_bytes" not in ev.counters):
        return None
    asked = ev.counters["host_view_bytes"]
    if not asked:
        return 0.0
    return 100.0 * ev.counters["host_view_ahead_bytes"] / asked
