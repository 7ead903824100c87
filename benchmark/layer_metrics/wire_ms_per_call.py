"""Transport: median time from a call's submit until the client thread
polled its completion: request and response on the wire and the server's
handler between, plus the wait until the one client thread came back to
poll.  The program stamps no completion with its own time, so this is
the wire's upper bound; with several calls in flight it is mostly the
wait, and it nears the wire itself only with one call in flight."""

from benchmark import stats

UNIT = "ms"
DRIVERS = ("served_echo",)


def read(ev):
    wire = ev.spans.durations("wire", ev.t_open, ev.t_close)
    return stats.median(wire) * 1e3 if wire else None
