"""Python/C-ABI boundary: client-thread time inside `submit` and the
non-waiting polls, per call completed in the window."""

UNIT = "us"
DRIVERS = ("served_echo",)


def read(ev):
    if not ev.call_s:
        return None
    inside = sum(ev.spans.total(name, ev.t_open, ev.t_close)
                 for name in ("submit", "poll"))
    return inside / len(ev.call_s) * 1e6
