"""Channel/Socket/dispatcher: share of socket writes that went out inline
from the caller instead of through the write queue, over the window."""

UNIT = "%"
DRIVERS = ("served_echo",)


def read(ev):
    attempts = ev.counters.get("socket_inline_write_attempts", 0.0)
    if not attempts:
        return None
    return 100.0 * ev.counters["socket_inline_write_hits"] / attempts
