"""Channel/Socket/dispatcher: client-thread time in a block's registry
round trips (`register_many`, `lookup_many`, `evict_many`: the intervals
the driver's wrapper round the registry client records), per block of
the window."""

UNIT = "us"
DRIVERS = ("kv_pull",)


def read(ev):
    blocks = len(ev.spans.durations("publish", ev.t_open, ev.t_close))
    if not blocks:
        return None
    inside = sum(ev.spans.total(name, ev.t_open, ev.t_close)
                 for name in ("register", "lookup", "evict"))
    return inside / blocks * 1e6
