"""D2H/H2D staging: the bytes the window's turns restored, pages and
states, over the time of their `jax.device_put`s ended by
`block_until_ready` (`h2d`: one a window of landed pages; `state_h2d`:
one a turn's state)."""

UNIT = "GB/s"
DRIVERS = ("kv_prefix_linear",)


def read(ev):
    seconds = sum(ev.spans.total(name, ev.t_open, ev.t_close)
                  for name in ("h2d", "state_h2d"))
    if not seconds or not ev.call_s:
        return None
    return ev.bytes_per_call * len(ev.call_s) / seconds / 1e9
