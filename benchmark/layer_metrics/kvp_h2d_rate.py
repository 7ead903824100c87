"""D2H/H2D staging: the bytes the window's turns restored over the time
of their `jax.device_put`s ended by `block_until_ready` (`h2d`: one a
window of landed blocks, up to `fetch_window_pages` blocks each)."""

UNIT = "GB/s"
DRIVERS = ("kv_prefix",)


def read(ev):
    seconds = ev.spans.total("h2d", ev.t_open, ev.t_close)
    if not seconds or not ev.call_s:
        return None
    return ev.bytes_per_call * len(ev.call_s) / seconds / 1e9
