"""Prefix store: pages served from the pool over prompt pages asked for
(counters `kv_prefix_fetch_total` less `kv_prefix_state_fetch_total`,
over `kv_prefix_match_keys`: every turn asks `KvReg.Match` for the chain
key of each full page of its prompt): the share of a prompt that came
from the cache and was not prefilled.  A program without the state
counter reads nothing."""

UNIT = "%"
DRIVERS = ("kv_prefix_linear",)


def read(ev):
    asked = ev.counters.get("kv_prefix_match_keys")
    if not asked or "kv_prefix_state_fetch_total" not in ev.counters:
        return None
    pages = (ev.counters.get("kv_prefix_fetch_total", 0.0)
             - ev.counters["kv_prefix_state_fetch_total"])
    return 100.0 * pages / asked
