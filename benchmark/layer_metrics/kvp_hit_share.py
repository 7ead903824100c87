"""Prefix store: blocks served from the pool over prompt pages asked for
(counters `kv_prefix_fetch_total` over `kv_prefix_match_keys`: every
turn asks `KvReg.Match` for the chain key of each full page of its
prompt): the share of a prompt that came from the cache and was not
prefilled.  A program without the counters reads nothing."""

UNIT = "%"
DRIVERS = ("kv_prefix",)


def read(ev):
    asked = ev.counters.get("kv_prefix_match_keys")
    if not asked or "kv_prefix_fetch_total" not in ev.counters:
        return None
    return 100.0 * ev.counters["kv_prefix_fetch_total"] / asked
