"""Prefix store: time prefix fetches and publishes waited for the
store's one lock, per block served (counters `kv_prefix_lock_wait_us`
over `kv_prefix_fetch_total`; a lock found free counts nothing).  What
a copy under the lock would show: a demote or a promote of 9 MB holds
it for none of its copy."""

UNIT = "us"
DRIVERS = ("kv_prefix",)


def read(ev):
    served = ev.counters.get("kv_prefix_fetch_total")
    if not served or "kv_prefix_lock_wait_us" not in ev.counters:
        return None
    return ev.counters["kv_prefix_lock_wait_us"] / served
