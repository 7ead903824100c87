"""Channel/Socket/dispatcher: the server's share of one registry round
trip: request whole to response handed off (queue + handler + send of
the server's own per-method fold) per call, over the three batch
methods a block or a sequence costs (`KvReg.RegisterMany`,
`KvReg.LookupMany`, `KvReg.EvictMany`; they go through `Channel.call`,
not the pipeline, so this is the one view inside them).  Set it against
a third of `kv_registry_us` / `kvh_registry_us`, the client thread's
time in the three: the rest is the two legs and Python's marshalling.
A program without the counters, and a window with no registry call,
reads nothing."""

UNIT = "us"
DRIVERS = ("kv_pull", "kv_seq_pull")
METHODS = ("KvReg.RegisterMany", "KvReg.LookupMany", "KvReg.EvictMany")
PARTS = ("queue_us", "handler_us", "send_us")


def read(ev):
    calls = sum(ev.counters.get(f"rpc_server_{method}_calls", 0.0)
                for method in METHODS)
    if not calls:
        return None
    inside = sum(ev.counters.get(f"rpc_server_{method}_{part}", 0.0)
                 for method in METHODS for part in PARTS)
    return inside / calls
