"""Channel/Socket/dispatcher: records a batch registry RPC carried
(counters `kv_reg_many_records` over `kv_reg_many_total`, which close
together in the handler); a program without them reads nothing."""

UNIT = "records"
DRIVERS = ("kv_pull",)


def read(ev):
    rpcs = ev.counters.get("kv_reg_many_total", 0.0)
    if not rpcs:
        return None
    return ev.counters["kv_reg_many_records"] / rpcs
