"""Transport: the response's leg of a call's wire time: mean time from
the entry of the handler's `done()` at the server to the entry of the
call's completion at the caller (the put into the one-sided window or
the write, the ring or socket, read, parse, dispatch).  `batch_net_us`
is wire less the server's arrival-to-done, both legs together; less the
request's leg (`batch_req_leg_us`) it is the response's, per
`batch_leg_calls`.  Read only where every split call had its legs
(`batch_leg_calls` = `batch_split_calls`): the two sums are then over
the same calls.  A program without the stamps reads nothing."""

UNIT = "us"
DRIVERS = ("served_echo", "kv_pull", "kv_seq_pull")


def read(ev):
    calls = ev.counters.get("batch_leg_calls", 0.0)
    if not calls or calls != ev.counters.get("batch_split_calls", 0.0):
        return None
    return (ev.counters["batch_net_us"]
            - ev.counters["batch_req_leg_us"]) / calls
