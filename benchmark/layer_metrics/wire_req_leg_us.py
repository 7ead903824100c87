"""Transport: the request's leg of a call's wire time: mean time from
just before `CallMethod` to the request being cut from the connection
and whole at the server (pack, write, ring or socket, read, cut, a large
body's reassembly), by the stamp the response carries back (counter
`batch_req_leg_us` per `batch_leg_calls`: the polled calls whose
connection's two ends read one clock, so that the server's stamp can be
set against the caller's).  A program without the stamps, and a window
in which no call had them, reads nothing."""

UNIT = "us"
DRIVERS = ("served_echo", "kv_pull", "kv_seq_pull")


def read(ev):
    calls = ev.counters.get("batch_leg_calls", 0.0)
    if not calls:
        return None
    return ev.counters["batch_req_leg_us"] / calls
