"""Channel/Socket/dispatcher: the part of a call's wire time inside the
server's handler, from its entry to its `done()` (the native echo;
`Kv.Fetch`'s lookup and pin), by the server's own clock (counter
`batch_srv_handler_us` per `batch_split_calls`).  A program without the
stamps, and a window in which no call had them, reads nothing."""

UNIT = "us"
DRIVERS = ("served_echo", "kv_pull", "kv_seq_pull")


def read(ev):
    calls = ev.counters.get("batch_split_calls", 0.0)
    if not calls:
        return None
    return ev.counters["batch_srv_handler_us"] / calls
