"""Channel/Socket/dispatcher: the part of a call's wire time between its
request being whole at the server and its handler being entered (QoS
lane, dispatch backlog, admission, the handler pool), by the server's
own clock (counter `batch_srv_queue_us` per `batch_split_calls`: the
polled calls whose response carried the server's stamps).  A program
without the stamps, and a window in which no call had them, reads
nothing."""

UNIT = "us"
DRIVERS = ("served_echo", "kv_pull", "kv_seq_pull")


def read(ev):
    calls = ev.counters.get("batch_split_calls", 0.0)
    if not calls:
        return None
    return ev.counters["batch_srv_queue_us"] / calls
