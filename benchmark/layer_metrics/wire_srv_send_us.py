"""Transport: the part of the response's leg spent inside the server's
send call, from the entry of `done()` to the return of the one-sided
put, the stripes or the frame's hand-over to the connection: the
server's own per-method fold (`rpc_server_<method>_send_us` per
`rpc_server_<method>_calls`) of the one method the cell's pipeline
calls, `Echo.Echo` in the served cells and `Kv.Fetch` in the KV cells.
A program without the counters, and a window in which the method
answered nothing, reads nothing."""

UNIT = "us"
DRIVERS = ("served_echo", "kv_pull", "kv_seq_pull")
METHODS = ("Echo.Echo", "Kv.Fetch")


def read(ev):
    calls = send_us = 0.0
    for method in METHODS:
        answered = ev.counters.get(f"rpc_server_{method}_calls", 0.0)
        if answered:
            calls += answered
            send_us += ev.counters[f"rpc_server_{method}_send_us"]
    if not calls:
        return None
    return send_us / calls
