"""The wire phase, opened (cpp/net/wire_split.h): a tstd request is
stamped four times in the server (whole, handler entered, done() entered,
response handed off), folded per method into always-on
`rpc_server_<method>_*` counters, and three of the stamps ride the
response back, where the batch pipeline's poll cuts `batch_wire_us` at
them (`batch_split_calls`, `batch_srv_queue_us`, `batch_srv_handler_us`,
`batch_net_us`, and on a connection whose two ends read one clock
`batch_leg_calls`, `batch_req_leg_us`).

Everything here reads the counters as deltas around one pipeline's
traffic on CPU loopback, over tcp and over the shm ring; the registry is
the process's, so each test settles its own calls before it reads.
"""

import errno
import socket
import struct
import threading
import time

import numpy as np
import pytest
from test_batch_phase_vars import _drain
from test_hotpath_vars import _vars_json

from brpc_tpu.rpc import Channel, Server, observe

METHOD = "Echo.Echo"
SERVER = tuple(f"rpc_server_{METHOD}_{part}"
               for part in ("calls", "queue_us", "handler_us", "send_us"))
SPLIT = ("batch_split_calls", "batch_srv_queue_us", "batch_srv_handler_us",
         "batch_net_us", "batch_leg_calls", "batch_req_leg_us")
COUNTERS = SERVER + SPLIT + ("batch_calls_polled", "batch_calls_failed",
                             "batch_wire_us")
CALLS, QUEUE, HANDLER, SEND = SERVER


def _read(names=COUNTERS) -> dict:
    dump = observe.Vars.dump()
    return {name: dump[name] for name in names}


def _moved(before: dict) -> dict:
    after = _read(tuple(before))
    return {name: after[name] - before[name] for name in before}


def _served(before: dict, n: int, calls: str = CALLS) -> dict:
    """The deltas once the server has folded all `n` calls (counted by
    `calls`): a call's fold runs after its response is handed off, so
    the caller's poll can be ahead of the last one by a few
    microseconds."""
    deadline = time.monotonic() + 5
    while True:
        moved = _moved(before)
        if moved[calls] >= n or time.monotonic() > deadline:
            return moved
        time.sleep(0.001)


@pytest.fixture(params=["tcp", "shm"])
def echo(request):
    srv = Server()
    srv.register_native_echo(METHOD)
    srv.start(0)
    ch = Channel(f"127.0.0.1:{srv.port}", timeout_ms=10000,
                 use_shm=request.param == "shm")
    pipe = ch.pipeline()
    try:
        yield srv, ch, pipe
    finally:
        srv.set_faults("")
        pipe.close()
        ch.close()
        srv.stop()


def test_every_served_call_counts_once_at_both_ends_and_the_parts_sum_to_wire(
        echo):
    _, ch, pipe = echo
    n = 24
    requests = [np.full(4096, i, dtype=np.uint8) for i in range(n)]
    landing = [np.zeros(4096, dtype=np.uint8) for _ in range(n)]
    before = _read()
    pipe.submit(METHOD, requests, resp_bufs=landing)
    done = _drain(pipe, n)
    moved = _served(before, n)
    assert all(c.ok for c in done)
    assert ch.transport in ("tcp", "shm_ring")
    assert moved[CALLS] == n
    assert moved["batch_calls_polled"] == n
    # Loopback and the ring are both one host: every call is split, and
    # every split call has its legs.
    assert moved["batch_split_calls"] == moved["batch_leg_calls"] == n
    assert all(moved[name] >= 0 for name in COUNTERS)
    # The identity, to the microsecond: req_leg + queue + handler +
    # resp_leg, with resp_leg = net - req_leg, is wire.
    resp_leg = moved["batch_net_us"] - moved["batch_req_leg_us"]
    assert resp_leg >= 0
    assert (moved["batch_req_leg_us"] + moved["batch_srv_queue_us"]
            + moved["batch_srv_handler_us"] + resp_leg
            == moved["batch_wire_us"])
    # The server's own fold saw the same calls through the same stamps.
    assert moved[QUEUE] == moved["batch_srv_queue_us"]
    assert moved[HANDLER] == moved["batch_srv_handler_us"]
    # The native echo's send is inside the handler's done(): it ends
    # before the response can be back at the caller.
    assert moved[SEND] <= moved["batch_net_us"]


def test_an_injected_dispatch_delay_is_queue_and_not_handler(echo):
    srv, _, pipe = echo
    n = 4
    before = _read()
    pipe.submit(METHOD, [b"q" * 1024] * n)
    _drain(pipe, n)
    quiet = _served(before, n)
    srv.set_faults("svr_delay=1:30")  # every dispatch parks 30 ms
    before = _read()
    pipe.submit(METHOD, [b"q" * 1024] * n)
    _drain(pipe, n)
    moved = _served(before, n)
    assert moved[CALLS] == moved["batch_split_calls"] == n
    assert moved[QUEUE] >= n * 30_000
    assert moved["batch_srv_queue_us"] >= n * 30_000
    assert moved[QUEUE] == moved["batch_srv_queue_us"]
    # The handler is the native echo on both sides of the fault.
    assert moved[HANDLER] < n * 5_000
    assert moved["batch_srv_handler_us"] < n * 5_000
    assert quiet[QUEUE] < n * 30_000


@pytest.fixture(params=["tcp", "shm"])
def sleeper(request):
    """A Python handler that sleeps 20 ms and answers."""
    srv = Server()

    def handler(call, data):
        time.sleep(0.020)
        call.respond(data)

    srv.register("Sleep.Echo", handler)
    srv.set_qos("solo:weight=1,limit=1")
    srv.start(0)
    ch = Channel(f"127.0.0.1:{srv.port}", timeout_ms=10000,
                 use_shm=request.param == "shm")
    pipe = ch.pipeline()
    try:
        yield srv, ch, pipe
    finally:
        srv.set_faults("")
        pipe.close()
        ch.close()
        srv.stop()


SLEEP = tuple(f"rpc_server_Sleep.Echo_{part}"
              for part in ("calls", "queue_us", "handler_us", "send_us"))


def test_a_handler_that_sleeps_moves_handler_time_alone(sleeper):
    _, _, pipe = sleeper
    n = 3
    names = SLEEP + SPLIT + ("batch_wire_us", "batch_calls_polled")
    before = _read(names)
    for _ in range(n):     # one at a time: no call queues behind the GIL
        pipe.submit("Sleep.Echo", [b"s" * 256])
        _drain(pipe, 1)
    calls, queue, handler, send = SLEEP
    moved = _served(before, n, calls)
    assert moved[calls] == moved["batch_split_calls"] == n
    assert moved[handler] >= n * 20_000
    assert moved["batch_srv_handler_us"] == moved[handler]
    assert moved[queue] < n * 10_000
    assert moved[send] < n * 10_000
    assert moved["batch_net_us"] < n * 10_000
    assert (moved["batch_net_us"] + moved["batch_srv_queue_us"]
            + moved["batch_srv_handler_us"] == moved["batch_wire_us"])


def test_a_shed_and_an_answered_before_the_handler_call_count_with_no_handler_time(
        sleeper):
    srv, ch, pipe = sleeper
    calls, queue, handler, send = SLEEP
    names = SLEEP + SPLIT + ("batch_calls_polled", "batch_calls_failed")
    # Shed: the tenant's one slot is held by a sleeping handler while two
    # more requests arrive.
    ch.set_qos("solo")
    before = _read(names)
    pipe.submit("Sleep.Echo", [b"a" * 64] * 3)
    done = _drain(pipe, 3)
    moved = _served(before, 3, calls)
    assert sorted(c.ok for c in done) == [False, False, True]
    assert moved[calls] == 3
    assert 20_000 <= moved[handler] < 2 * 20_000      # the one that ran
    assert moved["batch_calls_polled"] == moved["batch_split_calls"] == 1
    assert moved["batch_calls_failed"] == 2
    assert moved["batch_srv_handler_us"] == moved[handler]
    # Answered before any handler: an injected error at dispatch.
    srv.set_faults(f"svr_error=1:{errno.EHOSTDOWN}")
    before = _read(names)
    done = []
    for _ in range(2):     # one at a time: the tenant has one slot
        pipe.submit("Sleep.Echo", [b"b" * 64])
        done += _drain(pipe, 1)
    moved = _served(before, 2, calls)
    assert {c.status for c in done} == {errno.EHOSTDOWN}
    assert moved[calls] == 2
    assert moved[handler] == 0
    assert moved[queue] >= 0 and moved[send] >= 0
    # A failed call is in no sum of the caller's.
    assert all(moved[name] == 0 for name in SPLIT)


MAGIC = b"TRP1"


def _old_peer(listener: socket.socket, answered: list) -> None:
    """A tstd server that predates the stamps, in a dozen lines: it
    answers every request frame with a response whose meta ends at
    error_text, the payload echoed."""
    conn, _ = listener.accept()
    conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    buf = b""
    with conn:
        while True:
            chunk = conn.recv(65536)
            if not chunk:
                return
            buf += chunk
            while len(buf) >= 16:
                assert buf[:4] == MAGIC
                meta_len, payload_len = struct.unpack_from("<IQ", buf, 4)
                if len(buf) < 16 + meta_len + payload_len:
                    break
                meta = buf[16:16 + meta_len]
                payload = buf[16 + meta_len:16 + meta_len + payload_len]
                buf = buf[16 + meta_len + payload_len:]
                kind, cid = struct.unpack_from("<BQ", meta, 0)
                if kind != 0:
                    continue
                # type 1, cid, error 0, attachment 0, stream 0, flags 0,
                # ack 0, no method, no error text, NO tail.
                reply = struct.pack("<BQiIQBQII", 1, cid, 0, 0, 0, 0, 0, 0,
                                    0)
                conn.sendall(MAGIC + struct.pack("<IQ", len(reply),
                                                 len(payload))
                             + reply + payload)
                answered.append(cid)


def test_a_response_without_the_stamps_leaves_the_split_counters_still():
    listener = socket.socket()
    listener.bind(("127.0.0.1", 0))
    listener.listen(1)
    answered: list = []
    peer = threading.Thread(target=_old_peer, args=(listener, answered),
                            daemon=True)
    peer.start()
    ch = Channel(f"127.0.0.1:{listener.getsockname()[1]}", timeout_ms=10000)
    pipe = ch.pipeline()
    names = SPLIT + ("batch_calls_polled", "batch_wire_us")
    try:
        n = 5
        before = _read(names)
        pipe.submit(METHOD, [bytes([i]) * 512 for i in range(n)])
        done = _drain(pipe, n)
        moved = _moved(before)
        assert all(c.ok for c in done)
        assert sorted(c.tobytes() for c in done) == [
            bytes([i]) * 512 for i in range(n)]
        for c in done:
            c.data.release()
        assert len(answered) == n
        assert moved["batch_calls_polled"] == n
        assert moved["batch_wire_us"] > 0
        assert all(moved[name] == 0 for name in SPLIT), moved
    finally:
        pipe.close()
        ch.close()
        listener.close()
        peer.join(timeout=5)


def test_a_methods_counters_are_there_from_the_servers_start_with_no_flag_set():
    srv = Server()
    srv.register_native_echo("Fresh.Echo")
    srv.register("Fresh.Python", lambda call, data: call.respond(data))
    try:
        # Registered, not yet started, never called.
        dump = observe.Vars.dump()
        for method in ("Fresh.Echo", "Fresh.Python"):
            for part in ("calls", "queue_us", "handler_us", "send_us"):
                assert dump[f"rpc_server_{method}_{part}"] == 0
        srv.start(0)
        page = _vars_json(srv.port)
        assert "rpc_server_Fresh.Echo_queue_us" in page
        exposition = observe.Vars.prometheus()
        for name in SPLIT:
            assert f"# HELP {name}_total " in exposition, name
        assert "# HELP rpc_server_Fresh_Echo_handler_us_total " in exposition
        assert "server's clock" in exposition
    finally:
        srv.stop()


def test_two_servers_with_one_method_are_one_series():
    """A name in the registry has one owner: a store on each of two
    ranks in one process must not hide one another's calls."""
    servers = [Server(), Server()]
    pipes = []
    try:
        for srv in servers:
            srv.register_native_echo("Twin.Echo")
            srv.start(0)
            ch = Channel(f"127.0.0.1:{srv.port}", timeout_ms=10000)
            pipes.append((ch, ch.pipeline()))
        before = _read(("rpc_server_Twin.Echo_calls",))
        for _, pipe in pipes:
            pipe.submit("Twin.Echo", [b"t" * 64] * 3)
            _drain(pipe, 3)
        moved = _served(before, 6, "rpc_server_Twin.Echo_calls")
        assert moved["rpc_server_Twin.Echo_calls"] == 6
    finally:
        for ch, pipe in pipes:
            pipe.close()
            ch.close()
        for srv in servers:
            srv.stop()
