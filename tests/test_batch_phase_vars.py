"""The batch pipeline's phase clocks (cpp/capi/batch_capi.cc): five stamps
in each call's own state, folded at poll into always-on `batch_*`
counters of the native registry; a staged call (batch.py's stager) also
carries when its request's transfer to the host was started, the fifth
phase in front of the four.

Everything here reads the counters as deltas around one pipeline's
traffic on CPU loopback; the registry is the process's, so each test
settles its own calls before it reads.
"""

import errno
import time

import numpy as np
import pytest
from test_batch_staging import HeldArray
from test_hotpath_vars import _vars_json

from brpc_tpu.rpc import Channel, Server, observe, zerocopy

PHASES = ("batch_queue_us", "batch_wire_us", "batch_land_us",
          "batch_ready_us")
STAGING = ("batch_staged_calls", "batch_stage_us", "batch_stage_fetch_us",
           "batch_stage_fetch_bytes")
# The wire phase cut at the server's stamps (tests/test_server_phase_vars.py
# holds them to their meaning; here they ride along as whole-call sums).
SPLIT = ("batch_split_calls", "batch_srv_queue_us", "batch_srv_handler_us",
         "batch_net_us", "batch_leg_calls", "batch_req_leg_us")
COUNTERS = PHASES + STAGING + SPLIT + (
    "batch_calls_polled", "batch_calls_failed", "batch_resp_bytes",
    "batch_land_copy_bytes", "batch_submits", "batch_submit_us")


@pytest.fixture
def echo():
    srv = Server()
    srv.register_native_echo("Echo.Echo")
    srv.start(0)
    ch = Channel(f"127.0.0.1:{srv.port}", timeout_ms=10000)
    pipe = ch.pipeline()
    try:
        yield srv, pipe
    finally:
        srv.set_faults("")
        pipe.close()
        ch.close()
        srv.stop()


def _read() -> dict:
    dump = observe.Vars.dump()
    return {name: dump[name] for name in COUNTERS}


def _drain(pipe, n: int) -> list:
    done = []
    deadline = time.monotonic() + 15
    while len(done) < n and time.monotonic() < deadline:
        done.extend(pipe.poll(timeout_ms=2000))
    assert len(done) == n
    return done


def _settled(pipe) -> None:
    """Every submitted call has completed into the done-ring."""
    deadline = time.monotonic() + 15
    while pipe.inflight and time.monotonic() < deadline:
        time.sleep(0.002)
    assert pipe.inflight == 0


def _moved(before: dict) -> dict:
    after = _read()
    return {name: after[name] - before[name] for name in COUNTERS}


def test_every_polled_call_counts_once_and_the_phases_fit_its_interval(echo):
    _, pipe = echo
    n = 16
    requests = [np.full(4096, i, dtype=np.uint8) for i in range(n)]
    landing = [np.zeros(4096, dtype=np.uint8) for _ in range(n)]
    before = _read()
    t0 = time.perf_counter()
    pipe.submit("Echo.Echo", requests, resp_bufs=landing)
    done = _drain(pipe, n)
    interval_us = (time.perf_counter() - t0) * 1e6
    moved = _moved(before)
    assert all(c.ok for c in done)
    assert moved["batch_calls_polled"] == n
    assert moved["batch_calls_failed"] == 0
    assert all(moved[phase] >= 0 for phase in PHASES)
    # enter ... polled of every call lies inside submit ... last poll.
    total = sum(moved[phase] for phase in PHASES)
    assert 0 < total <= n * (interval_us + 1)
    assert moved["batch_wire_us"] > 0
    # Every one of them came back with the server's stamps, and the
    # server's share and the two legs are inside the wire phase.
    assert moved["batch_split_calls"] == moved["batch_leg_calls"] == n
    assert (moved["batch_srv_queue_us"] + moved["batch_srv_handler_us"]
            + moved["batch_net_us"] == moved["batch_wire_us"])
    assert 0 <= moved["batch_req_leg_us"] <= moved["batch_net_us"]
    assert moved["batch_submits"] == 1
    assert 0 <= moved["batch_submit_us"] <= interval_us + 1
    assert moved["batch_resp_bytes"] == n * 4096
    assert all(moved[name] == 0 for name in STAGING)   # nothing was pending


def test_a_polled_call_counts_as_staged_once_or_not_at_all_and_the_five_fit(
        echo):
    _, pipe = echo
    n, size = 6, 4096
    direct = [np.full(size, 100 + i, dtype=np.uint8) for i in range(n)]
    before = _read()
    pipe.submit("Echo.Echo", direct)
    _drain(pipe, n)
    t0 = time.perf_counter()
    held = [HeldArray(np.full(size, i, dtype=np.uint8), held=False)
            for i in range(n)]
    views = [zerocopy.host_view(a)[0] for a in held]
    # One ready request behind the pending ones rides the stager too.
    pipe.submit("Echo.Echo", views + [direct[0]])
    done = _drain(pipe, n + 1)
    interval_us = (time.perf_counter() - t0) * 1e6
    moved = _moved(before)
    assert all(c.ok for c in done)
    assert moved["batch_calls_polled"] == 2 * n + 1
    assert moved["batch_staged_calls"] == n + 1
    assert moved["batch_stage_fetch_bytes"] == n * size    # the pending ones
    assert 0 <= moved["batch_stage_fetch_us"] <= interval_us + 1
    # staged ... polled of every staged call lies inside host_view ...
    # last poll; the direct calls (stage 0) were settled before t0.
    assert 0 < moved["batch_stage_us"] <= (n + 1) * (interval_us + 1)


def test_a_held_fetch_shows_in_stage_and_in_no_other_phase(echo):
    _, pipe = echo
    n, size, hold_s = 3, 4096, 0.06
    held = [HeldArray(np.full(size, i, dtype=np.uint8)) for i in range(n)]
    before = _read()
    t0 = time.perf_counter()
    views = [zerocopy.host_view(a)[0] for a in held]
    pipe.submit("Echo.Echo", views)
    time.sleep(hold_s)
    for a in held:
        a.release()
    _drain(pipe, n)
    interval_us = (time.perf_counter() - t0) * 1e6
    moved = _moved(before)
    assert moved["batch_calls_polled"] == moved["batch_staged_calls"] == n
    assert moved["batch_stage_us"] >= n * hold_s * 1e6
    assert sum(moved[phase] for phase in PHASES) < n * hold_s * 1e6
    # The five phases are polled_us - staged_us of each call.
    five = moved["batch_stage_us"] + sum(moved[phase] for phase in PHASES)
    assert n * hold_s * 1e6 <= five <= n * (interval_us + 1)
    # The stager waited for the first; the others had landed by then.
    assert hold_s * 1e6 * 0.9 <= moved["batch_stage_fetch_us"] <= interval_us
    assert moved["batch_stage_fetch_bytes"] == n * size


def test_a_finished_call_left_unpolled_waits_in_ready_not_on_the_wire(echo):
    _, pipe = echo
    n = 4
    before = _read()
    pipe.submit("Echo.Echo", [b"r" * 1024] * n)
    _settled(pipe)
    time.sleep(0.05)
    _drain(pipe, n)
    moved = _moved(before)
    assert moved["batch_calls_polled"] == n
    assert moved["batch_ready_us"] >= n * 50_000
    assert moved["batch_wire_us"] < n * 50_000


def test_a_slow_server_shows_on_the_wire_not_in_ready(echo):
    srv, pipe = echo
    n = 4
    srv.set_faults("svr_delay=1:20")  # every dispatch parks 20 ms
    before = _read()
    pipe.submit("Echo.Echo", [b"w" * 1024] * n)
    _drain(pipe, n)
    moved = _moved(before)
    assert moved["batch_calls_polled"] == n
    assert moved["batch_wire_us"] >= n * 20_000
    assert moved["batch_ready_us"] < n * 20_000


@pytest.mark.parametrize("caller_buffer", [True, False])
def test_land_copy_bytes_are_the_bytes_copied_into_a_caller_buffer(
        echo, caller_buffer):
    _, pipe = echo
    n, size = 4, 64 << 10
    requests = [np.full(size, i + 1, dtype=np.uint8) for i in range(n)]
    landing = ([np.zeros(size, dtype=np.uint8) for _ in range(n)]
               if caller_buffer else None)
    before = _read()
    pipe.submit("Echo.Echo", requests, resp_bufs=landing)
    done = _drain(pipe, n)
    moved = _moved(before)
    assert moved["batch_resp_bytes"] == n * size
    if caller_buffer:
        # Plain tcp below the stripe threshold: the body arrives in pool
        # blocks and the completion fiber copies all of it.
        assert all(c.in_caller_buffer for c in done)
        assert moved["batch_land_copy_bytes"] == n * size
    else:
        assert moved["batch_land_copy_bytes"] == 0
        assert moved["batch_land_us"] == 0
        for c in done:
            c.data.release()


def test_a_call_that_times_out_counts_as_failed_and_in_no_sum(echo):
    srv, pipe = echo
    n = 3
    srv.set_faults("svr_delay=1:400")
    before = _read()
    pipe.submit("Echo.Echo", [b"t" * 64] * n, timeout_ms=50)
    done = _drain(pipe, n)
    moved = _moved(before)
    assert {c.status for c in done} == {errno.ETIMEDOUT}
    assert moved["batch_calls_failed"] == n
    assert moved["batch_submits"] == 1
    for name in PHASES + STAGING + SPLIT + (
            "batch_calls_polled", "batch_resp_bytes",
            "batch_land_copy_bytes"):
        assert moved[name] == 0, name
    time.sleep(0.45)  # the parked handlers answer into a live server


def test_a_staged_call_that_never_issues_counts_as_failed_and_in_no_sum(
        echo):
    _, pipe = echo
    lost = HeldArray(np.zeros(64, dtype=np.uint8), held=False, fails=True)
    held = HeldArray(np.ones(64, dtype=np.uint8))
    before = _read()
    tokens = pipe.submit("Echo.Echo", [zerocopy.host_view(lost)[0],
                                       zerocopy.host_view(held)[0]])
    assert pipe.cancel(tokens[1])
    held.release()
    done = _drain(pipe, 2)
    moved = _moved(before)
    assert sorted(c.status for c in done) == sorted(
        [errno.EIO, errno.ECANCELED])
    assert moved["batch_calls_failed"] == 2
    for name in PHASES + STAGING + ("batch_calls_polled",):
        assert moved[name] == 0, name


def test_the_phase_counters_are_on_the_vars_page_with_their_descriptions(
        echo):
    srv, _ = echo
    page = _vars_json(srv.port)
    assert not [name for name in COUNTERS if name not in page]
    exposition = observe.Vars.prometheus()
    for name in COUNTERS:
        # Adders are Prometheus counters: `<name>_total` with a HELP line.
        assert f"# HELP {name}_total " in exposition, name
    assert "done-ring" in exposition
    assert "stager" in exposition
