"""Deadline & cancellation plane through the Python surface (ISSUE 15):

- deadline_scope propagates an end-to-end budget: calls stamp
  min(timeout, remaining), a tighter ambient budget surfaces the TYPED
  DeadlineExpiredError, and nested scopes only tighten;
- server-side enforcement: expired work is shed BEFORE the handler
  (deadline_expired_shed_total moves, handler never runs), with the
  in-deadline traffic unharmed — the svr_delay chaos composition;
- Python handlers read Call.remaining_us / Call.cancelled;
- the error-code table: _lib.ERROR_CODES mirrors the runtime capi
  (the lint error-code-sync rule pins the cpp side);
- the deadline knobs exist, validate, and reload; with trpc_deadline_wire
  off the deadline vars are provably frozen (byte-identity guard);
- cancel-scope registry hygiene: drains to zero when idle.
"""

import time

import pytest

from brpc_tpu.rpc import (
    Channel,
    DeadlineExpiredError,
    Server,
    deadline_scope,
    observe,
)
from brpc_tpu.rpc._lib import ERROR_CODES, load_library
from brpc_tpu.rpc.flags import get_flag, set_flag


def _var(name: str) -> int:
    return observe.Vars.dump().get(name, 0)


@pytest.fixture
def echo_server():
    srv = Server()
    srv.register_native_echo("Echo.Echo")
    srv.handled = []                     # what reached the Python handler

    def counted(call, req):
        srv.handled.append(bytes(req))
        call.respond(req)

    srv.register("Echo.Counted", counted)
    srv.start(0)
    try:
        yield srv
    finally:
        srv.set_faults("")
        srv.stop()


def test_error_code_table_matches_runtime():
    lib = load_library()
    assert ERROR_CODES["kEDeadlineExpired"] == lib.trpc_deadline_expired_code()
    assert ERROR_CODES["kEOverloaded"] == lib.trpc_qos_overloaded_code()
    assert ERROR_CODES["kEDraining"] == lib.trpc_draining_code()


def test_deadline_flags_exist_and_validate():
    lib = load_library()
    lib.trpc_deadline_ensure_registered()
    assert get_flag("trpc_deadline_wire") == "true"
    assert get_flag("trpc_cluster_retry_budget_pct") == "0"
    set_flag("trpc_cluster_retry_budget_pct", "10")
    assert get_flag("trpc_cluster_retry_budget_pct") == "10"
    with pytest.raises(ValueError):
        set_flag("trpc_cluster_retry_budget_pct", "101")  # out of [0,100]
    set_flag("trpc_cluster_retry_budget_pct", "0")


@pytest.mark.parametrize("method", ["Echo.Echo", "Echo.Counted"])
def test_scope_surfaces_typed_error_and_sheds_server_side(echo_server,
                                                          method):
    """svr_delay chaos + a tight end-to-end budget: the caller gets the
    TYPED DeadlineExpiredError at its budget (not a generic timeout at
    the much larger per-hop timeout), and the server sheds the expired
    request before the handler — never half-executed: a Python handler
    counts zero executions for it."""
    ch = Channel(f"127.0.0.1:{echo_server.port}", timeout_ms=10000)
    try:
        echo_server.set_faults("seed=1;svr_delay=1:150")
        shed0 = _var("deadline_expired_shed_total")
        t0 = time.monotonic()
        with deadline_scope(50):
            with pytest.raises(DeadlineExpiredError):
                ch.call(method, b"doomed")
        dt_ms = (time.monotonic() - t0) * 1000
        assert dt_ms < 150, f"died at the budget, not the delay: {dt_ms}"
        deadline = time.monotonic() + 3
        while _var("deadline_expired_shed_total") == shed0 and \
                time.monotonic() < deadline:
            time.sleep(0.01)
        assert _var("deadline_expired_shed_total") > shed0
        echo_server.set_faults("")
        # In-deadline traffic is unharmed.
        assert ch.call(method, b"fine") == b"fine"
        assert echo_server.handled == ([b"fine"] if method == "Echo.Counted"
                                       else [])
    finally:
        ch.close()


def test_nested_scopes_only_tighten(echo_server):
    with deadline_scope(500) as outer:
        with deadline_scope(10_000) as inner:
            # The inner scope asked for more than the outer's remainder:
            # it was clamped.
            assert inner.remaining_us <= 500_000
        assert outer.remaining_us <= 500_000


def test_python_handler_reads_remaining_and_cancelled():
    seen = {}
    srv = Server()

    def handler(call, data):
        seen["remaining"] = call.remaining_us
        seen["cancelled"] = call.cancelled
        call.respond(data)

    srv.register("Echo.Budget", handler)
    srv.start(0)
    ch = Channel(f"127.0.0.1:{srv.port}", timeout_ms=400)
    try:
        assert ch.call("Echo.Budget", b"x") == b"x"
        assert 0 < seen["remaining"] <= 400_000
        assert seen["cancelled"] is False
    finally:
        ch.close()
        srv.stop()


def test_wire_flag_off_freezes_deadline_vars(echo_server):
    """Byte-identity guard: with stamping off, no budget rides the wire
    and every deadline var is provably frozen."""
    set_flag("trpc_deadline_wire", "false")
    ch = Channel(f"127.0.0.1:{echo_server.port}", timeout_ms=5000)
    try:
        stamped0 = _var("deadline_stamped_total")
        shed0 = _var("deadline_expired_shed_total")
        for i in range(32):
            assert ch.call("Echo.Echo", b"p" * 64) == b"p" * 64
        assert _var("deadline_stamped_total") == stamped0
        assert _var("deadline_expired_shed_total") == shed0
    finally:
        set_flag("trpc_deadline_wire", "true")
        ch.close()


def test_stamping_on_by_default(echo_server):
    ch = Channel(f"127.0.0.1:{echo_server.port}", timeout_ms=5000)
    try:
        stamped0 = _var("deadline_stamped_total")
        assert ch.call("Echo.Echo", b"x") == b"x"
        assert _var("deadline_stamped_total") == stamped0 + 1
    finally:
        ch.close()


def test_cancel_registry_drains_when_idle(echo_server):
    lib = load_library()
    ch = Channel(f"127.0.0.1:{echo_server.port}", timeout_ms=5000)
    try:
        for _ in range(8):
            ch.call("Echo.Echo", b"x")
        deadline = time.monotonic() + 3
        while lib.trpc_cancel_registered() != 0 and \
                time.monotonic() < deadline:
            time.sleep(0.01)
        assert lib.trpc_cancel_registered() == 0
    finally:
        ch.close()
