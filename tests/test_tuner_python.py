"""Self-tuning controller, Python surfaces (ISSUE 14): flag validators,
the /tuner builtin JSON over HTTP, the flag-introspection roundtrip
(observe.flags() == /flags?format=json == the C++ registry), and the
tuner module's status/decisions/counters bindings.

What the controller costs and recovers on the chip is not measured: no
cell of the benchmark turns it on.
"""

import json
import urllib.request

import pytest

from brpc_tpu.rpc import Server, get_flag, set_flag, tuner
from brpc_tpu.rpc import observe


@pytest.fixture
def parked_tuner():
    """Tuner enabled with the control loop parked (max interval) so
    nothing ticks behind the test's back; always disabled after."""
    old_interval = get_flag("trpc_tuner_interval_ms")
    set_flag("trpc_tuner_interval_ms", "3600000")
    try:
        yield
    finally:
        tuner.enable_tuner(False)
        set_flag("trpc_tuner_interval_ms", old_interval)


def test_tuner_defaults_off_and_flags_validate():
    assert get_flag("trpc_tuner") == "false", \
        "trpc_tuner must default off (tuning is opt-in)"
    assert not tuner.tuner_enabled()
    # Counters frozen at 0 while the flag has never been on in this
    # process order-of-tests caveat: other tests flip it, so only the
    # validator invariants are asserted unconditionally here.
    for bad in ("bogus", "2", ""):
        with pytest.raises(ValueError):
            set_flag("trpc_tuner", bad)
    with pytest.raises(ValueError):
        set_flag("trpc_tuner_interval_ms", "5")  # below the 10ms floor
    with pytest.raises(ValueError):
        set_flag("trpc_tuner_interval_ms", "9999999999")
    with pytest.raises(ValueError):
        set_flag("trpc_tuner_eval_ticks", "0")
    with pytest.raises(ValueError):
        set_flag("trpc_tuner_hysteresis_pct", "95")


@pytest.mark.parametrize("flag", ["rpcz_enabled", "trpc_tuner"])
def test_opt_in_planes_default_off(flag):
    """Spans and the tuner's sampling cost the hot path
    one relaxed load unless switched on: the compiled-in default, whatever
    an earlier test of this process left the value at."""
    get_flag(flag)                       # rpcz registers its flags lazily
    record = {f["name"]: f for f in observe.flags()}[flag]
    assert record["default"] == "false", record


def test_flags_introspection_roundtrip():
    """observe.flags() carries {name, type, value, default, reloadable}
    for every flag and validator-declared bounds for the range-validated
    knobs — and agrees with get_flag."""
    fl = observe.flags()
    by_name = {f["name"]: f for f in fl}
    # Every entry carries the full record.
    for f in fl:
        for key in ("name", "type", "value", "default", "reloadable"):
            assert key in f, f
    # The tuner's actuated knobs all declare bounds (out-of-range
    # actuation impossible by construction).
    for knob, lo, hi in (
        ("trpc_stripe_chunk_bytes", 64 << 10, 64 << 20),
        ("trpc_stripe_rails", 1, 16),
        ("trpc_messenger_cut_budget", 0, 1 << 30),
        ("trpc_rma_window_bytes", 16 << 20, 4 << 30),
        ("trpc_tuner_interval_ms", 10, 3600000),
    ):
        f = by_name[knob]
        assert f["reloadable"] is True, f
        assert f["min"] == lo and f["max"] == hi, f
    # Values agree with the scalar reader.
    assert by_name["trpc_stripe_rails"]["value"] == \
        get_flag("trpc_stripe_rails")
    assert by_name["trpc_tuner"]["type"] == "bool"
    assert by_name["trpc_qos_lane_weights"]["type"] == "string"


def test_tuner_http_json_and_flags_json(parked_tuner):
    """/tuner serves the status+journal JSON (even while off), and
    /flags?format=json serves the same introspection records as
    observe.flags()."""
    srv = Server()
    srv.register_native_echo("Echo.Echo")
    srv.start(0)
    try:
        base = f"http://127.0.0.1:{srv.port}"
        with urllib.request.urlopen(f"{base}/tuner", timeout=10) as r:
            off = json.loads(r.read().decode())
        assert off["enabled"] is False
        assert "decisions" in off and "rules" in off

        srv.enable_tuner()  # the Server attach point
        assert tuner.tuner_enabled()
        with urllib.request.urlopen(f"{base}/tuner?limit=16",
                                    timeout=10) as r:
            on = json.loads(r.read().decode())
        assert on["enabled"] is True
        # The rule table is visible with knob + effective bounds.
        knobs = {r["knob"] for r in on["rules"]}
        assert "trpc_stripe_chunk_bytes" in knobs
        assert "trpc_messenger_cut_budget" in knobs
        for rule in on["rules"]:
            assert rule["mode"] in ("hill_climb", "aimd", "qos_weights")
        # Flip off over HTTP like any reloadable flag.
        with urllib.request.urlopen(
                f"{base}/flags/trpc_tuner?setvalue=false",
                timeout=10) as r:
            assert b"trpc_tuner = false" in r.read()
        assert not tuner.tuner_enabled()

        with urllib.request.urlopen(f"{base}/flags?format=json",
                                    timeout=10) as r:
            http_flags = json.loads(r.read().decode())
        assert {f["name"] for f in http_flags} == \
            {f["name"] for f in observe.flags()}
        chunk = next(f for f in http_flags
                     if f["name"] == "trpc_stripe_chunk_bytes")
        assert chunk["min"] == 64 << 10 and chunk["max"] == 64 << 20
    finally:
        tuner.enable_tuner(False)
        srv.stop()


def test_tuner_status_counters_and_decisions_bindings(parked_tuner):
    st = tuner.status()
    assert set(st) >= {"enabled", "interval_ms", "ticks_total",
                       "decisions_total", "reverts_total",
                       "freezes_total", "rules", "inputs", "decisions"}
    c = tuner.counters()
    assert set(c) == {"ticks", "decisions", "reverts", "freezes"}
    assert all(isinstance(v, int) for v in c.values())
    # decisions() parses whatever the journal holds into typed records.
    for d in tuner.decisions():
        assert d.action in ("apply", "revert", "freeze")
        assert d.knob.startswith("trpc_")
