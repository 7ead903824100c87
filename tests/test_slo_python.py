"""Fleet observability plane from Python (ISSUE 19): mergeable latency
digests, per-tenant SLO attainment / burn rates, and fleet publication
over naming://.

Acceptance exercised here:
- a genuine 3-PROCESS fleet publishes digest+SLO blobs into a parent
  registry and the /fleet merged per-tenant p99 matches a pooled
  single-digest oracle within the octave error bound (ratio <= 2);
- an induced latency regression (svr_delay chaos) flips the tenant's
  burn-rate alert within ONE fast window, counts one breach edge in
  slo_breach_total, and CLEARS after recovery — breach_total counts
  edges, not evaluations;
- flag-off (a fresh process, `trpc_slo` at its default false) the whole
  plane is invisible: every slo_* var frozen at 0, dump empty;
- the /slo and /fleet builtins serve the same JSON the C API dumps, and
  every slo_* var carries Prometheus HELP text;
- tools/fleet_top.py --json renders the same merged view standalone.
"""

import json
import os
import select
import subprocess
import sys
import time
import urllib.request

import pytest

from brpc_tpu.rpc import Channel, Server, observe
from brpc_tpu.rpc.flags import get_flag, set_flag
from brpc_tpu.rpc.naming import NamingClient

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

_SAVED_FLAGS = ("trpc_slo", "trpc_fleet_publish", "trpc_slo_fast_window_ms",
                "trpc_slo_slow_window_ms", "trpc_naming_lease_ms")


def _http(port: int, path: str) -> str:
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=5) as r:
        return r.read().decode()


@pytest.fixture()
def slo_flags():
    """Save/restore every flag this file flips; leave the plane off."""
    saved = {f: get_flag(f) for f in _SAVED_FLAGS}
    yield
    for f, v in saved.items():
        set_flag(f, v)


def _tenant_row(dump: dict, tenant: str) -> dict:
    rows = [t for t in dump["tenants"] if t["tenant"] == tenant]
    assert rows, f"tenant {tenant!r} missing from {dump!r}"
    return rows[0]


# ------------------------------------------ in-process surface + HTTP --


def test_slo_surface_vars_help_and_http(slo_flags):
    """One armed server: per-tenant attainment in slo_dump(), the same
    body over /slo, HELP text on every slo_* var, and /fleet degrading
    cleanly (naming-miss) when no registry exists in-process."""
    set_flag("trpc_slo_fast_window_ms", "2000")
    set_flag("trpc_slo_slow_window_ms", "8000")
    observe.enable_slo(True)
    srv = Server()
    srv.register_native_echo("Echo.Echo")
    srv.set_slo("tenantA:p99_us=2000,avail=99.0;*:p99_us=10000")
    srv.start(0)
    try:
        cha = Channel(f"127.0.0.1:{srv.port}", timeout_ms=2000,
                      qos_tenant="tenantA")
        chs = Channel(f"127.0.0.1:{srv.port}", timeout_ms=2000)
        for _ in range(40):
            assert cha.call("Echo.Echo", b"x" * 64) == b"x" * 64
        for _ in range(10):
            assert chs.call("Echo.Echo", b"y" * 64) == b"y" * 64

        d = srv.slo_dump()
        assert d["enabled"] is True
        row = _tenant_row(d, "tenantA")
        assert row["p99_target_us"] == 2000
        assert row["avail_target"] == pytest.approx(0.99)
        assert row["fast"]["total"] >= 40
        assert row["slow"]["total"] >= 40
        assert row["latency"]["count"] >= 40
        assert row["breached"] is False
        assert row["attainment"] == pytest.approx(1.0)
        assert row["budget_remaining"] == pytest.approx(1.0)
        star = _tenant_row(d, "*")
        assert star["fast"]["total"] >= 10
        assert star["p99_target_us"] == 10000

        # /slo serves the same engine: same tenants, same counters.
        over_http = json.loads(_http(srv.port, "/slo"))
        assert over_http["enabled"] is True
        http_row = _tenant_row(over_http, "tenantA")
        assert http_row["fast"]["total"] >= row["fast"]["total"]

        # Every slo_* var is registered with HELP text (satellite b).
        prom = observe.Vars.prometheus()
        slo_vars = [n for n in observe.Vars.dump() if n.startswith("slo_")]
        assert "slo_observed_total" in slo_vars
        assert any(n.startswith("slo_tenant_tenantA_") for n in slo_vars)
        for name in slo_vars:
            # Latency-recorder families expose HELP on their summary
            # metric (<name>_latency_us), like every other recorder.
            assert (f"# HELP {name} " in prom
                    or f"# HELP {name}_latency_us " in prom), (
                f"no HELP for {name}")
        assert observe.Vars.read("slo_observed_total") >= 50

        # /fleet with no in-process registry: clean structured miss.
        miss = json.loads(_http(srv.port, "/fleet?service=fleet"))
        assert miss["error"] == "naming-miss"
        assert miss["tenants"] == []
    finally:
        srv.stop()
        observe.enable_slo(False)


# ----------------------------------------- flag-off: fresh process --


_FLAG_OFF_SCRIPT = r"""
import json, sys
from brpc_tpu.rpc import Channel, Server, observe

srv = Server()
srv.register_native_echo("Echo.Echo")
srv.set_slo("tenantA:p99_us=2000,avail=99.9;*:p99_us=10000")
srv.start(0)
ch = Channel("127.0.0.1:%d" % srv.port, timeout_ms=2000,
             qos_tenant="tenantA")
for _ in range(32):
    assert ch.call("Echo.Echo", b"p" * 32) == b"p" * 32

assert observe.slo_enabled() is False, "trpc_slo must default OFF"
d = srv.slo_dump()
assert d["enabled"] is False
for t in d["tenants"]:
    for w in ("fast", "slow"):
        assert t[w]["total"] == 0 and t[w]["bad"] == 0 and t[w]["err"] == 0
    assert t["breached"] is False
assert observe.slo_breach_total() == 0
frozen = {n: v for n, v in observe.Vars.dump().items()
          if n.startswith("slo_")}
for n, v in frozen.items():
    if isinstance(v, str):  # recorder families dump a JSON summary
        v = json.loads(v)
    if isinstance(v, dict):
        assert all(float(x) == 0 for x in v.values()), \
            "recorder moved with the flag off: %s=%r" % (n, v)
    else:
        assert float(v) == 0, "var moved with the flag off: %s=%r" % (n, v)
blob = observe.fleet_blob_decode(srv.fleet_blob())
for t in blob["tenants"]:
    assert t["slow_total"] == 0 and t["fast_total"] == 0
    assert t["digest"].count == 0, "digest fed with the flag off"
srv.stop()
print("FLAG_OFF_OK")
"""


def test_flag_off_invisible_in_fresh_process():
    """In a FRESH interpreter (flag at its compiled default), a server
    with an installed SLO spec serving real traffic moves NOTHING:
    every slo_* var frozen at 0, dump counters empty, no blob."""
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _FLAG_OFF_SCRIPT],
                          env=env, capture_output=True, timeout=120)
    assert proc.returncode == 0, (
        f"flag-off probe failed:\n{proc.stderr.decode(errors='replace')}")
    assert b"FLAG_OFF_OK" in proc.stdout


# ------------------------------------------- 3-process fleet oracle --


def _spawn_fleet_node(reg_addr: str, zone: str):
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["FLEET_REGISTRY"] = reg_addr
    env["FLEET_ZONE"] = zone
    env["FLEET_LEASE_MS"] = "400"
    env["FLEET_FAST_MS"] = "4000"
    env["FLEET_SLOW_MS"] = "16000"
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "tests", "_fleet_node.py")],
        env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE)
    deadline = time.time() + 120
    buf = b""
    while b"\n" not in buf:
        left = deadline - time.time()
        if left <= 0 or proc.poll() is not None:
            err = proc.communicate()[1].decode(errors="replace") \
                if proc.poll() is not None else "(still running)"
            proc.kill()
            raise AssertionError(f"fleet node gave no port; stderr:\n{err}")
        ready, _, _ = select.select([proc.stdout], [], [], min(left, 1.0))
        if not ready:
            continue
        chunk = os.read(proc.stdout.fileno(), 4096)
        if not chunk:
            raise AssertionError(
                "fleet node exited early: "
                + proc.communicate()[1].decode(errors="replace"))
        buf += chunk
    return proc, json.loads(buf.split(b"\n")[0])["port"]


def _stop_node(proc):
    try:
        proc.stdin.close()
        proc.wait(timeout=30)
    except Exception:
        proc.kill()


def test_three_process_fleet_matches_pooled_oracle(slo_flags, tmp_path):
    """The headline acceptance: three real node processes publish their
    digest+SLO blobs over naming://; the registry-side /fleet merge and
    the standalone fleet_top.py both reconstruct a fleet-wide tenantA
    p99 that agrees with a pooled single-digest oracle within the octave
    bound (ratio <= 2), with counts conserved across the merge."""
    set_flag("trpc_naming_lease_ms", "400")
    registry = Server()
    registry.enable_naming_registry()
    registry.start(0)
    reg_addr = f"127.0.0.1:{registry.port}"
    nodes = []
    try:
        for i in range(3):
            nodes.append(_spawn_fleet_node(reg_addr, f"z{i}"))

        # Skewed per-node traffic: the merged view must reflect ALL of
        # it, not any single node's recorder.
        per_node = (30, 20, 10)
        for (proc, port), n in zip(nodes, per_node):
            ch = Channel(f"127.0.0.1:{port}", timeout_ms=5000,
                         qos_tenant="tenantA")
            for k in range(n):
                assert ch.call("Echo.Echo", b"f" * (64 + k)) \
                    == b"f" * (64 + k)
            ch.close()
        want = sum(per_node)

        # Wait until every node's renew rounds have republished blobs
        # that cover all the traffic we just drove.
        nc = NamingClient(reg_addr)
        deadline = time.time() + 60
        blobs = []
        while time.time() < deadline:
            _, recs = nc.stats("fleet")
            blobs = [r.payload for r in recs if r.payload]
            if len(blobs) == 3:
                decoded = [observe.fleet_blob_decode(b) for b in blobs]
                rows = [t for d in decoded for t in d["tenants"]
                        if t["tenant"] == "tenantA"]
                if (len(rows) == 3
                        and sum(r["slow_total"] for r in rows) >= want):
                    break
            time.sleep(0.2)
        else:
            raise AssertionError(
                f"fleet blobs never covered the traffic: {len(blobs)} "
                f"published")

        # Pooled oracle: merge the three per-node digests ourselves and
        # rank-walk the pooled reservoir — the single-recorder ground
        # truth the octave bound is stated against.
        pooled = None
        oracle_count = 0
        for d in decoded:
            row = [t for t in d["tenants"] if t["tenant"] == "tenantA"][0]
            dg = row["digest"]
            oracle_count += dg.count
            pooled = dg if pooled is None \
                else observe.digest_merge(pooled, dg)
        assert oracle_count >= want
        oracle_p99 = observe.digest_percentile_us(pooled, 0.99)
        assert oracle_p99 > 0

        # The registry-side merge (/fleet body) against the oracle.
        view = observe.fleet_dump("fleet")
        assert view["publish_enabled"] in (True, False)
        assert len(view["nodes"]) == 3
        assert all(n["published"] for n in view["nodes"])
        frow = _tenant_row(view, "tenantA")
        assert frow["nodes"] == 3
        assert frow["p99_target_us"] == 2000
        assert frow["count"] >= want
        ratio = max(frow["p99_us"], oracle_p99) \
            / max(min(frow["p99_us"], oracle_p99), 1)
        assert ratio <= 2.0 + 1e-9, (
            f"merged p99 {frow['p99_us']}us vs pooled oracle "
            f"{oracle_p99}us breaks the octave bound")

        # Same body over the registry's /fleet builtin.
        http_view = json.loads(
            _http(registry.port, "/fleet?service=fleet"))
        assert _tenant_row(http_view, "tenantA")["nodes"] == 3

        # And the standalone CLI agrees (satellite: tools/fleet_top.py).
        env = dict(os.environ)
        env.setdefault("JAX_PLATFORMS", "cpu")
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        top = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "fleet_top.py"),
             reg_addr, "--service", "fleet", "--json"],
            env=env, capture_output=True, timeout=120)
        assert top.returncode == 0, top.stderr.decode(errors="replace")
        cli = json.loads(top.stdout.decode())
        crow = _tenant_row(cli, "tenantA")
        assert crow["nodes"] == 3
        cratio = max(crow["p99_us"], oracle_p99) \
            / max(min(crow["p99_us"], oracle_p99), 1)
        assert cratio <= 2.0 + 1e-9
        assert frow["breached_nodes"] == 0 and crow["breached_nodes"] == 0

        # Induced regression on ONE node (over its /faults builtin —
        # the node is a separate process): its published blob must flip
        # tenantA's burn-rate alert and the fleet merge must attribute
        # it — breached_nodes rises to exactly 1 in BOTH the /fleet
        # body and the standalone fleet_top merge.
        port0 = nodes[0][1]
        _http(port0, "/faults?server=svr_delay=1:50")
        bad = Channel(f"127.0.0.1:{port0}", timeout_ms=10000,
                      qos_tenant="tenantA")
        deadline = time.time() + 45
        breached_view = None
        while time.time() < deadline:
            bad.call("Echo.Echo", b"z" * 64)
            v = observe.fleet_dump("fleet")
            r = [t for t in v["tenants"] if t["tenant"] == "tenantA"]
            if r and r[0]["breached_nodes"] == 1:
                breached_view = v
                break
        bad.close()
        _http(port0, "/faults?server=")
        assert breached_view is not None, (
            "one-node latency regression never surfaced as "
            "breached_nodes=1 in the fleet merge")
        import fleet_top
        top_view = fleet_top.fleet_view(reg_addr, "fleet", 2000)
        trow = _tenant_row(top_view, "tenantA")
        assert trow["breached_nodes"] >= 1
    finally:
        for proc, _ in nodes:
            _stop_node(proc)
        registry.stop()


# --------------------------------------- burn-rate alert under chaos --


def test_burn_alert_fires_within_fast_window_and_clears(slo_flags):
    """Induced latency regression (svr_delay chaos) must flip tenantA's
    burn-rate alert within ONE fast window, count exactly one breach
    EDGE (slo_breach_total +1), and clear once the fault lifts and
    healthy traffic dilutes the fast window — with no extra edges from
    re-evaluation or from the clear."""
    fast_ms = 1500
    set_flag("trpc_slo_fast_window_ms", str(fast_ms))
    set_flag("trpc_slo_slow_window_ms", "6000")
    observe.enable_slo(True)
    srv = Server()
    srv.register_native_echo("Echo.Echo")
    srv.set_slo("tenantA:p99_us=2000,avail=99.0")
    srv.start(0)
    try:
        ch = Channel(f"127.0.0.1:{srv.port}", timeout_ms=5000,
                     qos_tenant="tenantA")
        for _ in range(40):
            assert ch.call("Echo.Echo", b"h" * 32) == b"h" * 32
        assert _tenant_row(srv.slo_dump(), "tenantA")["breached"] is False
        base_edges = observe.slo_breach_total()

        # Chaos: every dispatch now eats 50ms — far past the 2ms p99
        # target, so each response is "bad" and the burn climbs.
        srv.set_faults("svr_delay=1:50")
        t0 = time.monotonic()
        detect_ms = None
        while time.monotonic() - t0 < fast_ms / 1000 * 4:
            ch.call("Echo.Echo", b"b" * 32)
            row = _tenant_row(srv.slo_dump(), "tenantA")
            if row["breached"]:
                detect_ms = (time.monotonic() - t0) * 1000
                break
        assert detect_ms is not None, "burn alert never fired under chaos"
        assert detect_ms <= fast_ms, (
            f"breach detected in {detect_ms:.0f}ms — slower than one "
            f"fast window ({fast_ms}ms)")
        assert row["burn_fast"] >= 2.0
        assert observe.slo_breach_total() == base_edges + 1

        # More bad traffic re-evaluates but must NOT mint new edges.
        for _ in range(5):
            ch.call("Echo.Echo", b"b" * 32)
        assert observe.slo_breach_total() == base_edges + 1

        # Recovery: lift the fault, dilute the fast window.
        srv.set_faults("")
        deadline = time.time() + 20
        cleared = False
        while time.time() < deadline:
            ch.call("Echo.Echo", b"g" * 32)
            if not _tenant_row(srv.slo_dump(), "tenantA")["breached"]:
                cleared = True
                break
            time.sleep(0.05)
        assert cleared, "burn alert never cleared after recovery"
        assert observe.slo_breach_total() == base_edges + 1
    finally:
        srv.set_faults("")
        srv.stop()
        observe.enable_slo(False)
