"""Paged KV-block registry through the Python surface (ISSUE 11).

The C++ tier (cpp/net/kvstore.h) maps block_id -> {node, rkey, offset,
len, generation} under lease-based ownership; brpc_tpu/rpc/kv.py is the
decode/prefill client surface.  These tests pin the Python-visible
contract:

- publish/register/lookup/fetch roundtrip + typed kv errors;
- one-sided landing: a fetched block lands in the caller's RmaBuffer
  over shm with the rma vars moving (the transfer genuinely bypassed
  the frame plane);
- a GENUINE two-process prefill -> decode landing (separate publisher
  process, cross-pid region mapping);
- lookup-cache invalidation: a re-published block (bumped generation)
  is fetched transparently after exactly one stale round-trip;
- lease expiry mid-transfer (svr_delay outlasting the lease) answers
  kv-stale and admits NOTHING — no stale-generation admit;
- chaos composition: chunk drops in the prefill process fail block
  pulls whole-or-nothing while the decode node's token stream stays
  clean, and registry svr_delay slows lookups without touching it;
- flag validators + the serving node's fetch counters.
"""

import os
import subprocess
import sys
import time

import numpy as np
import pytest

from brpc_tpu.rpc import Channel, RmaBuffer, Server, kv, observe
from brpc_tpu.rpc import get_flag, set_flag

BB = 4 << 20  # block bytes used throughout


def _pattern(n: int, salt: int) -> np.ndarray:
    return ((np.arange(n, dtype=np.uint64) * 2654435761 + salt * 97)
            >> 13).astype(np.uint8)


def _vars(keys):
    v = observe.Vars.dump()
    return {k: v.get(k, 0) for k in keys}


@pytest.fixture()
def fresh_kv():
    kv.reset()
    yield
    kv.reset()


@pytest.fixture()
def node(fresh_kv):
    """One in-process prefill node: store + registry + token echo, with
    two published/registered blocks."""
    srv = Server()
    srv.enable_kv_store()
    srv.enable_kv_registry()
    srv.register_native_echo("Token.Step")
    srv.start(0)
    addr = f"127.0.0.1:{srv.port}"
    pages = RmaBuffer(2 * BB)
    view = np.frombuffer(pages.view, dtype=np.uint8)
    view[:BB] = _pattern(BB, 1)
    view[BB:] = _pattern(BB, 2)
    reg = kv.KvRegistryClient(Channel(addr, timeout_ms=10000),
                              owns_channel=True)
    metas = {}
    for i in (1, 2):
        m = kv.publish(i, pages, offset=(i - 1) * BB, length=BB,
                       lease_ms=600000, node=addr)
        reg.register(m, lease_ms=600000)
        metas[i] = m
    yield srv, addr, pages, reg, metas
    reg.close()
    pages.free()
    srv.stop()


def test_kv_publish_register_fetch_roundtrip(node):
    srv, addr, pages, reg, metas = node
    assert metas[1].generation == 1
    looked = reg.lookup(1)
    assert looked.generation == 1
    assert looked.length == BB
    assert looked.node == addr
    assert looked.lease_left_ms > 0
    assert kv.store_count() == 2
    assert kv.registry_count() == 2
    assert kv.store_bytes_used() == 2 * BB
    assert reg.renew(1, lease_ms=600000) == 1  # echoes the generation

    cli = kv.KvClient(addr, use_shm=True)
    try:
        data = cli.fetch(1)
        assert data == _pattern(BB, 1).tobytes()
        cli.fetch(1)  # second fetch rides the cached lookup
        assert cli.cache_hits == 1
        assert cli.cache_misses == 1
    finally:
        cli.close()


def test_kv_typed_errors(node):
    srv, addr, pages, reg, metas = node
    # Double-register of a live block: exclusive ownership.
    with pytest.raises(kv.KvExistsError):
        reg.register(metas[1], lease_ms=600000)
    with pytest.raises(kv.KvExistsError):
        kv.publish(1, pages, length=BB, node=addr)
    # Unknown block: miss, everywhere.
    with pytest.raises(kv.KvMissError):
        reg.lookup(99)
    with pytest.raises(kv.KvMissError):
        kv.withdraw(99)
    cli = kv.KvClient(addr, use_shm=True)
    try:
        with pytest.raises(kv.KvMissError):
            cli.fetch(99)
    finally:
        cli.close()


_RMA_KEYS = ("rma_tx_msgs", "rma_rx_msgs", "rma_rejected")


def test_kv_one_sided_landing_shm(node):
    """A fetched block lands in the caller's RmaBuffer over shm: the
    MB-scale payload rides the one-sided plane (rma vars move), and the
    landed bytes are exact."""
    srv, addr, pages, reg, metas = node
    cli = kv.KvClient(addr, use_shm=True)
    try:
        rma0 = _vars(_RMA_KEYS)
        with RmaBuffer(BB) as land:
            n = cli.fetch(2, resp_buf=land.view)
            assert n == BB
            got = np.frombuffer(land.view, dtype=np.uint8)
            assert np.array_equal(got, _pattern(BB, 2))
        rma1 = _vars(_RMA_KEYS)
        assert rma1["rma_rx_msgs"] > rma0["rma_rx_msgs"]
        assert rma1["rma_rejected"] == rma0["rma_rejected"]
    finally:
        cli.close()


def test_kv_lookup_cache_invalidation(node):
    """The block moves on (withdraw + republish + re-register = a NEWER
    generation with different bytes); the decode side's cached record is
    invalidated by exactly one stale answer and the retry lands the new
    generation's bytes."""
    srv, addr, pages, reg, metas = node
    cli = kv.KvClient(addr, use_shm=True)
    try:
        assert cli.fetch(1) == _pattern(BB, 1).tobytes()
        kv.withdraw(1)
        view = np.frombuffer(pages.view, dtype=np.uint8)
        view[:BB] = _pattern(BB, 7)
        m2 = kv.publish(1, pages, length=BB, lease_ms=600000, node=addr)
        assert m2.generation == 2
        reg.register(m2, lease_ms=600000)
        inval0 = cli.invalidations
        data = cli.fetch(1)  # stale -> invalidate -> re-lookup -> retry
        assert data == _pattern(BB, 7).tobytes()
        assert cli.invalidations == inval0 + 1
        assert cli.lookup(1).generation == 2
    finally:
        cli.close()


def test_kv_lease_expiry_mid_transfer_never_admits(fresh_kv):
    """A fetch issued while the lease is live but DISPATCHED after it
    lapses (svr_delay outlasting the lease) answers kv-stale: validity
    is decided at serve time, so nothing stale is ever admitted into
    the landing buffer."""
    srv = Server()
    srv.enable_kv_store()
    srv.enable_kv_registry()
    srv.start(0)
    addr = f"127.0.0.1:{srv.port}"
    pages = RmaBuffer(BB)
    np.frombuffer(pages.view, dtype=np.uint8)[:] = _pattern(BB, 3)
    try:
        m = kv.publish(31, pages, length=BB, lease_ms=250, node=addr)
        reg = kv.KvRegistryClient(Channel(addr, timeout_ms=10000),
                                  owns_channel=True)
        reg.register(m, lease_ms=600000)  # registry lease outlives store's
        cli = kv.KvClient(addr, use_shm=True, timeout_ms=10000)
        try:
            srv.set_faults("svr_delay=1:400")  # dispatch after the lease
            with RmaBuffer(BB) as land:
                view = np.frombuffer(land.view, dtype=np.uint8)
                view[:] = 0
                with pytest.raises(kv.KvError):
                    cli.fetch(31, resp_buf=land.view)
                assert not view.any(), "stale bytes admitted after expiry"
            stale = observe.Vars.dump().get("kv_stale_total", 0)
            assert stale >= 1
        finally:
            srv.set_faults("")
            cli.close()
            reg.close()
    finally:
        pages.free()
        srv.stop()


_PREFILL_CHILD = r"""
import sys
import numpy as np
from brpc_tpu.rpc import Channel, RmaBuffer, Server, kv, fault

srv = Server()
srv.enable_kv_store()
srv.enable_kv_registry()
srv.start(0)
addr = f"127.0.0.1:{srv.port}"
BB = 4 << 20
N = int(sys.argv[1]) if len(sys.argv) > 1 else 2
pages = RmaBuffer(N * BB)
view = np.frombuffer(pages.view, dtype=np.uint8)
for i in range(N):
    view[i * BB:(i + 1) * BB] = ((np.arange(BB, dtype=np.uint64)
                                  * 2654435761 + (i + 1) * 97)
                                 >> 13).astype(np.uint8)
reg = kv.KvRegistryClient(Channel(addr, timeout_ms=10000),
                          owns_channel=True)
for i in range(N):
    reg.register(kv.publish(1 + i, pages, offset=i * BB, length=BB,
                            lease_ms=600000, node=addr), lease_ms=600000)
print("PORT", srv.port, flush=True)
for line in sys.stdin:
    line = line.strip()
    if line.startswith("faults "):
        fault.set_schedule(line[len("faults "):])
        print("OK", flush=True)
    elif line == "clearfaults":
        fault.set_schedule("")
        print("OK", flush=True)
    elif line.startswith("svrfaults "):
        srv.set_faults(line[len("svrfaults "):])
        print("OK", flush=True)
    elif line == "clearsvrfaults":
        srv.set_faults("")
        print("OK", flush=True)
    elif line == "quit":
        break
reg.close()
srv.stop()
"""


def _spawn_prefill(blocks: int = 2):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))
    child = subprocess.Popen(
        [sys.executable, "-c", _PREFILL_CHILD, str(blocks)], env=env,
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
        bufsize=1)
    port = None
    for _ in range(200):
        line = child.stdout.readline()
        if line.startswith("PORT "):
            port = int(line.split()[1])
            break
    assert port is not None, "prefill child never printed PORT"
    return child, port


def _child_cmd(child, cmd: str) -> None:
    child.stdin.write(cmd + "\n")
    child.stdin.flush()
    assert child.stdout.readline().strip() == "OK"


def _stop_child(child) -> None:
    try:
        child.stdin.write("quit\n")
        child.stdin.flush()
        child.wait(timeout=10)
    except Exception:  # noqa: BLE001
        child.kill()


def test_kv_two_process_prefill_decode_landing(fresh_kv):
    """The real disaggregation data path: a SEPARATE prefill process
    publishes blocks out of its RmaBuffer; this (decode) process
    resolves them through the registry and lands them one-sided in its
    own RmaBuffer — cross-pid region mapping on both ends."""
    child, port = _spawn_prefill(blocks=2)
    try:
        cli = kv.KvClient(f"127.0.0.1:{port}", use_shm=True,
                          timeout_ms=30000)
        try:
            rma0 = _vars(_RMA_KEYS)
            with RmaBuffer(BB) as land:
                n = cli.fetch(1, resp_buf=land.view)
                assert n == BB
                got = np.frombuffer(land.view, dtype=np.uint8)
                assert np.array_equal(got, _pattern(BB, 1))
            assert cli.fetch(2) == _pattern(BB, 2).tobytes()
            rma1 = _vars(_RMA_KEYS)
            # This process RESOLVED remote-landed payloads (the decode
            # side of the one-sided path).
            assert rma1["rma_rx_msgs"] > rma0["rma_rx_msgs"]
        finally:
            cli.close()
    finally:
        _stop_child(child)


def test_kv_chaos_composition_whole_or_nothing(fresh_kv):
    """Chunk drops inside the PREFILL process + registry svr_delay,
    composed: every block pull either fails whole or lands byte-exact
    (never partial), the decode node's token stream stays clean (it is
    served by THIS process, untouched by the prefill's chaos), and
    lookups merely slow down under svr_delay.  Faults are bounded
    (max=) so the tail of the test proves recovery."""
    # Decode-side token server: the stream that must stay unaffected.
    tok_srv = Server()
    tok_srv.register_native_echo("Token.Step")
    tok_srv.start(0)
    tok_ch = Channel(f"127.0.0.1:{tok_srv.port}", timeout_ms=5000)
    child, port = _spawn_prefill(blocks=2)
    try:
        cli = kv.KvClient(f"127.0.0.1:{port}", use_shm=True,
                          timeout_ms=2000)
        try:
            assert cli.fetch(1) == _pattern(BB, 1).tobytes()  # clean warm
            # Chunk drops in the prefill process, bounded to 24 faults.
            _child_cmd(child, "faults seed=7;drop=0.6;max=24")
            ok = fail = 0
            tok_lat = []
            payload = b"t" * 1024
            for i in range(12):
                t0 = time.perf_counter()
                assert tok_ch.call("Token.Step", payload) == payload
                tok_lat.append(time.perf_counter() - t0)
                land = RmaBuffer(BB)
                try:
                    view = np.frombuffer(land.view, dtype=np.uint8)
                    view[:] = 0
                    n = cli.fetch(1 + (i % 2), resp_buf=land.view)
                    # Whole-or-nothing: a SUCCESS is always byte-exact.
                    assert n == BB
                    assert np.array_equal(view, _pattern(BB, 1 + (i % 2)))
                    ok += 1
                except (kv.KvError, Exception):  # noqa: BLE001
                    fail += 1  # failed WHOLE; buffer discarded below
                finally:
                    land.free()
            assert fail > 0, "chaos never fired"
            # The decode stream was untouched: every token call answered,
            # fast, while block pulls were failing around it.
            assert max(tok_lat) < 1.0
            _child_cmd(child, "clearfaults")
            # Recovery: the same cached records serve again (transport
            # faults never invalidated the generation).
            hits0 = cli.cache_hits
            assert cli.fetch(1) == _pattern(BB, 1).tobytes()
            assert cli.cache_hits == hits0 + 1

            # Registry svr_delay: lookups slow but succeed; the token
            # stream still does not care.
            _child_cmd(child, "svrfaults svr_delay=1:300")
            t0 = time.perf_counter()
            meta = cli.lookup(1, refresh=True)
            lookup_s = time.perf_counter() - t0
            assert meta.generation == 1
            assert lookup_s >= 0.25
            t0 = time.perf_counter()
            assert tok_ch.call("Token.Step", payload) == payload
            assert time.perf_counter() - t0 < 0.25
            _child_cmd(child, "clearsvrfaults")
        finally:
            cli.close()
    finally:
        _stop_child(child)
        tok_ch.close()
        tok_srv.stop()


def test_kv_fetch_reresolves_through_naming_when_node_gone(fresh_kv):
    """ISSUE 12 satellite: a KvClient given a naming view re-resolves a
    TRANSPORT-dead owner through it — the cached (dead) channel is
    dropped and the re-published block fetches from its new owner,
    instead of retrying the dead pid once and surfacing the error."""
    from brpc_tpu.rpc import naming

    naming.reset()
    # Registry host: kv registry + naming registry, survives the churn.
    hub = Server()
    hub.enable_kv_registry()
    hub.enable_naming_registry()
    hub.start(0)
    hub_addr = f"127.0.0.1:{hub.port}"

    # Node A: publishes block 7 and announces itself.
    node_a = Server()
    node_a.enable_kv_store()
    node_a.start(0)
    a_addr = f"127.0.0.1:{node_a.port}"
    node_a.announce(hub_addr, "kv")
    pages = RmaBuffer(1 << 20)
    np.frombuffer(pages.view, dtype=np.uint8)[:4096] = _pattern(4096, 9)
    reg = kv.KvRegistryClient(Channel(hub_addr, timeout_ms=5000),
                              owns_channel=True)
    meta_a = kv.publish(7, pages, length=4096, lease_ms=600000,
                        node=a_addr)
    reg.register(meta_a, lease_ms=600000)

    cli = kv.KvClient(hub_addr, use_shm=False, timeout_ms=2000,
                      naming_addr=hub_addr, naming_service="kv")
    try:
        assert cli.fetch(7) == _pattern(4096, 9).tobytes()  # warm cache

        # Node A dies abruptly (no graceful drain): its channel goes
        # transport-dead and its announcement withdraws with it.
        node_a.close()
        deadline = time.time() + 5
        while time.time() < deadline:
            if naming.local_member_count("kv") == 0:
                break
            time.sleep(0.02)
        assert naming.local_member_count("kv") == 0

        # Successor node B re-publishes block 7 (newer generation) and
        # announces; the registry record now points at B.
        node_b = Server()
        node_b.enable_kv_store()
        node_b.start(0)
        b_addr = f"127.0.0.1:{node_b.port}"
        node_b.announce(hub_addr, "kv")
        kv.withdraw(7)  # process-local store shared in this test
        meta_b = kv.publish(7, pages, length=4096, lease_ms=600000,
                            node=b_addr)
        assert meta_b.generation == 2
        reg.register(meta_b, lease_ms=600000)

        # THE regression: the fetch must drop the dead channel, consult
        # the naming view, re-resolve, and land on node B — one call,
        # no surfaced transport error.
        assert cli.fetch(7) == _pattern(4096, 9).tobytes()
        assert cli.node_reresolves == 1
        node_b.close()
    finally:
        cli.close()
        reg.close()
        pages.free()
        hub.close()
        naming.reset()


def test_kv_flag_validators():
    old_lease = get_flag("trpc_kv_lease_ms")
    old_bytes = get_flag("trpc_kv_store_bytes")
    try:
        set_flag("trpc_kv_lease_ms", "5000")
        assert get_flag("trpc_kv_lease_ms") == "5000"
        with pytest.raises(Exception):
            set_flag("trpc_kv_lease_ms", "10")  # below the 50ms floor
        with pytest.raises(Exception):
            set_flag("trpc_kv_lease_ms", "garbage")
        set_flag("trpc_kv_store_bytes", str(64 << 20))
        with pytest.raises(Exception):
            set_flag("trpc_kv_store_bytes", "1024")  # below 1MB
    finally:
        set_flag("trpc_kv_lease_ms", old_lease)
        set_flag("trpc_kv_store_bytes", old_bytes)


def test_kv_served_fetch_moves_fetch_counters(node):
    """A served fetch of block 1 is counted on the serving node: one
    more `kv_fetch_total` and the block's bytes more `kv_fetch_bytes`."""
    srv, addr, pages, reg, metas = node
    keys = ("kv_fetch_total", "kv_fetch_bytes")
    before = _vars(keys)
    cli = kv.KvClient(addr, use_shm=True)
    try:
        assert cli.fetch(1) == _pattern(BB, 1).tobytes()
    finally:
        cli.close()
    after = _vars(keys)
    assert after["kv_fetch_total"] - before["kv_fetch_total"] == 1
    assert after["kv_fetch_bytes"] - before["kv_fetch_bytes"] == BB
