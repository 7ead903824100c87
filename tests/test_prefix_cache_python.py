"""Fleet-wide content-addressed prefix cache through the Python surface
(ISSUE 17).

The C++ tier grows content addressing (128-bit bytes+token-span hash as
an alternate registry key with replica sets), longest-prefix trie match
(KvReg.Match), a two-tier hot/cold store, and cache-aware routing
(c_hash_bl prefix-hash hint).  These tests pin the Python-visible
contract:

- GENUINE two-process dedup: two separate publisher processes offering
  the same prompt prefix collapse to ONE registry record per chain key
  with a two-entry replica set (the dedup counter moves);
- cache-aware routing roundtrip: the deepest matched replica's node is
  the hint, c_hash_bl honors it (hit), an absent member degrades to the
  ring walk (miss) with the call still succeeding;
- chaos composition: svr_delay on the registry slows match without
  breaking it while chunk drops on one replica fail its block pulls
  whole-or-nothing and the SECOND replica serves byte-exact;
- the node-channel pool stays bounded under membership churn
  (channels for departed nodes evict through the naming view);
- flag validators + prefix-stable chain keys.
"""

import os
import subprocess
import sys
import time

import numpy as np
import pytest

from brpc_tpu.rpc import Channel, Server, kv
from brpc_tpu.rpc import get_flag, set_flag
from brpc_tpu.rpc.client import ClusterChannel, lb_hint_counters

BT = 128          # tokens per prefix block (the flag default)
PB = 256 << 10    # bytes per prefix block in these tests


def _tokens(nblocks: int) -> list[int]:
    return [7000 + t for t in range(nblocks * BT)]


def _block_bytes(depth: int) -> bytes:
    return (((np.arange(PB, dtype=np.uint64) * 2654435761
              + (depth + 1) * 97) >> 13).astype(np.uint8)).tobytes()


@pytest.fixture()
def fresh_kv():
    kv.reset()
    yield
    kv.reset()


# A publisher process: local two-tier store + Token.Step echo; publishes
# `nblocks` prefix blocks for the SHARED deterministic prompt prefix and
# registers every one with the hub registry (argv[1]).  Because the
# bytes and token spans are derived from depth alone, every publisher
# offers the SAME content hashes — the fleet-wide dedup scenario.
_PUBLISHER_CHILD = r"""
import sys
import numpy as np
from brpc_tpu.rpc import Channel, Server, kv, fault

hub_addr = sys.argv[1]
nblocks = int(sys.argv[2])
BT = 128
PB = 256 << 10

srv = Server()
srv.enable_kv_store()
srv.register_native_echo("Token.Step")
srv.start(0)
addr = f"127.0.0.1:{srv.port}"

tokens = [7000 + t for t in range(nblocks * BT)]
keys = kv.prefix_chain(tokens, BT)
assert len(keys) == nblocks
reg = kv.KvRegistryClient(Channel(hub_addr, timeout_ms=10000),
                          owns_channel=True)
for d, key in enumerate(keys):
    data = (((np.arange(PB, dtype=np.uint64) * 2654435761
              + (d + 1) * 97) >> 13).astype(np.uint8)).tobytes()
    span = tokens[d * BT:(d + 1) * BT]
    meta, fresh = kv.prefix_publish(key, d, data, span,
                                    lease_ms=600000, node=addr)
    assert fresh
    reg.put_prefix(meta, lease_ms=600000)
print("PORT", srv.port, flush=True)
for line in sys.stdin:
    line = line.strip()
    if line.startswith("faults "):
        fault.set_schedule(line[len("faults "):])
        print("OK", flush=True)
    elif line == "clearfaults":
        fault.set_schedule("")
        print("OK", flush=True)
    elif line == "quit":
        break
reg.close()
srv.stop()
"""


def _spawn_publisher(hub_addr: str, nblocks: int = 2):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))
    child = subprocess.Popen(
        [sys.executable, "-c", _PUBLISHER_CHILD, hub_addr, str(nblocks)],
        env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
        bufsize=1)
    port = None
    for _ in range(200):
        line = child.stdout.readline()
        if line.startswith("PORT "):
            port = int(line.split()[1])
            break
    assert port is not None, "publisher child never printed PORT"
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


@pytest.fixture()
def hub(fresh_kv):
    """The fleet registry, hosted by THIS process (so the native dedup
    counter and registry accessors are directly observable)."""
    srv = Server()
    srv.enable_kv_registry()
    srv.register_native_echo("Token.Step")
    srv.start(0)
    yield srv, f"127.0.0.1:{srv.port}"
    srv.stop()


def test_prefix_two_publisher_dedup_replica_sets(hub):
    """Two SEPARATE publisher processes offering the same prompt prefix:
    one registry record per chain key, a two-entry replica set each, and
    the dedup counter counts the collapsed offers."""
    hub_srv, hub_addr = hub
    dedup0 = kv.prefix_counters()["dedup"]
    child_a, port_a = _spawn_publisher(hub_addr, nblocks=2)
    child_b, port_b = _spawn_publisher(hub_addr, nblocks=2)
    try:
        assert kv.prefix_registry_count() == 2       # chain keys, not offers
        assert kv.prefix_registry_replicas() == 4    # 2 blocks x 2 homes
        assert kv.prefix_counters()["dedup"] == dedup0 + 2

        cli = kv.KvClient(hub_addr, use_shm=False, timeout_ms=10000)
        try:
            groups = cli.match_prefix(_tokens(2), BT)
            assert len(groups) == 2
            for depth, group in enumerate(groups):
                assert len(group) == 2
                homes = {r.node for r in group}
                assert homes == {f"127.0.0.1:{port_a}",
                                 f"127.0.0.1:{port_b}"}
                hashes = {r.hash for r in group}
                assert len(hashes) == 1  # content-addressed: one hash
                assert all(r.depth == depth for r in group)
                assert all(r.length == PB for r in group)
                assert all(r.lease_left_ms > 0 for r in group)
            # A 3-block prompt sharing the 2-block prefix still matches
            # depth 2 — longest CACHED prefix, not exact-length.
            assert len(cli.match_prefix(_tokens(3), BT)) == 2
            blocks = cli.fetch_prefix(_tokens(2), BT)
            assert [b for b in blocks] == [_block_bytes(0), _block_bytes(1)]
        finally:
            cli.close()
    finally:
        _stop_child(child_a)
        _stop_child(child_b)


def test_prefix_cache_aware_routing_roundtrip(hub):
    """match -> hint -> hinted cluster call: the deepest replica's node
    is the hint and c_hash_bl honors it; a hint naming a departed member
    degrades to the ring walk with the call still succeeding."""
    hub_srv, hub_addr = hub
    child, port = _spawn_publisher(hub_addr, nblocks=2)
    pub_addr = f"127.0.0.1:{port}"
    try:
        cli = kv.KvClient(hub_addr, use_shm=False, timeout_ms=10000)
        ch = ClusterChannel(f"list://{pub_addr},{hub_addr}", "c_hash_bl",
                            timeout_ms=10000)
        try:
            groups = cli.match_prefix(_tokens(2), BT)
            hint = kv.KvClient.prefix_hint(groups)
            assert hint == pub_addr  # deepest matched block's home
            assert kv.KvClient.prefix_hint([]) == ""  # cold prompt: no hint

            hit0, veto0, miss0 = lb_hint_counters()
            assert ch.call("Token.Step", b"decode", hint=hint) == b"decode"
            hit1, veto1, miss1 = lb_hint_counters()
            assert hit1 == hit0 + 1
            assert (veto1, miss1) == (veto0, miss0)
            # The hinted member drained away: miss, ring walk answers.
            assert ch.call("Token.Step", b"decode",
                           hint="127.0.0.1:1") == b"decode"
            assert lb_hint_counters()[2] == miss0 + 1
            # No hint: the plain path, counters untouched.
            assert ch.call("Token.Step", b"decode") == b"decode"
            assert lb_hint_counters() == (hit1, veto1, miss0 + 1)
        finally:
            ch.close()
            cli.close()
    finally:
        _stop_child(child)


def test_prefix_chaos_second_replica_serves_whole_or_nothing(hub):
    """Chunk drops on replica A + svr_delay on the registry, composed:
    A's block pulls fail WHOLE (nothing partial ever admitted), replica
    B serves every block byte-exact in the same fetch_prefix call, and
    match merely slows down under the registry fault."""
    hub_srv, hub_addr = hub
    child_a, port_a = _spawn_publisher(hub_addr, nblocks=2)  # first home
    child_b, port_b = _spawn_publisher(hub_addr, nblocks=2)  # second home
    try:
        cli = kv.KvClient(hub_addr, use_shm=False, timeout_ms=2000)
        try:
            want = [_block_bytes(0), _block_bytes(1)]
            assert cli.fetch_prefix(_tokens(2), BT) == want  # clean warm
            # Every chunk out of replica A now drops (bounded): its
            # pulls fail whole-or-nothing and failover lands on B.
            _child_cmd(child_a, "faults seed=7;drop=1.0;max=40")
            blocks = cli.fetch_prefix(_tokens(2), BT)
            assert blocks == want, "failover block not byte-exact"
            # Registry svr_delay composes on top: match slows, still
            # answers, and the replica-set contents are unchanged.
            hub_srv.set_faults("svr_delay=1:300")
            t0 = time.perf_counter()
            groups = cli.match_prefix(_tokens(2), BT)
            assert time.perf_counter() - t0 >= 0.25
            assert [len(g) for g in groups] == [2, 2]
            hub_srv.set_faults("")
            _child_cmd(child_a, "clearfaults")
            # Recovery: replica A serves again (transport faults never
            # invalidated its generation).
            assert cli.fetch_prefix(_tokens(2), BT) == want
        finally:
            cli.close()
    finally:
        _stop_child(child_a)
        _stop_child(child_b)


def test_kv_client_channel_pool_bounded_under_churn(fresh_kv):
    """ISSUE 17 satellite: the per-node channel pool prunes channels for
    nodes that LEFT the naming view instead of growing with every node
    that ever served a block."""
    from brpc_tpu.rpc import naming

    naming.reset()
    hub = Server()
    hub.enable_kv_registry()
    hub.enable_naming_registry()
    hub.start(0)
    hub_addr = f"127.0.0.1:{hub.port}"

    nodes = []
    for _ in range(5):
        srv = Server()
        srv.register_native_echo("Token.Step")
        srv.start(0)
        srv.announce(hub_addr, "kv")
        nodes.append(srv)
    cli = kv.KvClient(hub_addr, use_shm=False, timeout_ms=2000,
                      naming_addr=hub_addr, naming_service="kv")
    try:
        for srv in nodes[:4]:
            cli._node_channel(f"127.0.0.1:{srv.port}")
        assert len(cli._node_chs) == 4
        # Three nodes die; their announcements withdraw with them.
        for srv in nodes[:3]:
            srv.close()
        deadline = time.time() + 5
        while time.time() < deadline:
            if naming.local_member_count("kv") == 2:
                break
            time.sleep(0.02)
        assert naming.local_member_count("kv") == 2
        # The next NEW channel triggers the prune: the three dead nodes'
        # channels evict, the pool ends at live-members size.
        cli._node_channel(f"127.0.0.1:{nodes[4].port}")
        assert cli.channels_evicted == 3
        assert set(cli._node_chs) == {f"127.0.0.1:{nodes[3].port}",
                                      f"127.0.0.1:{nodes[4].port}"}
    finally:
        cli.close()
        for srv in nodes[3:]:
            srv.close()
        hub.close()
        naming.reset()


def test_prefix_flag_validators_and_chain_keys(fresh_kv):
    old_hot = get_flag("trpc_kv_prefix_hot_bytes")
    old_bt = get_flag("trpc_kv_prefix_block_tokens")
    try:
        set_flag("trpc_kv_prefix_hot_bytes", str(8 << 20))
        assert get_flag("trpc_kv_prefix_hot_bytes") == str(8 << 20)
        with pytest.raises(Exception):
            set_flag("trpc_kv_prefix_hot_bytes", "1024")  # below 1MB
        with pytest.raises(Exception):
            set_flag("trpc_kv_prefix_hot_bytes", "garbage")
        set_flag("trpc_kv_prefix_block_tokens", "64")
        with pytest.raises(Exception):
            set_flag("trpc_kv_prefix_block_tokens", "0")
        with pytest.raises(Exception):
            set_flag("trpc_kv_prefix_block_tokens", "100000")
    finally:
        set_flag("trpc_kv_prefix_hot_bytes", old_hot)
        set_flag("trpc_kv_prefix_block_tokens", old_bt)
    # Chain keys are prefix-stable from Python too (the decode side
    # derives them from token ids alone).
    keys4 = kv.prefix_chain(_tokens(4), BT)
    keys2 = kv.prefix_chain(_tokens(2), BT)
    assert len(keys4) == 4 and keys4[:2] == keys2
    assert kv.prefix_chain(_tokens(1)[:BT - 1], BT) == []
