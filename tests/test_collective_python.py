"""Collective transfer schedules — the Python contract (ISSUE 13).

Covers:
- in-process member fleets: all_gather / reduce_scatter / all_to_all
  byte-exactness over shm rings with one-sided landings;
- reshard planning minimality (moved < naive whenever the shardings
  overlap; identity moves nothing) locally AND over the Reshard.Plan
  wire;
- Reshard.Execute moving KV-block-addressed shards on a member fleet
  (publish → execute → fetch-verify the re-published blocks);
- a GENUINE multi-process all-gather: N separate member processes
  rendezvous through a naming registry, derive identical rank orders,
  and byte-verify every gathered shard;
- chaos composition: chunk drops fail runs whole-or-nothing (no member
  ever reports success with torn bytes), sessions quiesce, and the same
  fleet recovers byte-exact after the faults clear;
- observability: coll_* vars moving, and the per-op step latency
  recorders registered with HELP.
"""

import json
import os
import struct
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from brpc_tpu.rpc import (Channel, Server, collective, fault, get_flag,
                          observe, rma, set_flag)


def _assert_sessions_drained(timeout_s: float = 5.0) -> None:
    """sessions_live is process-global: another test's fleet on this
    worker may still be finishing its last completions.  Waits up to
    `timeout_s` for the count to drain, then asserts it is exactly 0."""
    deadline = time.time() + timeout_s
    while collective.sessions_live() != 0 and time.time() < deadline:
        time.sleep(0.05)
    assert collective.sessions_live() == 0


class Fleet:
    """N in-process members: servers with the collective handlers and a
    Group per rank."""

    def __init__(self, n, timeout_ms=20000, enable_kv=False):
        self.servers = []
        for _ in range(n):
            s = Server()
            s.enable_collective()
            if enable_kv:
                s.enable_kv_store()
            s.start(0)
            self.servers.append(s)
        self.members = [f"127.0.0.1:{s.port}" for s in self.servers]
        self.groups = [collective.Group(self.members, r,
                                        timeout_ms=timeout_ms)
                       for r in range(n)]
        self.n = n
        self.seq = 0

    def run_all(self, fn):
        """fn(group, rank, seq) on every member concurrently; returns
        the per-rank exception list."""
        self.seq += 1
        errs = [None] * self.n

        def go(r):
            try:
                fn(self.groups[r], r, self.seq)
            except Exception as e:  # noqa: BLE001 — collected for asserts
                errs[r] = e

        threads = [threading.Thread(target=go, args=(r,))
                   for r in range(self.n)]
        for t in threads:
            t.start()
        for r, t in enumerate(threads):
            t.join(150)
            if t.is_alive():
                # A wedged member must surface as an ERROR, never as a
                # silent success (errs[r] left None would let the torn-
                # shard checks read a buffer a live run still owns).
                errs[r] = errs[r] or TimeoutError(
                    f"member {r} still running after join budget")
        return errs

    def close(self):
        for g in self.groups:
            g.close()
        for s in self.servers:
            s.stop()


def _view(buf):
    return np.frombuffer(memoryview(buf.view), dtype=np.uint8)


def test_all_gather_byte_exact_and_one_sided():
    # Above the stripe threshold, so the pulls' direct landings resolve
    # as one-sided rma messages (the rx assertion below).
    n, shard = 3, 4 << 20
    fleet = Fleet(n)
    try:
        sends = [rma.RmaBuffer(shard) for _ in range(n)]
        recvs = [rma.RmaBuffer(n * shard) for _ in range(n)]
        for r in range(n):
            _view(sends[r])[:] = (np.arange(shard) * (r + 3)) % 251
        rx0 = observe.Vars.dump().get("rma_rx_msgs", 0)
        errs = fleet.run_all(
            lambda g, r, seq: g.all_gather(sends[r], recvs[r],
                                           shard_bytes=shard, run_seq=seq))
        assert not any(errs), errs
        for r in range(n):
            got = _view(recvs[r])
            for src in range(n):
                want = ((np.arange(shard) * (src + 3)) % 251).astype(np.uint8)
                assert np.array_equal(got[src * shard:(src + 1) * shard],
                                      want), f"rank {r} shard {src} torn"
        # The MB-scale pulls rode the one-sided plane (direct landings
        # resolve as rma messages), not the frame path.
        assert observe.Vars.dump().get("rma_rx_msgs", 0) > rx0
        _assert_sessions_drained()
    finally:
        fleet.close()


def test_reduce_scatter_u32_sums():
    n, shard = 3, 512 << 10
    fleet = Fleet(n)
    try:
        sends = [rma.RmaBuffer(n * shard) for _ in range(n)]
        recvs = [rma.RmaBuffer(shard) for _ in range(n)]
        base = np.arange(n * shard // 4, dtype=np.uint32)
        for r in range(n):
            np.frombuffer(memoryview(sends[r].view),
                          dtype=np.uint32)[:] = base + r
        errs = fleet.run_all(
            lambda g, r, seq: g.reduce_scatter(sends[r], recvs[r],
                                               shard_bytes=shard,
                                               run_seq=seq))
        assert not any(errs), errs
        w = shard // 4
        for r in range(n):
            got = np.frombuffer(memoryview(recvs[r].view), dtype=np.uint32)
            want = sum((base[r * w:(r + 1) * w] + k)
                       for k in range(n)).astype(np.uint32)
            assert np.array_equal(got, want), f"rank {r} reduction wrong"
    finally:
        fleet.close()


def test_all_to_all_transposes_blocks():
    n, shard = 3, 256 << 10
    fleet = Fleet(n)
    try:
        sends = [rma.RmaBuffer(n * shard) for _ in range(n)]
        recvs = [rma.RmaBuffer(n * shard) for _ in range(n)]
        for r in range(n):
            v = _view(sends[r])
            for d in range(n):
                v[d * shard:(d + 1) * shard] = (1 + r * 16 + d) % 251
        errs = fleet.run_all(
            lambda g, r, seq: g.all_to_all(sends[r], recvs[r], run_seq=seq))
        assert not any(errs), errs
        for d in range(n):
            got = _view(recvs[d])
            for src in range(n):
                assert np.all(got[src * shard:(src + 1) * shard]
                              == (1 + src * 16 + d) % 251)
    finally:
        fleet.close()


def test_reshard_plan_minimality_local_and_wire():
    total = 1 << 20
    q = total // 4
    src = [(r, r * q, q) for r in range(4)]
    shift = 64 << 10
    dst = [(0, 0, q + shift), (1, q + shift, q), (2, 2 * q + shift, q),
           (3, 3 * q + shift, q - shift)]
    plan = collective.plan_reshard_bytes(src, dst, total, 4)
    assert plan["naive_bytes"] == 3 * total
    assert plan["bytes_moved"] == 3 * shift
    assert plan["bytes_moved"] < plan["naive_bytes"]
    assert plan["bytes_moved"] + plan["bytes_reused"] == total
    # Identity: nothing moves, everything reuses.
    ident = collective.plan_reshard_bytes(src, src, total, 4)
    assert ident["bytes_moved"] == 0
    assert ident["bytes_reused"] == total
    # The same answer over the wire (Reshard.Plan on any coll server).
    srv = Server()
    srv.enable_collective()
    srv.start(0)
    try:
        ch = Channel(f"127.0.0.1:{srv.port}", timeout_ms=10000)
        rc = collective.ReshardClient(ch)
        wire_plan = rc.plan(src, dst, total, 4)
        assert wire_plan["bytes_moved"] == plan["bytes_moved"]
        assert wire_plan["bytes_reused"] == plan["bytes_reused"]
        assert wire_plan["naive_bytes"] == plan["naive_bytes"]
        assert wire_plan["transfers"] > 0
        ch.close()
    finally:
        srv.stop()


def test_reshard_execute_moves_kv_blocks():
    """The service form: each member's source shard is a published KV
    block; Reshard.Execute runs the planned schedule on the fleet and
    re-publishes the target layout as new blocks — verified byte-exact
    through Kv.Fetch."""
    from brpc_tpu.rpc import kv

    n = 3
    total = 3 << 20
    third = total // n
    src = [(0, 0, third), (1, third, third), (2, 2 * third, third)]
    dst = [(0, 0, third // 2), (1, third // 2, third),
           (2, third // 2 + third, total - third - third // 2)]
    fleet = Fleet(n, enable_kv=True)
    glob = (np.arange(total) % 249).astype(np.uint8)
    srcbufs = []
    try:
        for r, (rk, off, ln) in enumerate(src):
            b = rma.RmaBuffer(ln)
            _view(b)[:] = glob[off:off + ln]
            kv.publish(500 + r, b, node=fleet.members[r])
            srcbufs.append(b)
        chs = [Channel(m, timeout_ms=30000) for m in fleet.members]
        results = [None] * n
        errs = [None] * n

        def exec_one(r):
            try:
                c = collective.ReshardClient(chs[r])
                req = collective.ReshardClient.execute_request(
                    91, fleet.members, r, src, dst, total, 500, 600)
                results[r] = c.execute(req, timeout_ms=60000)
            except Exception as e:  # noqa: BLE001
                errs[r] = e

        threads = [threading.Thread(target=exec_one, args=(r,))
                   for r in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(90)
        assert not any(errs), errs
        kv_wire = struct.Struct("<QQQQQq64s")
        for r, (rk, off, ln) in enumerate(dst):
            dst_len, gen = results[r]
            assert dst_len == ln
            req = kv_wire.pack(600 + r, gen, 0, 0, 0, 0, b"")
            data = chs[r].call("Kv.Fetch", req, timeout_ms=30000)
            assert data == glob[off:off + ln].tobytes(), \
                f"rank {r} resharded block torn"
        for ch in chs:
            ch.close()
    finally:
        kv.reset()
        fleet.close()


def test_chaos_chunk_faults_whole_or_nothing_and_scavenge():
    """Chunk drops fail runs WHOLE — a member that reports success must
    hold exact bytes (zero torn shards) — sessions quiesce, leaked
    window spans scavenge, and the fleet recovers byte-exact."""
    n, shard = 3, 2 << 20
    fleet = Fleet(n, timeout_ms=6000)
    try:
        sends = [rma.RmaBuffer(shard) for _ in range(n)]
        recvs = [rma.RmaBuffer(n * shard) for _ in range(n)]
        for r in range(n):
            _view(sends[r])[:] = (np.arange(shard) + r * 11) % 241

        def ag(g, r, seq):
            g.all_gather(sends[r], recvs[r], shard_bytes=shard,
                         run_seq=seq)

        assert not any(fleet.run_all(ag))  # clean baseline
        set_flag("trpc_rma_span_scavenge_ms", "200")
        fault.set_schedule("seed=41;drop=0.5;max=64")
        try:
            for r in range(n):
                _view(recvs[r])[:] = 0  # poison: torn admits detectable
            errs = fleet.run_all(ag)
        finally:
            fault.set_schedule("")
        assert any(errs), "chaos run should have failed somewhere"
        for r in range(n):
            if errs[r] is None:
                got = _view(recvs[r])
                for src in range(n):
                    want = ((np.arange(shard) + src * 11)
                            % 241).astype(np.uint8)
                    assert np.array_equal(
                        got[src * shard:(src + 1) * shard], want), \
                        f"rank {r} reported success with torn shard {src}"
        _assert_sessions_drained()
        # Scavenge any span whose control frame the chaos dropped; after
        # two aged passes the windows must be clean.
        collective.rma_scavenge()
        time.sleep(0.3)
        collective.rma_scavenge()
        lib = observe.load_library()
        assert int(lib.trpc_rma_spans_in_use()) == 0
        # Recovery on the SAME fleet, byte-exact.
        errs = fleet.run_all(ag)
        assert not any(errs), errs
        for r in range(n):
            got = _view(recvs[r])
            for src in range(n):
                want = ((np.arange(shard) + src * 11) % 241).astype(np.uint8)
                assert np.array_equal(got[src * shard:(src + 1) * shard],
                                      want)
    finally:
        fleet.close()


def test_coll_step_vars():
    n, shard = 3, 256 << 10
    fleet = Fleet(n)
    try:
        sends = [rma.RmaBuffer(shard) for _ in range(n)]
        recvs = [rma.RmaBuffer(n * shard) for _ in range(n)]
        v0 = observe.Vars.dump()
        errs = fleet.run_all(
            lambda g, r, seq: g.all_gather(sends[r], recvs[r],
                                           shard_bytes=shard, run_seq=seq))
        assert not any(errs), errs
        v1 = observe.Vars.dump()
        assert v1.get("coll_runs_total", 0) >= v0.get("coll_runs_total", 0) + n
        assert v1.get("coll_steps_total", 0) >= v0.get("coll_steps_total",
                                                       0) + n * (n - 1)
        assert v1.get("coll_puts_total", 0) > v0.get("coll_puts_total", 0)
        # Per-op latency recorder registered and fed (HELP'd Prometheus
        # series — lint guards the HELP, this guards the feed).
        stats = observe.Latency.read("coll_step_all_gather")
        assert stats.count > 0
    finally:
        fleet.close()


_CHILD_SRC = r"""
import sys, time
import numpy as np
from brpc_tpu.rpc import Server, collective, rma

reg_addr, n, shard = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
salt = int(sys.argv[4])
srv = Server(); srv.enable_collective(); srv.start(0)
srv.announce(reg_addr, "coll_mp", zone="z1")
self_addr = f"127.0.0.1:{srv.port}"
# Rendezvous: wait until every member announced, then snapshot.
from brpc_tpu.rpc import naming
nc = naming.NamingClient(reg_addr, timeout_ms=5000)
deadline = time.time() + 30
while True:
    _v, members = nc.resolve("coll_mp")
    if len(members) >= n:
        break
    if time.time() > deadline:
        print("RENDEZVOUS_TIMEOUT", flush=True); sys.exit(2)
    time.sleep(0.05)
g = collective.Group(naming_url=f"naming://{reg_addr}/coll_mp",
                     self_addr=self_addr, timeout_ms=30000)
send = rma.RmaBuffer(shard); recv = rma.RmaBuffer(n * shard)
np.frombuffer(memoryview(send.view), dtype=np.uint8)[:] = \
    (np.arange(shard) + (g.rank + 1) * salt) % 251
g.all_gather(send, recv, shard_bytes=shard, run_seq=1)
got = np.frombuffer(memoryview(recv.view), dtype=np.uint8)
for src in range(n):
    want = ((np.arange(shard) + (src + 1) * salt) % 251).astype(np.uint8)
    if not np.array_equal(got[src*shard:(src+1)*shard], want):
        print(f"MISMATCH rank={g.rank} src={src}", flush=True); sys.exit(3)
print(f"OK rank={g.rank}", flush=True)
g.close(); srv.stop()
"""


def test_multi_process_all_gather_over_naming():
    """The real thing: N SEPARATE member processes rendezvous through a
    naming registry, snapshot identical rank orders, and all-gather 4MB
    shards across genuine process boundaries (cross-pid shm region
    mapping) with full byte verification in every member."""
    n, shard, salt = 3, 4 << 20, 13
    registry = Server()
    registry.enable_naming_registry()
    registry.start(0)
    reg_addr = f"127.0.0.1:{registry.port}"
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    env.setdefault("JAX_PLATFORMS", "cpu")
    procs = [subprocess.Popen(
        [sys.executable, "-c", _CHILD_SRC, reg_addr, str(n), str(shard),
         str(salt)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True) for _ in range(n)]
    try:
        outs = []
        for p in procs:
            out, err = p.communicate(timeout=120)
            outs.append((p.returncode, out, err))
        oks = [o for rc, o, _ in outs for line in [o]
               if rc == 0 and "OK rank=" in line]
        assert len(oks) == n, f"multi-process all_gather failed: {outs}"
        ranks = sorted(int(o.split("OK rank=")[1].split()[0]) for o in oks)
        assert ranks == list(range(n)), outs
    finally:
        for p in procs:
            p.kill()
        registry.stop()


def test_error_mapping_and_mismatch():
    n = 2
    fleet = Fleet(n, timeout_ms=3000)
    try:
        small = rma.RmaBuffer(1 << 16)
        # recv too small for the plan: mismatch before any byte moves.
        with pytest.raises(collective.CollMismatchError):
            fleet.groups[0].all_gather(small, small,
                                       shard_bytes=1 << 16, run_seq=1)
        _assert_sessions_drained()
    finally:
        fleet.close()


# -- overlap-aware collectives (ISSUE 18) ----------------------------------


def test_overlap_flag_validation_and_ready_map_contract():
    """The runtime knobs reject garbage loudly and the ReadyMap argument
    contract (chunk alignment, bounds, idempotent stamps, close
    quiescence) raises instead of corrupting."""
    buf = rma.RmaBuffer(256 << 10)
    try:
        live0 = collective.ready_maps_live()
        m = collective.ReadyMap(buf, granularity=64 << 10)
        assert m.handle != 0
        # ReadyMap creation registered the collective runtime — the
        # flags exist from here on.
        with pytest.raises(ValueError):
            set_flag("trpc_coll_overlap", "banana")
        with pytest.raises(ValueError):
            set_flag("trpc_coll_ready_granularity_bytes", "1024")  # < 4KB
        with pytest.raises(ValueError):
            set_flag("trpc_coll_ready_granularity_bytes", str(1 << 40))
        assert get_flag("trpc_coll_overlap") == "false"  # default off
        with pytest.raises(ValueError):
            m.stamp(1, 64 << 10)  # misaligned offset
        with pytest.raises(ValueError):
            m.stamp(0, 512 << 10)  # beyond the buffer end
        with pytest.raises(ValueError):
            m.stamp(0, (64 << 10) + 1)  # not a chunk multiple
        m.stamp(0, 64 << 10)
        m.stamp(0, 64 << 10)  # monotonic: restamp is a no-op
        m.stamp(64 << 10, 192 << 10)  # reaches the buffer end
        assert collective.ready_maps_live() == live0 + 1
        m.close()
        assert m.handle == 0
        assert collective.ready_maps_live() == live0
    finally:
        buf.free()


def test_overlap_off_ready_attached_is_invisible_and_exact():
    """Default trpc_coll_overlap=false with a ready map ATTACHED: the
    run waits once for the producer extent, results are byte-identical,
    and the overlap vars stay frozen at 0 — the feature is invisible
    until the flag flips."""
    n, shard = 2, 128 << 10
    w = shard // 4
    fleet = Fleet(n)
    try:
        sends = [rma.RmaBuffer(n * shard) for _ in range(n)]
        recvs = [rma.RmaBuffer(shard) for _ in range(n)]
        base = np.arange(n * w, dtype=np.uint32)
        for r in range(n):
            np.frombuffer(memoryview(sends[r].view),
                          dtype=np.uint32)[:] = base * 5 + r
        maps = [collective.ReadyMap(sends[r], granularity=32 << 10)
                for r in range(n)]
        for m in maps:
            m.stamp(0, m.nbytes)
        v0 = observe.Vars.dump()
        errs = fleet.run_all(
            lambda g, r, seq: g.reduce_scatter(sends[r], recvs[r],
                                               shard_bytes=shard,
                                               run_seq=seq,
                                               ready=maps[r]))
        assert not any(errs), errs
        v1 = observe.Vars.dump()
        assert v1.get("coll_ready_triggers_total", 0) == \
            v0.get("coll_ready_triggers_total", 0), \
            "overlap off must never readiness-trigger a transfer"
        assert v1.get("coll_overlap_runs_total", 0) == \
            v0.get("coll_overlap_runs_total", 0), \
            "overlap off must not count overlap runs"
        for r in range(n):
            got = np.frombuffer(memoryview(recvs[r].view), dtype=np.uint32)
            want = sum((base[r * w:(r + 1) * w] * np.uint32(5) + k)
                       for k in range(n)).astype(np.uint32)
            assert np.array_equal(got, want), f"rank {r} reduction wrong"
        for m in maps:
            m.close()
        _assert_sessions_drained()
    finally:
        fleet.close()


_OVL_CHILD_SRC = r"""
import sys, threading, time
import numpy as np
from brpc_tpu.rpc import (Server, collective, naming, observe, rma,
                          set_flag)

reg_addr, n, shard, M = (sys.argv[1], int(sys.argv[2]), int(sys.argv[3]),
                         int(sys.argv[4]))
w = shard // 4
srv = Server(); srv.enable_collective(); srv.start(0)
srv.announce(reg_addr, "coll_ovl", zone="z1")
self_addr = f"127.0.0.1:{srv.port}"
nc = naming.NamingClient(reg_addr, timeout_ms=5000)
deadline = time.time() + 30
while True:
    _v, members = nc.resolve("coll_ovl")
    if len(members) >= n:
        break
    if time.time() > deadline:
        print("RENDEZVOUS_TIMEOUT", flush=True); sys.exit(2)
    time.sleep(0.05)
g = collective.Group(naming_url=f"naming://{reg_addr}/coll_ovl",
                     self_addr=self_addr, timeout_ms=30000)
r = g.rank
grads = [rma.RmaBuffer(n * shard) for _ in range(M)]
reds = [rma.RmaBuffer(shard) for _ in range(M)]
gaths = [rma.RmaBuffer(n * shard) for _ in range(M)]

def fill(m):
    v = np.frombuffer(memoryview(grads[m].view), dtype=np.uint32)
    for p in range(n):
        v[p*w:(p+1)*w] = (np.arange(w, dtype=np.uint32)
                          * np.uint32(2654435761)
                          + np.uint32(r*1000003 + m*10007 + p*101))

# Sequential baseline: fill whole buffer, then communicate.
for m in range(M):
    fill(m)
    g.reduce_scatter(grads[m], reds[m], shard_bytes=shard,
                     run_seq=1 + 2*m)
    g.all_gather(reds[m], gaths[m], shard_bytes=shard, run_seq=2 + 2*m)
golden = [bytes(memoryview(gaths[m].view)) for m in range(M)]

# Overlapped: per-microbatch ReadyMap; the comm lane runs UNDER the
# producer, transfers firing as pieces stamp.
set_flag("trpc_coll_overlap", "true")
readies = [collective.ReadyMap(grads[m], granularity=shard)
           for m in range(M)]
base = 2 * M

def comm():
    for m in range(M):
        g.reduce_scatter(grads[m], reds[m], shard_bytes=shard,
                         run_seq=base + 1 + 2*m, ready=readies[m])
        g.all_gather(reds[m], gaths[m], shard_bytes=shard,
                     run_seq=base + 2 + 2*m)

t = threading.Thread(target=comm)
t.start()
for m in range(M):
    v = np.frombuffer(memoryview(grads[m].view), dtype=np.uint32)
    for p in range(n):
        v[p*w:(p+1)*w] = (np.arange(w, dtype=np.uint32)
                          * np.uint32(2654435761)
                          + np.uint32(r*1000003 + m*10007 + p*101))
        readies[m].stamp(p * shard, shard)
        time.sleep(0.002)
t.join(120)
if t.is_alive():
    print("WEDGED", flush=True); sys.exit(4)
if any(bytes(memoryview(gaths[m].view)) != golden[m] for m in range(M)):
    print(f"MISMATCH rank={r}", flush=True); sys.exit(3)
trig = observe.Vars.dump().get("coll_ready_triggers_total", 0)
if trig <= 0:
    print("NO_TRIGGERS", flush=True); sys.exit(5)
for rm in readies:
    rm.close()
if collective.sessions_live() != 0 or collective.ready_maps_live() != 0:
    print("NOT_QUIESCED", flush=True); sys.exit(6)
print(f"OK rank={r} triggers={trig}", flush=True)
g.close(); srv.stop()
"""


def test_multi_process_overlapped_pipeline_byte_exact():
    """The overlapped dataflow across GENUINE process boundaries: N
    member processes rendezvous through a naming registry, run M
    microbatches sequentially (golden bytes), then re-run the same
    dataflow overlapped — per-microbatch ReadyMap, producer stamping
    piece by piece while the comm lane is already inside the
    collective — and byte-verify against the sequential golden in every
    member, with readiness triggers observed and full quiescence."""
    n, shard, microbatches = 3, 128 << 10, 2
    registry = Server()
    registry.enable_naming_registry()
    registry.start(0)
    reg_addr = f"127.0.0.1:{registry.port}"
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    env.setdefault("JAX_PLATFORMS", "cpu")
    procs = [subprocess.Popen(
        [sys.executable, "-c", _OVL_CHILD_SRC, reg_addr, str(n),
         str(shard), str(microbatches)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True) for _ in range(n)]
    try:
        outs = []
        for p in procs:
            out, err = p.communicate(timeout=120)
            outs.append((p.returncode, out, err))
        oks = [o for rc, o, _ in outs if rc == 0 and "OK rank=" in o]
        assert len(oks) == n, f"multi-process overlap failed: {outs}"
        ranks = sorted(int(o.split("OK rank=")[1].split()[0]) for o in oks)
        assert ranks == list(range(n)), outs
        assert all("triggers=" in o for o in oks), outs
    finally:
        for p in procs:
            p.kill()
        registry.stop()
