"""One-sided RMA plane through the Python surface (ISSUE 10).

The C++ side (cpp/net/rma.{h,cc}) registers shm-backed regions under
rkeys; a batch call whose resp_buf is an `RmaBuffer` advertises the rkey
on the request and — over shm/ici connections — the SERVER writes the
response payload straight into the caller's buffer (remote landing, zero
receiver-side copies), completing with a release-fenced chunk bitmap
plus one control frame.  These tests pin the Python-visible contract:

- RmaBuffer lifecycle (alloc/free, registry count, double-free safe);
- batch resp_buf remote landing: byte-exact 16MB echo over an shm
  channel INTO an RmaBuffer, in_caller_buffer set, rma vars moved and
  stripe vars NOT (the payload genuinely bypassed the frame plane);
- cross-process landing: a separate server process maps this process's
  region by rkey and writes into it (pid != self path);
- graceful degradation: the same RmaBuffer over TCP still lands
  correctly via the striped copy path;
- the io_uring kernel-capability probe (satellite: the ROADMAP item 2
  gate) agrees with /vars' kernel_io_uring_supported gauge.
"""

import os
import subprocess
import sys
import time

import numpy as np
import pytest

from brpc_tpu.rpc import Channel, RmaBuffer, Server, kernel_supports
from brpc_tpu.rpc import observe
from brpc_tpu.rpc._lib import load_library


@pytest.fixture(scope="module")
def server():
    srv = Server()
    srv.register_native_echo("Echo.Echo")
    srv.start(0)
    yield srv
    srv.stop()


def _vars(keys):
    v = observe.Vars.dump()
    return {k: v.get(k, 0) for k in keys}


_RMA_KEYS = ("rma_tx_msgs", "rma_rx_msgs", "rma_tx_bytes", "rma_rejected")
_STRIPE_KEYS = ("stripe_tx_chunks", "stripe_reassembled")


def _pattern(n: int) -> np.ndarray:
    return (np.arange(n, dtype=np.uint64) * 2654435761 >> 13).astype(np.uint8)


def test_rma_buffer_lifecycle():
    lib = load_library()
    before = int(lib.trpc_rma_region_count())
    buf = RmaBuffer(1 << 20)
    assert buf.rkey != 0
    assert len(buf) == 1 << 20
    assert int(lib.trpc_rma_region_count()) == before + 1
    view = np.frombuffer(buf.view, dtype=np.uint8)
    view[:] = 0x5A
    assert int(view[12345]) == 0x5A
    buf.free()
    buf.free()  # idempotent
    assert int(lib.trpc_rma_region_count()) == before
    with pytest.raises(ValueError):
        _ = buf.view


def test_an_orphan_under_this_pids_name_does_not_refuse_the_buffer():
    """A process killed before it could unlink leaves `trpc_rma_<pid>_<n>`
    in /dev/shm, and pids are recycled: the name a new region asks for
    may be there already.  It is an orphan by construction (the ordinal
    is minted once in this process), and the region takes the name."""
    orphans = [f"/dev/shm/trpc_rma_{os.getpid()}_{n}" for n in range(4096)]
    made = []
    for path in orphans:
        try:
            with open(path, "xb") as f:
                f.write(b"left by a dead process")
            made.append(path)
        except FileExistsError:      # a live region of this process
            pass
    try:
        with RmaBuffer(1 << 16) as buf:
            view = np.frombuffer(buf.view, np.uint8)
            assert not view.any()        # fresh pages, not the orphan's
            view[:] = 7
            assert len(buf) == 1 << 16
    finally:
        for path in made:
            if os.path.exists(path):
                os.unlink(path)


def test_batch_resp_buf_remote_landing_shm(server):
    """The mirror of the C++ direct-landing case: a 16MB response is PUT
    by the server straight into the caller's registered buffer."""
    size = 16 << 20
    payload = _pattern(size)
    ch = Channel(f"127.0.0.1:{server.port}", timeout_ms=60000, use_shm=True)
    try:
        assert ch.call("Echo.Echo", b"warm") == b"warm"
        assert ch.transport == "shm_ring"
        rma0 = _vars(_RMA_KEYS)
        stripe0 = _vars(_STRIPE_KEYS)
        with RmaBuffer(size) as land:
            pipe = ch.pipeline()
            try:
                toks = pipe.submit("Echo.Echo", [payload],
                                   resp_bufs=[land.view])
                cs = pipe.poll(max_n=1, timeout_ms=60000)
                assert len(cs) == 1 and cs[0].ok and cs[0].token == toks[0]
                assert cs[0].in_caller_buffer
                got = np.frombuffer(land.view, dtype=np.uint8)
                assert np.array_equal(got, payload), "remote landing corrupt"
            finally:
                pipe.close()
        rma1 = _vars(_RMA_KEYS)
        stripe1 = _vars(_STRIPE_KEYS)
        # The request AND the response rode the one-sided plane; the
        # frame-based stripe plane moved nothing for this transfer.
        assert rma1["rma_tx_msgs"] >= rma0["rma_tx_msgs"] + 2
        assert rma1["rma_rx_msgs"] >= rma0["rma_rx_msgs"] + 2
        assert rma1["rma_tx_bytes"] >= rma0["rma_tx_bytes"] + 2 * size
        assert rma1["rma_rejected"] == rma0["rma_rejected"]
        assert stripe1 == stripe0
    finally:
        ch.close()


def test_rma_buffer_degrades_over_tcp(server):
    """Same RmaBuffer, TCP connection: no one-sided plane — the striped
    copy path lands the response in the buffer instead."""
    size = 8 << 20
    payload = _pattern(size)
    ch = Channel(f"127.0.0.1:{server.port}", timeout_ms=60000,
                 connection_type="pooled")
    try:
        rma0 = _vars(_RMA_KEYS)
        with RmaBuffer(size) as land:
            pipe = ch.pipeline()
            try:
                pipe.submit("Echo.Echo", [payload], resp_bufs=[land.view])
                cs = pipe.poll(max_n=1, timeout_ms=60000)
                assert len(cs) == 1 and cs[0].ok
                got = np.frombuffer(land.view, dtype=np.uint8)
                assert np.array_equal(got, payload)
            finally:
                pipe.close()
        rma1 = _vars(_RMA_KEYS)
        assert rma1["rma_tx_msgs"] == rma0["rma_tx_msgs"]  # TCP: untouched
    finally:
        ch.close()


_CHILD_SERVER = r"""
import sys
from brpc_tpu.rpc import Server
srv = Server()
srv.register_native_echo("Echo.Echo")
srv.start(0)
print(srv.port, flush=True)
sys.stdin.readline()  # parent closes stdin to stop us
srv.stop()
"""


def test_cross_process_remote_landing():
    """A SEPARATE server process maps this process's registered region
    by rkey (pid != self) and writes the response into it — the real
    two-process one-sided path, not loopback mapping-sharing."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))
    child = subprocess.Popen(
        [sys.executable, "-c", _CHILD_SERVER], env=env,
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    try:
        port = int(child.stdout.readline())
        size = 16 << 20
        payload = _pattern(size)
        ch = Channel(f"127.0.0.1:{port}", timeout_ms=60000, use_shm=True)
        try:
            assert ch.call("Echo.Echo", b"warm") == b"warm"
            assert ch.transport == "shm_ring"
            rma0 = _vars(_RMA_KEYS)
            with RmaBuffer(size) as land:
                pipe = ch.pipeline()
                try:
                    pipe.submit("Echo.Echo", [payload],
                                resp_bufs=[land.view])
                    cs = pipe.poll(max_n=1, timeout_ms=60000)
                    assert len(cs) == 1 and cs[0].ok
                    assert cs[0].in_caller_buffer
                    got = np.frombuffer(land.view, dtype=np.uint8)
                    assert np.array_equal(got, payload)
                finally:
                    pipe.close()
            rma1 = _vars(_RMA_KEYS)
            # This process SENT the request one-sided and RESOLVED the
            # remote-landed response.
            assert rma1["rma_tx_msgs"] > rma0["rma_tx_msgs"]
            assert rma1["rma_rx_msgs"] > rma0["rma_rx_msgs"]
        finally:
            ch.close()
    finally:
        try:
            child.stdin.close()
            child.wait(timeout=10)
        except Exception:  # noqa: BLE001
            child.kill()


def test_kernel_supports_probe_and_var(server):
    a = kernel_supports("io_uring")
    assert a in (0, 1)
    assert kernel_supports("io_uring") == a  # stable
    assert kernel_supports("definitely_not_a_feature") == -1
    # The /vars gauge agrees (registered by any running Server).
    deadline = time.time() + 5
    val = None
    while time.time() < deadline:
        val = observe.Vars.dump().get("kernel_io_uring_supported")
        if val is not None:
            break
        time.sleep(0.1)
    assert val == a


def test_rma_window_flag_validated():
    from brpc_tpu.rpc import get_flag, set_flag

    old = get_flag("trpc_rma_window_bytes")
    try:
        set_flag("trpc_rma_window_bytes", str(64 << 20))
        assert int(get_flag("trpc_rma_window_bytes")) == 64 << 20
        with pytest.raises(Exception):
            set_flag("trpc_rma_window_bytes", "12345")  # not a pow2 window
        with pytest.raises(Exception):
            set_flag("trpc_shm_rails", "99")  # out of range
    finally:
        set_flag("trpc_rma_window_bytes", old)
