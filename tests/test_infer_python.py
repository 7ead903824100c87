"""Streamed-inference front door through the Python surface (ISSUE 20).

The C++ tier (cpp/net/infer.h) runs a continuous-batching token
scheduler over multiplexed logical streams; brpc_tpu/rpc/infer.py is the
client, brpc_tpu/rpc/stream.py the raw stream surface.  These tests pin
the Python-visible contract:

- raw streams: offer/accept over an RPC, ordered bidirectional chunks,
  graceful close surfacing StreamClosedError after drain;
- end-to-end completions: ordered TokenRecords, EOS, deterministic
  tokens for equal prompts, infer_dump counters moving;
- prefix-cache prefill: the second identical prompt reports
  cached_tokens and recomputes NOTHING (bytes ratio measurable);
- cancel plane: client close frees the slot for a waiter the same step;
  deadline expiry raises CancelledError mid-stream;
- chaos composition: a disconnect mid-prefill, while prefix blocks pull
  from a svr_delay'd kv node, aborts the fetch whole-or-nothing
  (deadline_cancel_saved_bytes grows, nothing wedges, slot reused);
- per-tenant admission: an over-share tenant sheds TYPED
  (OverloadedError) while an in-share tenant still admits;
- flag validation, and the always-on counters of each way a request
  ends (EOS, cancel, shed).
"""

import time

import pytest

from brpc_tpu.rpc import (
    Channel,
    InferClient,
    OverloadedError,
    Server,
    StreamChunkTooLargeError,
    StreamClosedError,
    infer,
    kv,
    observe,
    open_stream,
    set_flag,
)

@pytest.fixture(autouse=True)
def _infer_flag_defaults():
    """Every test starts from known knobs and leaves the process-global
    flags back at their defaults (other suites read them)."""
    set_flag("trpc_infer_batch_max", "256")
    set_flag("trpc_infer_queue_max", "200000")
    set_flag("trpc_infer_step_us", "1000")
    set_flag("trpc_infer_prefill_us_per_token", "0")
    set_flag("trpc_infer_max_new_tokens", "256")
    set_flag("trpc_infer_bytes_per_token", "64")
    set_flag("trpc_kv_prefix_block_tokens", "8")
    yield
    set_flag("trpc_infer_batch_max", "256")
    set_flag("trpc_infer_queue_max", "200000")
    set_flag("trpc_infer_step_us", "1000")
    set_flag("trpc_infer_prefill_us_per_token", "5")
    set_flag("trpc_infer_max_new_tokens", "256")
    set_flag("trpc_infer_bytes_per_token", "64")
    set_flag("trpc_kv_prefix_block_tokens", "128")


def _prompt(seed: int, n: int) -> list:
    return [seed * 100003 + i + 1 for i in range(n)]


def _wait(cond, timeout_s: float = 5.0) -> bool:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.01)
    return cond()


def test_stream_echo_roundtrip():
    srv = Server()
    accepted = []

    def handler(call, req):
        st = call.accept_stream()
        accepted.append(st)
        call.respond(b"hi:" + req)

    srv.register("Echo.Stream", handler)
    port = srv.start()
    ch = Channel(f"127.0.0.1:{port}", timeout_ms=5000)
    try:
        st, resp = open_stream(ch, "Echo.Stream", b"abc")
        assert resp == b"hi:abc"
        assert _wait(lambda: len(accepted) == 1)
        peer = accepted[0]
        # Ordered chunks both directions; chunks never coalesce.
        peer.write(b"one")
        peer.write(b"two")
        assert st.read(timeout_ms=3000) == b"one"
        assert st.read(timeout_ms=3000) == b"two"
        st.write(b"up")
        assert peer.read(timeout_ms=3000) == b"up"
        # Graceful close: reads raise only after the buffer drains.
        peer.write(b"last")
        peer.close()
        assert st.read(timeout_ms=3000) == b"last"
        with pytest.raises(StreamClosedError):
            st.read(timeout_ms=3000)
        st.destroy()
        peer.destroy()
    finally:
        ch.close()
        srv.close()


def test_stream_read_never_truncates():
    """A chunk larger than the read buffer raises typed — nothing is
    dropped or truncated (silent truncation would desynchronize framed
    readers like the 16-byte TokenRecord stream)."""
    srv = Server()
    accepted = []

    def handler(call, req):
        accepted.append(call.accept_stream())
        call.respond(b"ok")

    srv.register("Echo.Stream", handler)
    port = srv.start()
    ch = Channel(f"127.0.0.1:{port}", timeout_ms=5000)
    try:
        st, _ = open_stream(ch, "Echo.Stream")
        assert _wait(lambda: len(accepted) == 1)
        peer = accepted[0]
        peer.write(b"x" * 32)
        with pytest.raises(StreamChunkTooLargeError) as ei:
            st.read(max_bytes=16, timeout_ms=3000)
        assert ei.value.needed == 32
        # The chunk stayed queued: a fitting retry gets ALL of it.
        assert st.read(max_bytes=32, timeout_ms=3000) == b"x" * 32
        st.destroy()
        peer.destroy()
    finally:
        ch.close()
        srv.close()


def test_infer_end_to_end_tokens_and_eos():
    srv = Server()
    srv.enable_infer(prefix_cache=False)
    port = srv.start()
    ch = Channel(f"127.0.0.1:{port}", timeout_ms=5000)
    try:
        d0 = srv.infer_dump()
        client = InferClient(ch)
        comp = client.submit(_prompt(1, 4), max_new_tokens=8,
                             timeout_ms=30000)
        assert comp.request_id > 0
        assert comp.cached_tokens == 0
        recs = list(comp.records())
        assert [r.index for r in recs] == list(range(8))
        assert recs[-1].eos
        # Equal prompts decode to equal tokens (deterministic sim).
        comp2 = client.submit(_prompt(1, 4), max_new_tokens=8,
                              timeout_ms=30000)
        assert list(comp2) == [r.token for r in recs]
        d1 = srv.infer_dump()
        assert d1["done"] - d0["done"] == 2
        assert d1["tokens"] - d0["tokens"] == 16
        assert d1["ttft"]["count"] > d0["ttft"]["count"]
        assert _wait(lambda: srv.infer_streams_live() == 0)
        assert srv.infer_streams_peak() >= 1
    finally:
        ch.close()
        srv.close()


def test_infer_prefix_cache_skips_recompute():
    kv.reset()
    srv = Server()
    srv.enable_infer(prefix_cache=True)
    port = srv.start()
    ch = Channel(f"127.0.0.1:{port}", timeout_ms=5000)
    try:
        client = InferClient(ch)
        prompt = _prompt(2, 32)  # 4 full blocks at block_tokens=8
        d0 = srv.infer_dump()
        cold = client.submit(prompt, max_new_tokens=4, timeout_ms=30000)
        assert cold.cached_tokens == 0
        cold_tokens = list(cold)
        d1 = srv.infer_dump()
        assert d1["bytes_recomputed"] - d0["bytes_recomputed"] == 32 * 64

        warm = client.submit(prompt, max_new_tokens=4, timeout_ms=30000)
        assert warm.cached_tokens == 32
        assert warm.block_tokens == 8
        assert list(warm) == cold_tokens
        d2 = srv.infer_dump()
        # The warm prompt recomputed NOTHING; its bytes came from cache.
        assert d2["bytes_recomputed"] == d1["bytes_recomputed"]
        assert d2["bytes_cached"] - d1["bytes_cached"] == 4 * 8 * 64
        assert _wait(lambda: srv.infer_streams_live() == 0)
    finally:
        ch.close()
        srv.close()
        kv.reset()


def test_infer_client_close_frees_slot_for_waiter():
    set_flag("trpc_infer_batch_max", "1")
    set_flag("trpc_infer_step_us", "5000")
    srv = Server()
    srv.enable_infer(prefix_cache=False)
    port = srv.start()
    ch = Channel(f"127.0.0.1:{port}", timeout_ms=5000)
    try:
        client = InferClient(ch)
        hog = client.submit(_prompt(3, 4), max_new_tokens=200,
                            timeout_ms=30000)
        waiter = client.submit(_prompt(4, 4), max_new_tokens=3,
                               timeout_ms=30000)
        # The single slot is held; the waiter can't have finished.
        assert srv.infer_dump()["waiting"] >= 1 or not waiter.finished
        hog.close()  # client walks away mid-generation
        toks = list(waiter)  # admitted into the freed slot, completes
        assert len(toks) == 3
        assert _wait(lambda: srv.infer_streams_live() == 0)
        assert srv.infer_dump()["cancelled"] >= 1
    finally:
        ch.close()
        srv.close()


def test_infer_deadline_expiry_raises_cancelled():
    set_flag("trpc_infer_step_us", "20000")  # ~5s for 256 tokens
    srv = Server()
    srv.enable_infer(prefix_cache=False)
    port = srv.start()
    ch = Channel(f"127.0.0.1:{port}", timeout_ms=5000)
    try:
        client = InferClient(ch)
        comp = client.submit(_prompt(5, 4), max_new_tokens=256,
                             timeout_ms=400)
        got = []
        with pytest.raises(infer.CancelledError):
            for tok in comp:
                got.append(tok)
        assert 0 < len(got) < 256
        assert _wait(lambda: srv.infer_streams_live() == 0)
    finally:
        ch.close()
        srv.close()


def test_infer_chaos_disconnect_aborts_prefix_fetch():
    kv.reset()
    # kv node: serves Kv.FetchPrefix from the process-wide store.
    kvsrv = Server()
    kvsrv.enable_kv_store()
    kv_port = kvsrv.start()
    # Serving node: same process singletons, but pulls matched blocks
    # over the wire from the kv node (prefill/decode disaggregation).
    srv = Server()
    srv.enable_infer(prefix_cache=True,
                     kv_fetch_addr=f"127.0.0.1:{kv_port}")
    port = srv.start()
    ch = Channel(f"127.0.0.1:{port}", timeout_ms=5000)
    try:
        client = InferClient(ch)
        prompt = _prompt(6, 32)
        # Populate: the cold submit publishes all 4 blocks.
        cold = client.submit(prompt, max_new_tokens=2, timeout_ms=30000)
        list(cold)

        # Now every fetch from the kv node crawls (100ms each, 4 blocks).
        kvsrv.set_faults("svr_delay=1:100")
        v0 = observe.Vars.dump()
        d0 = srv.infer_dump()
        warm = client.submit(prompt, max_new_tokens=2, timeout_ms=30000)
        assert warm.cached_tokens == 32
        time.sleep(0.15)  # mid-chain: ~block 2 of 4 in flight
        warm.close()  # disconnect

        assert _wait(lambda: srv.infer_streams_live() == 0, 10.0)
        assert _wait(
            lambda: srv.infer_dump()["fetch_aborted"] > d0["fetch_aborted"],
            5.0)
        v1 = observe.Vars.dump()
        saved = (v1.get("deadline_cancel_saved_bytes", 0)
                 - v0.get("deadline_cancel_saved_bytes", 0))
        assert saved > 0  # unpulled bytes credited, not silently dropped
        d1 = srv.infer_dump()
        # Whole-or-nothing: cached bytes moved in whole blocks only.
        assert (d1["bytes_cached"] - d0["bytes_cached"]) % (8 * 64) == 0
        assert d1["cancelled"] > d0["cancelled"]

        # Nothing wedged: the freed slot serves a fresh request.
        kvsrv.set_faults("")
        again = client.submit(_prompt(7, 4), max_new_tokens=3,
                              timeout_ms=30000)
        assert len(list(again)) == 3
        assert _wait(lambda: srv.infer_streams_live() == 0)
    finally:
        ch.close()
        srv.close()
        kvsrv.close()
        kv.reset()


def test_infer_overload_sheds_typed_per_tenant():
    set_flag("trpc_infer_batch_max", "2")
    set_flag("trpc_infer_queue_max", "6")  # cap 8, pressure at live >= 4
    set_flag("trpc_infer_step_us", "5000")
    srv = Server()
    srv.enable_infer(prefix_cache=False)
    port = srv.start()
    ch = Channel(f"127.0.0.1:{port}", timeout_ms=5000)
    held = []
    try:
        hog = InferClient(ch, tenant="hog")
        victim = InferClient(ch, tenant="victim")
        for i in range(4):
            held.append(hog.submit(_prompt(10 + i, 4), max_new_tokens=200,
                                   timeout_ms=30000))
        held.append(victim.submit(_prompt(20, 4), max_new_tokens=200,
                                  timeout_ms=30000))
        # hog holds 4 of its fair share of 4 under pressure: TYPED shed.
        with pytest.raises(OverloadedError):
            hog.submit(_prompt(21, 4), max_new_tokens=200,
                       timeout_ms=30000)
        # The in-share tenant still admits at the same instant.
        held.append(victim.submit(_prompt(22, 4), max_new_tokens=200,
                                  timeout_ms=30000))
        assert srv.infer_dump()["shed"] >= 1
    finally:
        for c in held:
            c.close()
        assert _wait(lambda: srv.infer_streams_live() == 0, 10.0)
        ch.close()
        srv.close()


def test_infer_flag_validation():
    for name, bad in [
        ("trpc_infer_batch_max", "0"),
        ("trpc_infer_batch_max", "70000"),
        ("trpc_infer_step_us", "-1"),
        ("trpc_infer_queue_max", "2000000"),
        ("trpc_infer_max_new_tokens", "0"),
        ("trpc_infer_bytes_per_token", "0"),
        ("trpc_infer_prefill_us_per_token", "1000001"),
    ]:
        with pytest.raises(ValueError):
            set_flag(name, bad)
    set_flag("trpc_infer_batch_max", "16")  # in-range value lands
    set_flag("trpc_infer_batch_max", "256")


_OUTCOME_VARS = ("infer_admitted_total", "infer_done_total",
                 "infer_cancelled_total", "infer_shed_total",
                 "infer_tokens_total")


def _outcome_vars() -> dict:
    v = observe.Vars.dump()
    return {k: int(v.get(k, 0)) for k in _OUTCOME_VARS}


@pytest.mark.parametrize("outcome", ["eos", "cancel", "shed"])
def test_infer_outcome_moves_its_counters(outcome):
    """Each way a request ends is counted by its own always-on var: a
    request decoded to EOS is admitted and done, one the client walks
    away from is admitted and cancelled, and one past batch+queue is
    shed without being admitted."""
    set_flag("trpc_infer_step_us", "5000")
    if outcome == "shed":
        set_flag("trpc_infer_batch_max", "1")
        set_flag("trpc_infer_queue_max", "0")  # cap 1: the holder fills it
    srv = Server()
    srv.enable_infer(prefix_cache=False)
    port = srv.start()
    ch = Channel(f"127.0.0.1:{port}", timeout_ms=5000)
    try:
        client = InferClient(ch)
        v0 = _outcome_vars()
        if outcome == "eos":
            toks = list(client.submit(_prompt(30, 4), max_new_tokens=4,
                                      timeout_ms=30000))
            assert len(toks) == 4
        else:
            held = client.submit(_prompt(31, 4), max_new_tokens=200,
                                 timeout_ms=30000)
            next(iter(held))  # admitted and decoding
            if outcome == "shed":
                with pytest.raises(OverloadedError):
                    client.submit(_prompt(32, 4), max_new_tokens=4,
                                  timeout_ms=30000)
            held.close()
        assert _wait(lambda: srv.infer_streams_live() == 0, 10.0)
        d = {k: n - v0[k] for k, n in _outcome_vars().items()}
        assert d["infer_admitted_total"] == 1, d
        if outcome == "eos":
            assert d["infer_done_total"] == 1, d
            assert d["infer_cancelled_total"] == 0, d
            assert d["infer_shed_total"] == 0, d
            assert d["infer_tokens_total"] == 4, d
        else:
            assert d["infer_done_total"] == 0, d
            assert d["infer_cancelled_total"] == 1, d
            assert d["infer_shed_total"] == (1 if outcome == "shed" else 0), d
            assert 1 <= d["infer_tokens_total"] < 200, d
    finally:
        ch.close()
        srv.close()
