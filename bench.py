#!/usr/bin/env python3
"""Headline benchmark: echo goodput + RTT percentiles, 1KB-64MB sweep.

BASELINE.json's metric is rpc_press-style goodput AND p99 RTT across
1KB-64MB echo (the reference measures both: docs/cn/benchmark.md:104 for
the 2.3 GB/s pooled-connection headline, example/rdma_performance/client.cpp
for the per-size attachment echo sweep). This driver measures the same
two quantities on the TPU data plane:

- per size in {1KB .. 64MB}: goodput and per-step time from chains of
  data-dependent echo steps, each chain ended by fetching its checksum to
  the host; both are the MARGINAL cost between a short and a long chain,
  so the constant cost of the final sync cancels;
- the fused Pallas kernel (one HBM pass for copy+checksum) carries sizes
  from 1MB; smaller payloads use the jitted XLA echo step;
- the C++ runtime's loopback numbers (bench_echo: 64-fiber sync echo via
  Server/Channel, the multi_threaded_echo_c++ analogue) ride along under
  "cpp" when the binary exists.

The device legs (the sweep and the device-origin RPC leg) measure the chip
or fail: each runs in a child that exits non-zero when JAX's first device
is not a TPU, when a step does not verify or when a leg errors, and the
parent — which never touches JAX, so the child is the one process that
wants the chip — exits non-zero with it.  There is no CPU re-run and no
retry.  Every device row names platform, device_kind and device count.
The host-only rows (qos_mixed, kv_disagg, collective, ...) run on host
cores and never touch the device.

Prints ONE JSON line. Headline metric stays the 64MB echo goodput vs the
reference's 2.3 GB/s; the sweep rows are under "sweep".

Env knobs: BENCH_CHILD=1 runs the row-emitting sweep in-process;
BENCH_TPU_RPC=1 the device-origin RPC leg; BENCH_QOS / BENCH_KV / ... one
host-only row each.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

BASELINE_GBPS = 2.3
SIZES = [1 << 10, 1 << 16, 1 << 20, 1 << 24, 1 << 26]  # 1KB .. 64MB
FUSED_MIN_BYTES = 1 << 20  # the fused kernel carries sizes from 1MB (whole
                           # 512KB blocks; chip_smoke.py uses the same rule)


# ---------------------------------------------------------------- child ----

def _require_tpu():
    """Prologue of a device leg: places the compile cache, then returns the
    first JAX device, which must be a TPU — a device leg measures the chip
    or fails, it never reports another platform under a device metric's
    name."""
    import jax

    from brpc_tpu.compile_cache import enable_compile_cache

    enable_compile_cache()
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(
            f"bench device leg needs a TPU; JAX found platform "
            f"{device.platform!r} (device_kind {device.device_kind!r}, "
            f"{len(jax.devices())} device(s))")
    return device


def _device_stamp(device) -> dict:
    import jax

    return {"platform": device.platform, "device_kind": device.device_kind,
            "device_count": len(jax.devices())}


def _child_sweep() -> None:
    """Runs in a subprocess: one JSON row per size, flushed immediately."""
    import jax
    import jax.numpy as jnp

    from brpc_tpu.models.echo import single_chip_echo_step
    from brpc_tpu.ops.echo_kernel import _BLOCK, echo_fused
    from brpc_tpu.ops.roofline import hbm_peak_gbps

    device = _require_tpu()
    hbm_peak = hbm_peak_gbps(device.device_kind)
    fused = jax.jit(echo_fused, donate_argnums=0)
    plain = jax.jit(single_chip_echo_step, donate_argnums=0)

    def chained(step, resp, iters: int):
        """Time `iters` data-dependent echo steps, ended by fetching the
        last checksum to the host.  Returns (seconds, live response) — the
        input buffer is donated away by the first step."""
        t0 = time.perf_counter()
        for _ in range(iters):
            resp, csum = step(resp)
        _ = int(csum)
        return time.perf_counter() - t0, resp

    for size in SIZES:
        lanes = size // 4
        step = fused if (size >= FUSED_MIN_BYTES
                         and lanes % _BLOCK == 0) else plain
        payload = jnp.arange(lanes, dtype=jnp.uint32)
        resp, csum = step(payload)  # compile + warm
        first = int(csum)  # noqa: F841 — forces compile+execute+fetch
        t0 = time.perf_counter()
        resp, csum = step(resp)
        _ = int(csum)
        probe = time.perf_counter() - t0  # one step + one 4-byte fetch

        # Goodput: marginal cost between a short and a long chained run.
        # Both runs pay the same constant sync cost; the difference is
        # (n2 - n1) genuinely-executed, data-dependent iterations.
        # min-of-2 per length sheds jitter spikes; n2 is sized from the
        # short runs' own marginal estimate so a slow step stays inside
        # the leg's deadline — an inflated estimate only shrinks n2, which
        # is the safe direction.
        n1 = 16
        t_a, resp = chained(step, resp, n1)
        t_a2, resp = chained(step, resp, n1)
        t_a = min(t_a, t_a2)
        marg_est = max((t_a - probe) / n1, 1e-5)
        n2 = max(4 * n1, min(1024, int(8.0 / marg_est)))
        t_b, resp = chained(step, resp, n2)
        t_b2, resp = chained(step, resp, n2)
        t_b = min(t_b, t_b2)
        if t_b <= t_a:
            raise RuntimeError(
                f"{size}B: a chain of {n2} steps ({t_b:.6f}s) was not "
                f"slower than one of {n1} ({t_a:.6f}s); no marginal cost "
                "to report")
        gbps = size * (n2 - n1) / (t_b - t_a) / 1e9

        # Per-step time of the DATA PLANE.  Each sample is a marginal-cost
        # estimate — (chain of base+m) − (chain of base), both paying the
        # same constant sync, divided by m — so the estimate is per-step
        # device time.  m is sized so the delta dominates sync jitter;
        # each sample still averages over m steps, so tails narrower than
        # jitter/m are not observable — "latency_method" says so.
        m = max(8, min(1024, int(0.15 / max(marg_est, 1e-7))))
        lat_samples = []
        nlat = 10
        base = 2
        for _ in range(nlat):
            t_s, resp = chained(step, resp, base)
            t_l, resp = chained(step, resp, base + m)
            lat_samples.append(max((t_l - t_s) / m, 0.0))
        lat_samples.sort()

        def pct(p: float) -> float:
            return lat_samples[min(len(lat_samples) - 1,
                                   int(p * len(lat_samples)))]

        row = {
            "size": size,
            "goodput_gbps": round(gbps, 3),
            "p50_us": round(pct(0.50) * 1e6, 1),
            "p99_us": round(pct(0.99) * 1e6, 1),
            "latency_method": f"marginal_chain_m{m}",
            "fetch_ms": round(probe * 1e3, 1),
            **_device_stamp(device),
            "goodput_method": "device_chain",
        }
        # Edge sizes: per-call Python overhead, not the runtime, bounds a
        # synchronous 1KB or 64MB call.  Drive these rows through the
        # batched RPC pipeline at depth >= 8 so goodput measures the data
        # plane; the device-chain number stays alongside.  16MB rides
        # along (ISSUE 5): the mid-large band is where the
        # monolithic-frame path collapsed, so it gets an RPC-path number
        # (and a perf-smoke floor) of its own.
        if size == SIZES[0] or size >= (16 << 20):
            # Small payloads need a deep window to amortize per-call
            # runtime cost (native 1KB echo is ~90k calls/s; 8-deep
            # leaves the pipe mostly empty); big payloads need few.
            rpc = _rpc_batch_goodput(
                size, depth=8 if size >= (1 << 20) else 256)
            if rpc is None:
                raise RuntimeError(f"{size}B: the RPC pipeline leg failed")
            row["device_step_gbps"] = row["goodput_gbps"]
            row["goodput_gbps"] = rpc["goodput_gbps"]
            row["pipeline_depth"] = rpc["pipeline_depth"]
            row["bytes_moved_per_iter"] = rpc["bytes_moved_per_iter"]
            row["goodput_method"] = "rpc_call_batch"
            for k in ("stripe_rails", "stripe_chunk_bytes",
                      "timeline"):
                if k in rpc:
                    row[k] = rpc[k]
            if rpc.get("vars"):
                row["vars"] = rpc["vars"]
        if step is fused:
            # One read + one write pass per echo → HBM bytes = 2× goodput
            # bytes.  The roofline discipline of BASELINE.md applied to
            # the kernel.
            row["hbm_frac"] = round(2 * gbps / hbm_peak, 3)
        print(json.dumps(row), flush=True)


def _child_tpu_rpc() -> None:
    """device array → staging DMA → the FULL C++ RPC stack (Server/Channel
    over tcp/shm/ici rings, GIL released, payload by reference) → echoed
    bytes → device array, verified on device.  The rpc_* numbers measure
    the framework data plane at native speed (an earlier 0.36 GB/s ceiling
    was per-call Python bounces, not the runtime)."""
    import ctypes

    import numpy as np

    import jax
    import jax.numpy as jnp

    from brpc_tpu.ops.checksum import sum32
    from brpc_tpu.rpc._lib import load_library

    device = _require_tpu()
    lib = load_library()
    f = lib.trpc_bench_echo_rpc

    size = 64 << 20
    dev = jnp.arange(size // 4, dtype=jnp.uint32)
    expected = int(sum32(dev))  # forces materialize

    # Registered staging slab: the device→host DMA lands
    # in ici-registered shm memory, so the ici leg ships it with
    # SENDER-OWNED descriptors — no ring DMA copy, one descriptor per
    # payload (the rdma block_pool takeover analogue; a PJRT pinned-host
    # backend would land the fetch here directly).
    lib.trpc_ici_staging_alloc.restype = ctypes.c_void_p
    lib.trpc_ici_staging_alloc.argtypes = [
        ctypes.c_size_t, ctypes.POINTER(ctypes.c_uint32)]
    lib.trpc_ici_zero_copy_counters.argtypes = [
        ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint64)]
    ord_out = ctypes.c_uint32()
    slab = lib.trpc_ici_staging_alloc(size, ctypes.byref(ord_out))
    if not slab:
        raise MemoryError(f"ici staging alloc of {size} bytes failed")

    # The PJRT hop (np.asarray; libtpu hands out no host-addressable
    # pointer to device memory — tools/PJRT_PROBE.md), then the landing
    # into registered memory.
    t0 = time.perf_counter()
    fetched = np.asarray(dev).view(np.uint8)
    dma_s = time.perf_counter() - t0
    staging = np.frombuffer(
        (ctypes.c_char * size).from_address(slab), dtype=np.uint8)
    t0 = time.perf_counter()
    np.copyto(staging, fetched)
    land_s = time.perf_counter() - t0

    iters = 12
    # Honest labeling: this leg is a LOOPBACK
    # descriptor-path measurement — the ici number counts sender-owned
    # descriptors over in-process rings, not bytes across a chip
    # interconnect, and each iteration's goodput-counted payload is
    # `size` bytes.  The fields make that unmistakable in the artifact.
    # Path attribution (ISSUE 10): each ring leg is stamped rma|copy from
    # the rma_rx_msgs delta around it, plus the rail counts in force, so
    # a BENCH row can never silently change data path between rounds.
    def _var(name: str) -> int:
        out = ctypes.create_string_buffer(64)
        return (int(out.value) if lib.trpc_var_read(name.encode(), out, 64)
                == 0 and out.value else 0)

    def _flag(name: str) -> str:
        out = ctypes.create_string_buffer(64)
        return (out.value.decode() if
                lib.trpc_flag_get(name.encode(), out, 64) == 0 else "?")

    row = {"kind": "tpu_rpc_64MB", **_device_stamp(device),
           "loopback": True,
           "bytes_moved_per_iter": size,
           "staging_dma_gbps": round(size / dma_s / 1e9, 3),
           "staging_land_gbps": round(size / land_s / 1e9, 3),
           "rpc": {}, "rpc_path": {}, "rpc_16mb": {}, "rpc_16mb_path": {},
           "rma_rails": {"shm": _flag("trpc_shm_rails"),
                         "ici": _flag("trpc_ici_rails")}}
    best = 0.0
    resp = np.empty(size, dtype=np.uint8)
    zc0_w, zc0_b = ctypes.c_uint64(), ctypes.c_uint64()
    lib.trpc_ici_zero_copy_counters(ctypes.byref(zc0_w),
                                    ctypes.byref(zc0_b))

    def _zc_bytes() -> int:
        w, b = ctypes.c_uint64(), ctypes.c_uint64()
        lib.trpc_ici_zero_copy_counters(ctypes.byref(w), ctypes.byref(b))
        return b.value

    def _ring_leg(tr: str, leg_size: int, leg_iters: int, resp_ptr,
                  goodput_out: dict, path_out: dict) -> float:
        """One echo leg + its path stamp (rma | desc_zero_copy | copy)."""
        g = ctypes.c_double()
        used = ctypes.create_string_buffer(32)
        err = ctypes.create_string_buffer(256)
        rma0 = _var("rma_rx_msgs")
        zcb0 = _zc_bytes()
        rc = f(staging.ctypes.data, leg_size, leg_iters, 1, tr.encode(),
               resp_ptr, ctypes.byref(g), used, 32, err, 256)
        if rc != 0:
            raise RuntimeError(
                f"{tr} leg at {leg_size}B failed: {err.value.decode()}")
        name = used.value.decode()
        goodput_out[name] = round(g.value, 3)
        if _var("rma_rx_msgs") > rma0:
            path_out[name] = "rma"
        elif _zc_bytes() - zcb0 >= leg_size:
            path_out[name] = "desc_zero_copy"  # sender-owned descriptors
        else:
            path_out[name] = "copy"
        return g.value

    for tr in ("ici", "shm", "tcp"):
        best = max(best, _ring_leg(
            tr, size, iters, resp.ctypes.data if tr == "ici" else None,
            row["rpc"], row["rpc_path"]))
    # 16MB ring legs (same stack, mid-large band) with their own stamps.
    for tr in ("ici", "shm"):
        _ring_leg(tr, 16 << 20, iters * 4, None,
                  row["rpc_16mb"], row["rpc_16mb_path"])
    zc1_w, zc1_b = ctypes.c_uint64(), ctypes.c_uint64()
    lib.trpc_ici_zero_copy_counters(ctypes.byref(zc1_w),
                                    ctypes.byref(zc1_b))
    # The no-extra-host-copy assertion: the ici leg's payload bytes rode
    # sender-owned descriptors (ring DMA elided), not the bounce path.
    row["ici_zero_copy"] = {
        "wrs": zc1_w.value - zc0_w.value,
        "bytes": zc1_b.value - zc0_b.value,
        "payload_covered": (zc1_b.value - zc0_b.value) >= size * iters,
    }

    # Close the loop: echoed bytes back onto the device, verified there.
    back = jax.device_put(resp.view(np.uint32))
    if not bool(jnp.array_equal(back, dev)) or int(sum32(back)) != expected:
        raise RuntimeError("echoed bytes differ from the device payload")
    row["roundtrip_verified"] = True
    row["value"] = round(best, 3)
    print(json.dumps(row), flush=True)


def _observe_snapshot() -> dict | None:
    """Key observability vars for a BENCH row (ISSUE 4): every perf
    number ships with its own attribution — how often the wait-free
    inline write path hit, how big dispatch batches ran, how deep the
    pipeline actually was, and the per-method/client p99s.  Tolerant of
    ANY missing var (older libraries, partial registries): absent keys
    are simply omitted so BENCH artifacts stay comparable across
    rounds."""
    try:
        from brpc_tpu.rpc import observe
        v = observe.Vars.dump()
    except Exception:  # noqa: BLE001 — bench must still print its line
        return None
    out: dict = {}
    try:
        att = v.get("socket_inline_write_attempts", 0)
        hit = v.get("socket_inline_write_hits", 0)
        if att:
            out["inline_write_ratio"] = round(hit / att, 4)
    except Exception:  # noqa: BLE001
        pass
    for var, key, field in (
        ("messenger_dispatch_batch", "dispatch_batch_p50", "p50_us"),
        ("rpc_server_Echo.Echo", "server_echo_p99_us", "p99_us"),
        ("rpc_client_batch", "client_batch_p99_us", "p99_us"),
    ):
        try:
            out[key] = getattr(observe.Latency.read(var), field)
        except Exception:  # noqa: BLE001 — var not registered in this run
            pass
    for name in ("batch_depth", "batch_inflight"):
        if isinstance(v.get(name), (int, float)) and v[name] >= 0:
            out[name] = v[name]
    return out or None


def _rpc_batch_goodput(size: int, depth: int = 8,
                       target_s: float = 1.0) -> dict | None:
    """Loopback echo goodput of the PYTHON DATA PLANE at `depth`-deep
    pipelining: a WINDOWED submit/poll pipeline (batch API, one GIL
    crossing per drain, completions polled off-GIL) with buffer-protocol
    zero-copy requests and responses landing in recycled caller buffers;
    native echo server so the server side has no GIL in the path.  The
    window stays full in steady state — poll k, resubmit k — so there is
    no wait-for-all bubble between batches (the per-call-bounce artifact
    this leg exists to retire).  None on any failure (bench must still
    print its line)."""
    try:
        import numpy as np

        from brpc_tpu.rpc import Channel, Server

        srv = Server()
        srv.register_native_echo("Echo.Echo")
        srv.start(0)
        ch = pipe = None
        try:
            # Large payloads stream best over per-call pooled sockets
            # (the batch pipeline fans out one issue fiber per member);
            # small ones over the single multiplexed connection.
            conn = "pooled" if size >= (1 << 20) else "single"
            ch = Channel(f"127.0.0.1:{srv.port}", timeout_ms=60000,
                         connection_type=conn)
            payload = np.empty(size, dtype=np.uint8)
            payload.reshape(-1, 256)[:] = np.arange(256, dtype=np.uint8)
            pipe = ch.pipeline()
            free_bufs = [np.empty(size, dtype=np.uint8)
                         for _ in range(depth)]
            token2buf: dict[int, object] = {}

            def submit_k(k: int) -> None:
                bs = [free_bufs.pop() for _ in range(k)]
                toks = pipe.submit("Echo.Echo", [payload] * k,
                                   resp_bufs=bs)
                token2buf.update(zip(toks, bs))

            # Warm pass (untimed): fault in the landing buffers, grow the
            # block pool and connections to steady state — at 64MB the
            # first window alone moves 512MB through cold pages and would
            # dominate a short measurement.
            verified = False
            submit_k(depth)
            warm_left = depth
            while warm_left > 0:
                cs = pipe.poll(max_n=depth, timeout_ms=60000)
                if not cs:
                    return None  # wedged: bench must still print its line
                for c in cs:
                    if not c.ok:
                        return None
                    buf = token2buf.pop(c.token)
                    if not verified:
                        if not np.array_equal(buf, payload):
                            return None
                        verified = True
                    free_bufs.append(buf)
                    warm_left -= 1

            submit_k(depth)  # prime the measured window
            completed = 0
            t0 = time.perf_counter()
            inflight = depth
            submitting = True
            while inflight > 0:
                cs = pipe.poll(max_n=depth, timeout_ms=60000)
                if not cs:
                    return None  # wedged
                for c in cs:
                    if not c.ok:
                        return None  # a failed member voids the run
                    free_bufs.append(token2buf.pop(c.token))
                completed += len(cs)
                inflight -= len(cs)
                if submitting and (time.perf_counter() - t0 >= target_s
                                   or completed >= 200_000):
                    submitting = False  # drain the tail, stop refilling
                if submitting:
                    submit_k(len(cs))
                    inflight += len(cs)
            dt = time.perf_counter() - t0
            if completed == 0 or not verified:
                return None
            row = {
                "goodput_gbps": round(size * completed / dt / 1e9, 3),
                "pipeline_depth": depth,
                "bytes_moved_per_iter": size * depth,
                "conn": conn,
                # Built-in attribution (ISSUE 4): the observability-plane
                # snapshot taken right after the measured window, from
                # the process that ran it.
                "vars": _observe_snapshot(),
            }
            # Large-message striping attribution (ISSUE 5): which rail /
            # chunk geometry this row ran under, so goodput deltas across
            # rounds are attributable to config, not code alone.  Only
            # stamped when the payload actually striped.
            try:
                from brpc_tpu.rpc import get_flag

                # Flight-recorder attribution (ISSUE 9): rows stamp
                # whether trpc_timeline was recording during the
                # measured window, so BENCH comparability across rounds
                # is explicit (a timeline-on row is not the same series).
                row["timeline"] = get_flag("trpc_timeline") == "true"
                thr = int(get_flag("trpc_stripe_threshold"))
                if thr > 0 and size > thr:  # 0 = striping disabled
                    row["stripe_rails"] = int(get_flag("trpc_stripe_rails"))
                    row["stripe_chunk_bytes"] = int(
                        get_flag("trpc_stripe_chunk_bytes"))
            except Exception:  # noqa: BLE001 — bench must still print
                pass
            return row
        finally:
            if pipe is not None:
                pipe.close()
            if ch is not None:
                ch.close()
            srv.stop()
    except Exception:  # noqa: BLE001
        return None


def _child_qos_mixed() -> None:
    """Mixed-workload QoS row (ISSUE 6): the high-priority 1KB floor
    measured WHILE low-priority 64MB streams saturate the same server and
    an admission-limited background tenant floods it.  Load generators
    run in their OWN processes — in-process threads would measure this
    interpreter's GIL, not the server's isolation.  Extends the PR-5
    cut-budget HOL guard into a published number: the ratio column is the
    acceptance metric (loaded p99 within 2x unloaded)."""
    import statistics

    from brpc_tpu.rpc import Channel, Server, get_flag, set_flag

    lanes = 4
    lane_weights = "8,4,2,1"
    bg_spec = "bg:weight=1,limit=4;*:limit=10000"
    bulk_bytes = 64 << 20
    set_flag("trpc_qos_lanes", str(lanes))
    set_flag("trpc_qos_lane_weights", lane_weights)
    srv = Server()
    srv.register_native_echo("Echo.Echo")
    srv.set_qos(bg_spec)
    srv.start(0)
    addr = f"127.0.0.1:{srv.port}"
    fg = Channel(addr, timeout_ms=10000, qos_tenant="fg", qos_priority=0)

    def p99(lat: list) -> float:
        lat = sorted(lat)
        return lat[len(lat) * 99 // 100]

    def sample(seconds: float) -> list:
        lat = []
        end = time.perf_counter() + seconds
        while time.perf_counter() < end:
            t0 = time.perf_counter()
            fg.call("Echo.Echo", b"x" * 1024)
            lat.append((time.perf_counter() - t0) * 1e6)
        return lat

    for _ in range(100):  # warm: connections, pools, lazy init
        fg.call("Echo.Echo", b"x" * 1024)
    unloaded = sample(3.0)

    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(os.path.abspath(__file__))
    load_secs = 14
    bulk_code = (
        "import time\nfrom brpc_tpu.rpc import Channel\n"
        f"ch = Channel({addr!r}, timeout_ms=60000, "
        "connection_type='pooled', qos_tenant='bulk', qos_priority=3)\n"
        f"buf = b'b' * {bulk_bytes}\n"
        f"end = time.time() + {load_secs}\n"
        "while time.time() < end:\n    ch.call('Echo.Echo', buf)\n")
    flood_code = (
        "import time\nfrom brpc_tpu.rpc import Channel\n"
        f"ch = Channel({addr!r}, timeout_ms=2000, qos_tenant='bg', "
        "qos_priority=2)\n"
        f"end = time.time() + {load_secs}\n"
        "while time.time() < end:\n"
        "    try: ch.call('Echo.Echo', b'y' * 1024)\n"
        "    except Exception: pass\n")
    procs = [subprocess.Popen([sys.executable, "-c", bulk_code], env=env)
             for _ in range(2)]
    procs += [subprocess.Popen([sys.executable, "-c", flood_code], env=env)
              for _ in range(2)]
    time.sleep(3)  # let the bulk streams reach steady state
    loaded = sample(8.0)
    for p in procs:
        p.wait()
    fg.close()
    srv.stop()
    row = {
        "workload": "qos_mixed_1kb_hi_under_64mb_lo",
        "p99_unloaded_us": round(p99(unloaded)),
        "p99_loaded_us": round(p99(loaded)),
        "median_unloaded_us": round(statistics.median(unloaded)),
        "median_loaded_us": round(statistics.median(loaded)),
        "ratio_p99": round(p99(loaded) / max(p99(unloaded), 1.0), 3),
        "samples_loaded": len(loaded),
        # Lane/tenant config stamped on the row: a future run with a
        # different config must not be read as the same series.  The
        # timeline stamp (ISSUE 9) keeps flight-recorder-on runs out of
        # the comparable series too.
        "timeline": get_flag("trpc_timeline") == "true",
        "qos_lanes": lanes,
        "lane_weights": lane_weights,
        "qos_spec": bg_spec,
        "bulk_bytes": bulk_bytes,
        "bulk_streams": 2,
        "bg_flooders": 2,
    }
    print(json.dumps(row))


def _child_kv_disagg() -> None:
    """Disaggregated prefill/decode KV row (ISSUE 11): KV-block goodput
    measured WHILE the token-RPC p99 is sampled against the same prefill
    server — the two metrics must hold *simultaneously* (the qos_mixed
    HOL guard generalized to the real serving workload).  The prefill
    server, the decode block puller, and this sampler are three separate
    PROCESSES (tools/kv_disagg.py driver), so the row measures the
    server's isolation, not one interpreter's GIL.  The row stamps the
    rails/lanes/rma-path config it ran under, like every BENCH series."""
    import subprocess as sp

    repo = os.path.dirname(os.path.abspath(__file__))
    tool = os.path.join(repo, "tools", "kv_disagg.py")
    shape = os.path.join(repo, "tests", "data", "golden_mixed.cap")
    env = dict(os.environ)
    env["PYTHONPATH"] = repo
    cmd = [sys.executable, tool, "--json", "--seconds", "6"]
    if os.path.exists(shape):
        # ISSUE 17: the prefix-cache phase rides the same run, with the
        # tenant mix shaped by the golden capture's recorded shares.
        cmd += ["--shape", shape]
    out = sp.run(cmd, env=env, capture_output=True, text=True, timeout=240)
    for ln in out.stdout.splitlines()[::-1]:
        if ln.startswith("{"):
            print(ln, flush=True)
            return
    raise RuntimeError(f"kv_disagg produced no row:\n{out.stderr[-2000:]}")


def _child_infer_serving() -> None:
    """Streamed-inference front door row (ISSUE 20): the four-phase
    tools/load_orchestrator.py --infer cycle — ramp 100k logical token
    streams over a handful of connections (the fd proof), drain every
    one to EOS (zero wedged), measure client-observed TTFT/TPOT through
    the prefix cache (cached prompt blocks skip recompute), then shed a
    2x-overloaded hog tenant typed-only while the victim tenant's TPOT
    p99 stays within 2x unloaded.  One driver run IS the row — the
    perf-smoke gate (BENCH_INFER_STREAMS scaled down) asserts the same
    measurement bench publishes."""
    import subprocess as sp

    repo = os.path.dirname(os.path.abspath(__file__))
    tool = os.path.join(repo, "tools", "load_orchestrator.py")
    env = dict(os.environ)
    env["PYTHONPATH"] = repo
    streams = os.environ.get("BENCH_INFER_STREAMS", "100000")
    out = sp.run([sys.executable, tool, "--infer", "--json",
                  "--infer-streams", streams, "--seconds", "6"],
                 env=env, capture_output=True, text=True, timeout=560)
    for ln in out.stdout.splitlines()[::-1]:
        if ln.startswith("{"):
            print(ln, flush=True)
            return
    raise RuntimeError(
        f"infer orchestrator produced no row:\n{out.stderr[-2000:]}")


def _child_pipeline_overlap() -> None:
    """Pipeline-parallel overlapped dataflow row (ISSUE 18): a 4-member
    fleet runs M microbatches of real jax CPU gradient compute whose
    reduce-scatter/all-gather rides UNDER the next microbatch's compute
    — transfers fire per-chunk as the producer stamps a readiness map
    (trpc_coll_overlap) instead of waiting for a whole-buffer barrier.
    Headline metric: overlap_efficiency = step_time / max(compute,
    comm) (1.0 = perfect overlap) plus the speedup over the sequential
    compute-then-communicate baseline of the SAME dataflow (acceptance
    ≥ 1.25x, byte-exact).  Driver is tools/pipeline_step.py so the row
    measures the multi-threaded fleet, not this interpreter's state."""
    import subprocess as sp

    repo = os.path.dirname(os.path.abspath(__file__))
    tool = os.path.join(repo, "tools", "pipeline_step.py")
    env = dict(os.environ)
    env["PYTHONPATH"] = repo
    env.setdefault("JAX_PLATFORMS", "cpu")
    cmd = [sys.executable, tool, "--json"]
    out = sp.run(cmd, env=env, capture_output=True, text=True, timeout=240)
    for ln in out.stdout.splitlines()[::-1]:
        if ln.startswith("{"):
            print(ln, flush=True)
            return
    raise RuntimeError(
        f"pipeline_step produced no row:\n{out.stderr[-2000:]}")


def _child_collective() -> None:
    """Collective-fabric row (ISSUE 13): a 4-member in-process fleet
    all-gathers 64MB shards over shm — every transfer a pull whose
    one-sided put lands DIRECT in the getter's registered buffer — and
    a reshard moves an overlapping source→target sharding pair through
    the planned minimal schedule.  Headline metrics: all-gather per-link
    GB/s ((n-1)·shard / wall per member link; acceptance ≥ 3.8, half the
    point-to-point one-sided 64MB put baseline) and reshard GB/s over
    the bytes the plan actually moves — stamped with the plan's
    moved/reused/naive bytes so the 2112.01075 minimality is in the
    artifact, plus the rpc_path/chunk/inflight config like every BENCH
    series."""
    import threading

    import numpy as np

    from brpc_tpu.rpc import (Server, collective, get_flag, observe, rma)

    n = 4
    shard = 64 << 20
    srvs = []
    for _ in range(n):
        s = Server()
        s.enable_collective()
        s.start(0)
        srvs.append(s)
    members = [f"127.0.0.1:{s.port}" for s in srvs]
    groups = [collective.Group(members, r, timeout_ms=60000)
              for r in range(n)]
    seq = [0]

    def run_all(fn):
        seq[0] += 1
        errs = [None] * n

        def go(r):
            try:
                fn(r, seq[0])
            except Exception as e:  # noqa: BLE001 — surfaced below
                errs[r] = e

        threads = [threading.Thread(target=go, args=(r,))
                   for r in range(n)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(180)
        dt = time.perf_counter() - t0
        if any(errs):
            raise RuntimeError(f"collective bench member failed: {errs}")
        return dt

    # --- all_gather leg ---
    sends = [rma.RmaBuffer(shard) for _ in range(n)]
    recvs = [rma.RmaBuffer(n * shard) for _ in range(n)]
    for r in range(n):
        np.frombuffer(memoryview(sends[r].view),
                      dtype=np.uint8)[:] = (r + 1)

    def ag(r, s):
        groups[r].all_gather(sends[r], recvs[r], shard_bytes=shard,
                             run_seq=s)

    run_all(ag)  # warm: rings, windows, peer mappings
    rx0 = observe.Vars.dump().get("rma_rx_msgs", 0)
    iters = 5
    t0 = time.perf_counter()
    for _ in range(iters):
        run_all(ag)
    dt = (time.perf_counter() - t0) / iters
    rma_path = observe.Vars.dump().get("rma_rx_msgs", 0) > rx0
    verified = all(
        np.all(np.frombuffer(memoryview(recvs[r].view),
                             dtype=np.uint8)[src * shard:(src + 1) * shard]
               == src + 1)
        for r in range(n) for src in range(n))
    ag_row = {
        "members": n,
        "shard_bytes": shard,
        "ms_per_iter": round(dt * 1e3, 1),
        "per_link_gbps": round((n - 1) * shard / dt / 1e9, 3),
        "aggregate_gbps": round(n * (n - 1) * shard / dt / 1e9, 3),
        "rpc_path": "rma" if rma_path else "copy",
        "verified": verified,
    }
    for b in sends + recvs:
        b.free()

    # --- reshard leg: overlapping shardings, only boundary strips move ---
    total = n * shard
    q = total // n
    shift = 16 << 20
    src_ranges = [(r, r * q, q) for r in range(n)]
    dst_ranges = ([(0, 0, q + shift)] +
                  [(r, r * q + shift, q) for r in range(1, n - 1)] +
                  [(n - 1, (n - 1) * q + shift, q - shift)])
    plan = collective.plan_reshard_bytes(src_ranges, dst_ranges, total, n)
    sbufs = [rma.RmaBuffer(q) for _ in range(n)]
    dlens = [q + shift] + [q] * (n - 2) + [q - shift]
    rbufs = [rma.RmaBuffer(ln) for ln in dlens]

    def rs(r, s):
        groups[r].reshard(src_ranges, dst_ranges, total, sbufs[r],
                          rbufs[r], run_seq=s)

    run_all(rs)  # warm
    iters = 4
    t0 = time.perf_counter()
    for _ in range(iters):
        run_all(rs)
    dt = (time.perf_counter() - t0) / iters
    reshard_row = {
        "members": n,
        "total_bytes": total,
        "bytes_moved": plan["bytes_moved"],
        "bytes_reused": plan["bytes_reused"],
        "naive_bytes": plan["naive_bytes"],
        "minimal": plan["bytes_moved"] < plan["naive_bytes"],
        "ms_per_iter": round(dt * 1e3, 1),
        "moved_gbps": round(plan["bytes_moved"] / dt / 1e9, 3),
    }
    row = {
        "workload": "collective",
        "all_gather": ag_row,
        "reshard": reshard_row,
        "chunk_bytes": int(get_flag("trpc_coll_chunk_bytes")),
        "inflight": int(get_flag("trpc_coll_inflight")),
        "timeline": get_flag("trpc_timeline") == "true",
    }
    for g in groups:
        g.close()
    for b in sbufs + rbufs:
        b.free()
    for s in srvs:
        s.stop()
    print(json.dumps(row), flush=True)


def _child_slo_fleet() -> None:
    """Fleet-observability row (ISSUE 19): a 3-node in-process fleet —
    every node an SLO-armed echo server publishing its digest+SLO blob
    into a naming registry — serves the golden-capture tenant mix
    (tests/data/golden_mixed.cap: fg 1KB foreground + bulk large), and
    the row reports the MERGED per-tenant view (/fleet body) against a
    pooled-digest oracle built from the very blobs the nodes published
    (p99_oracle_ratio; acceptance <= 2.0, the octave bound), the 1KB
    QPS with the publisher ON vs OFF (publication must ride the
    Announcer's renew cadence, not the request path), and the time for
    an induced latency regression on ONE node to flip that tenant's
    burn-rate alert (breach_detect_ms; acceptance <= one fast window)."""
    from brpc_tpu.rpc import Channel, Server, get_flag, observe, set_flag
    from brpc_tpu.rpc.capture import load_capture
    from brpc_tpu.rpc.naming import NamingClient

    repo = os.path.dirname(os.path.abspath(__file__))
    fast_ms = 1500
    saved = {f: get_flag(f) for f in
             ("trpc_slo", "trpc_fleet_publish", "trpc_slo_fast_window_ms",
              "trpc_slo_slow_window_ms", "trpc_naming_lease_ms")}
    set_flag("trpc_slo_fast_window_ms", str(fast_ms))
    set_flag("trpc_slo_slow_window_ms", "8000")
    set_flag("trpc_naming_lease_ms", "400")
    observe.enable_slo(True)
    observe.enable_fleet_publish(False)

    spec = ("fg:p99_us=5000,avail=99.0;bulk:p99_us=200000,avail=99.0;"
            "*:p99_us=100000")
    registry = Server()
    registry.enable_naming_registry()
    registry.start(0)
    reg_addr = f"127.0.0.1:{registry.port}"
    srvs = []
    for _ in range(3):
        s = Server()
        s.register_native_echo("Echo.Echo")
        s.set_slo(spec)
        s.start(0)
        srvs.append(s)
    addrs = [f"127.0.0.1:{s.port}" for s in srvs]
    chans = {}

    def chan(node: int, tenant: str) -> Channel:
        key = (node, tenant)
        if key not in chans:
            chans[key] = Channel(addrs[node], timeout_ms=10000,
                                 qos_tenant=tenant)
        return chans[key]

    def qps_1kb(seconds: float = 1.2) -> float:
        # Untagged (scored under '*'): the probe volume must not drown
        # tenant fg's burn windows before the breach-detection leg.
        ch = chan(0, "")
        body = b"q" * 1024
        for _ in range(30):
            ch.call("Echo.Echo", body)
        n = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            ch.call("Echo.Echo", body)
            n += 1
        return n / (time.perf_counter() - t0)

    # Publisher OFF vs ON, interleaved best-of-2 each: publication rides
    # the Announcer's renew thread, so the request path must not notice.
    qps_off = qps_1kb()
    observe.enable_fleet_publish(True)
    for i, s in enumerate(srvs):
        s.announce(reg_addr, "fleet", zone=f"z{i}")
    time.sleep(0.6)  # a few renew rounds so publication is in flight
    qps_on = qps_1kb()
    observe.enable_fleet_publish(False)
    qps_off = max(qps_off, qps_1kb())
    observe.enable_fleet_publish(True)
    qps_on = max(qps_on, qps_1kb())

    # The golden-capture tenant mix, striped across the 3 nodes.
    _, records = load_capture(
        os.path.join(repo, "tests", "data", "golden_mixed.cap"))
    driven = {}
    for i, r in enumerate(records[:600]):
        tenant = r.tenant or "fg"
        size = min(max(int(r.request_bytes), 1), 64 << 10)
        chan(i % 3, tenant).call("Echo.Echo", b"m" * size)
        driven[tenant] = driven.get(tenant, 0) + 1

    # Wait until every node's published blob covers the traffic, then
    # build the pooled oracle FROM those blobs (the single-recorder
    # ground truth the octave bound is stated against).
    nc = NamingClient(reg_addr)
    deadline = time.time() + 30
    decoded = []
    while time.time() < deadline:
        _, recs = nc.stats("fleet")
        blobs = [r.payload for r in recs if r.payload]
        if len(blobs) == 3:
            decoded = [observe.fleet_blob_decode(b) for b in blobs]
            fg = [t for d in decoded for t in d["tenants"]
                  if t["tenant"] == "fg"]
            if sum(t["slow_total"] for t in fg) >= driven.get("fg", 0):
                break
        time.sleep(0.2)
    if len(decoded) != 3:
        raise RuntimeError("fleet blobs never covered the driven traffic")
    pooled = {}
    for d in decoded:
        for t in d["tenants"]:
            dg = t["digest"]
            if t["tenant"] in pooled:
                observe.digest_merge(pooled[t["tenant"]], dg)
            else:
                pooled[t["tenant"]] = dg

    view = observe.fleet_dump("fleet")
    tenants = []
    worst_ratio = 0.0
    for row in view["tenants"]:
        oracle = pooled.get(row["tenant"])
        if oracle is None or oracle.count == 0:
            continue
        oracle_p99 = observe.digest_percentile_us(oracle, 0.99)
        ratio = (max(row["p99_us"], oracle_p99)
                 / max(min(row["p99_us"], oracle_p99), 1))
        worst_ratio = max(worst_ratio, ratio)
        tenants.append({
            "tenant": row["tenant"], "nodes": row["nodes"],
            "rate": row["rate"], "p50_us": row["p50_us"],
            "p99_us": row["p99_us"], "oracle_p99_us": oracle_p99,
            "p99_oracle_ratio": round(ratio, 3),
            "error_rate": row["error_rate"],
            "budget_remaining": row["budget_remaining"],
            "burn_fast": row["burn_fast"], "burn_slow": row["burn_slow"],
        })

    # Induced regression on ONE node: time-to-alert for tenant fg.
    srvs[0].set_faults("svr_delay=1:25")
    ch = chan(0, "fg")
    t0 = time.perf_counter()
    breach_detect_ms = None
    while time.perf_counter() - t0 < fast_ms / 1000 * 4:
        ch.call("Echo.Echo", b"d" * 1024)
        fg_row = [t for t in srvs[0].slo_dump()["tenants"]
                  if t["tenant"] == "fg"]
        if fg_row and fg_row[0]["breached"]:
            breach_detect_ms = round((time.perf_counter() - t0) * 1e3, 1)
            break
    srvs[0].set_faults("")

    row = {
        "workload": "slo_fleet",
        "nodes": 3,
        "capture": "tests/data/golden_mixed.cap",
        "calls_driven": sum(driven.values()),
        "tenant_mix": driven,
        "tenants": tenants,
        "p99_oracle_ratio_worst": round(worst_ratio, 3),
        "qps_1kb_publish_off": round(qps_off, 1),
        "qps_1kb_publish_on": round(qps_on, 1),
        "publish_qps_ratio": round(qps_on / max(qps_off, 1e-9), 3),
        "breach_detect_ms": breach_detect_ms,
        "fast_window_ms": fast_ms,
    }
    for c in chans.values():
        c.close()
    for s in srvs:
        s.stop()
    registry.stop()
    for f, v in saved.items():
        set_flag(f, v)
    print(json.dumps(row), flush=True)


def _child_self_tune() -> None:
    """Self-tuning row (ISSUE 14 / ROADMAP item 4): each leg measures a
    workload hand-tuned (compiled defaults, tuner off), then re-runs it
    from DELIBERATELY-WRONG flags with the tuner ON and reports the
    recovery ratio (tuned/hand for throughput, hand/tuned for latency),
    the per-second recovery trajectory, the converged knob values, and
    the decision counts — the `tuner:` stamp that makes a tuning run a
    comparable BENCH series.  Wrong seeds, chosen for measured damage
    on this box: stripe chunk 64KB + 1 rail (~5x off on 64MB striped),
    messenger cut budget 64KB (the AIMD growth path on the 1KB and
    qos_mixed rows).  All knob movement goes through the validated
    reload path; defaults are restored between legs."""
    import numpy as np

    from brpc_tpu.rpc import (Channel, Server, get_flag, observe,
                              set_flag, tuner)

    TUNER_INTERVAL_MS = 50
    TUNER_EVAL_TICKS = 2

    defaults = {f["name"]: f["default"] for f in observe.flags()}
    # Every knob the controller can actuate: restored wholesale between
    # legs, so a side-effect move in one leg (e.g. the budget rule
    # firing on the striped leg's yields) can never contaminate the
    # next leg's hand-tuned baseline.
    tuner_knobs = [
        "trpc_stripe_chunk_bytes", "trpc_stripe_rails",
        "trpc_messenger_cut_budget", "trpc_rma_window_bytes",
        "trpc_qos_lane_weights",
    ]

    def restore(names):
        for n in names:
            set_flag(n, defaults[n])

    def tuner_on():
        set_flag("trpc_tuner_interval_ms", str(TUNER_INTERVAL_MS))
        set_flag("trpc_tuner_eval_ticks", str(TUNER_EVAL_TICKS))
        tuner.enable_tuner(True)

    def tuner_off():
        tuner.enable_tuner(False)
        restore(["trpc_tuner_interval_ms", "trpc_tuner_eval_ticks"])

    def pipeline_rate(size, depth, seconds):
        """Loopback echo through the batch pipeline; returns (per-second
        completion buckets, completions/s over the final 3 buckets)."""
        srv = Server()
        srv.register_native_echo("Echo.Echo")
        srv.start(0)
        conn = "pooled" if size >= (1 << 20) else "single"
        ch = Channel(f"127.0.0.1:{srv.port}", timeout_ms=60000,
                     connection_type=conn)
        payload = np.zeros(size, dtype=np.uint8)
        pipe = ch.pipeline()
        free = [np.empty(size, dtype=np.uint8) for _ in range(depth)]
        t2b: dict = {}

        def submit(k):
            bs = [free.pop() for _ in range(k)]
            toks = pipe.submit("Echo.Echo", [payload] * k, resp_bufs=bs)
            t2b.update(zip(toks, bs))

        try:
            submit(depth)
            t0 = time.perf_counter()
            done = last_done = 0
            last = t0
            buckets = []
            while time.perf_counter() - t0 < seconds:
                cs = pipe.poll(max_n=depth, timeout_ms=60000)
                if not cs:
                    raise RuntimeError("self_tune pipeline wedged")
                for c in cs:
                    if not c.ok:
                        raise RuntimeError(f"self_tune member failed: {c}")
                    free.append(t2b.pop(c.token))
                    done += 1
                submit(len(cs))
                now = time.perf_counter()
                if now - last >= 1.0:
                    buckets.append((done - last_done) / (now - last))
                    last, last_done = now, done
            while t2b:
                for c in pipe.poll(max_n=depth, timeout_ms=60000):
                    free.append(t2b.pop(c.token))
        finally:
            pipe.close()
            ch.close()
            srv.stop()
        tail = buckets[-3:] if len(buckets) >= 3 else buckets
        return buckets, sum(tail) / len(tail)

    legs = {}
    decisions_before = 0

    def leg_decisions():
        nonlocal decisions_before
        now = tuner.counters()["decisions"]
        n, decisions_before = now - decisions_before, now
        return n

    # ---- leg 1: 64MB striped goodput --------------------------------
    size = 64 << 20
    stripe_knobs = ["trpc_stripe_chunk_bytes", "trpc_stripe_rails"]
    _, hand_rate = pipeline_rate(size, depth=4, seconds=5)
    hand_gbps = hand_rate * size / 1e9
    set_flag("trpc_stripe_chunk_bytes", "65536")
    set_flag("trpc_stripe_rails", "1")
    tuner_on()
    traj, tuned_rate = pipeline_rate(size, depth=4, seconds=14)
    tuner_off()
    tuned_gbps = tuned_rate * size / 1e9
    legs["striped_64mb"] = {
        "metric": "goodput_gbps",
        "hand": round(hand_gbps, 3),
        "wrong_flags": {"trpc_stripe_chunk_bytes": 65536,
                        "trpc_stripe_rails": 1},
        "tuned": round(tuned_gbps, 3),
        "recovery": round(tuned_gbps / hand_gbps, 3),
        "trajectory_gbps": [round(b * size / 1e9, 2) for b in traj],
        "converged": {k: int(get_flag(k)) for k in stripe_knobs},
        "decisions": leg_decisions(),
    }
    restore(tuner_knobs)

    # ---- leg 2: 1KB pipelined QPS -----------------------------------
    _, hand_qps = pipeline_rate(1024, depth=256, seconds=5)
    set_flag("trpc_messenger_cut_budget", "65536")
    tuner_on()
    traj, tuned_qps = pipeline_rate(1024, depth=256, seconds=10)
    tuner_off()
    legs["one_kb"] = {
        "metric": "qps",
        "hand": round(hand_qps),
        "wrong_flags": {"trpc_messenger_cut_budget": 65536},
        "tuned": round(tuned_qps),
        "recovery": round(tuned_qps / hand_qps, 3),
        "trajectory_qps": [round(b) for b in traj],
        "converged": {"trpc_messenger_cut_budget":
                      int(get_flag("trpc_messenger_cut_budget"))},
        "decisions": leg_decisions(),
    }
    restore(tuner_knobs)

    # ---- leg 3: qos_mixed fg p99 under bulk saturation --------------
    set_flag("trpc_qos_lanes", "4")
    srv = Server()
    srv.register_native_echo("Echo.Echo")
    srv.set_qos("bg:weight=1,limit=4;*:limit=10000")
    srv.start(0)
    addr = f"127.0.0.1:{srv.port}"
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(os.path.abspath(__file__))
    load_secs = 26
    bulk_code = (
        "import time\nfrom brpc_tpu.rpc import Channel\n"
        f"ch = Channel({addr!r}, timeout_ms=60000, "
        "connection_type='pooled', qos_tenant='bulk', qos_priority=3)\n"
        f"buf = b'b' * {64 << 20}\n"
        f"end = time.time() + {load_secs}\n"
        "while time.time() < end:\n    ch.call('Echo.Echo', buf)\n")
    procs = [subprocess.Popen([sys.executable, "-c", bulk_code], env=env)
             for _ in range(2)]
    fg = Channel(addr, timeout_ms=20000, qos_tenant="fg", qos_priority=0)

    def p99(seconds):
        lat = []
        for _ in range(100):
            fg.call("Echo.Echo", b"x" * 1024)
        end = time.perf_counter() + seconds
        while time.perf_counter() < end:
            t0 = time.perf_counter()
            fg.call("Echo.Echo", b"x" * 1024)
            lat.append((time.perf_counter() - t0) * 1e6)
        lat.sort()
        return lat[len(lat) * 99 // 100], len(lat)

    try:
        time.sleep(2.5)  # bulk streams to steady state
        hand_p99, hand_n = p99(5.0)
        set_flag("trpc_messenger_cut_budget", "65536")
        tuner_on()
        time.sleep(3.0)  # convergence window under live load
        tuned_p99, tuned_n = p99(5.0)
        tuner_off()
    finally:
        fg.close()
        for p in procs:  # measurements done: don't idle out their timer
            p.terminate()
        for p in procs:
            p.wait()
        srv.stop()
    legs["qos_mixed"] = {
        "metric": "fg_p99_us",
        "hand": round(hand_p99),
        "wrong_flags": {"trpc_messenger_cut_budget": 65536},
        "tuned": round(tuned_p99),
        # Latency: recovery = hand/tuned (1.0 = fully recovered;
        # >1 = the tuned box beat the hand numbers).
        "recovery": round(hand_p99 / max(tuned_p99, 1.0), 3),
        "samples": {"hand": hand_n, "tuned": tuned_n},
        "converged": {"trpc_messenger_cut_budget":
                      int(get_flag("trpc_messenger_cut_budget"))},
        "decisions": leg_decisions(),
    }
    restore(tuner_knobs + ["trpc_qos_lanes"])

    row = {
        "workload": "self_tune",
        "tuner": {"interval_ms": TUNER_INTERVAL_MS,
                  "eval_ticks": TUNER_EVAL_TICKS,
                  "counters": tuner.counters()},
        "legs": legs,
        "timeline": get_flag("trpc_timeline") == "true",
    }
    print(json.dumps(row), flush=True)


def _child_rolling_restart() -> None:
    """Cluster control-plane row (ISSUE 12): drain + hot-restart one
    node of a 3-node naming-backed cluster under mixed 1KB + striped
    load and KV pulls (tools/load_orchestrator.py --rolling-restart,
    separate hub/node/successor/worker PROCESSES).  Stamps the
    client-visible error count (acceptance: 0), the drain-window p99
    against steady state (acceptance: <= 2x), and the stale-KV-admit
    count (acceptance: 0) — the zero-downtime restart headline."""
    import subprocess as sp

    tool = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "load_orchestrator.py")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(os.path.abspath(__file__))
    env.setdefault("JAX_PLATFORMS", "cpu")
    out = sp.run([sys.executable, tool, "--rolling-restart", "--json",
                  "--seconds", "6", "--big-every", "50",
                  "--big-bytes", str(1 << 20)],
                 env=env, capture_output=True, text=True, timeout=240)
    for ln in out.stdout.splitlines()[::-1]:
        if ln.startswith("{"):
            print(ln, flush=True)
            return
    raise RuntimeError(
        f"rolling_restart produced no row:\n{out.stderr[-2000:]}")


def _child_replay() -> None:
    """Capture-and-replay regression row (ISSUE 16).  Records a mixed-
    tenant window on a QoS-laned server (fg 1KB echo — every 5th under a
    deadline scope — concurrent with a bulk tenant moving striped 16MB
    bodies from its own process), dumps the capture, then regresses two
    planes against it:

    exact leg — tools/traffic_replay.py re-offers the window open-loop
    at the recorded inter-arrival times with tenant/priority/deadline
    re-stamped; the capture tier stays armed through the replay, so the
    row compares the REPLAYED window's server-side per-tenant p99/rate
    against the RECORDED baseline apples-to-apples (acceptance: rate
    within 10%, p99 <= 2x, zero untyped errors).

    stat leg — statistical mode at 2x the fitted rate, composed with
    server-side chaos (svr_delay): shed-don't-degrade, i.e. every error
    is a typed shed (kELimit/kEOverloaded/kEDraining/kEDeadlineExpired),
    never an untyped failure."""
    import tempfile

    from brpc_tpu.rpc import Channel, Server, set_flag
    from brpc_tpu.rpc import capture as cap

    tool = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "traffic_replay.py")
    bulk_bytes = 16 << 20
    lanes = 4
    qos_spec = "fg:weight=8,limit=16;bulk:weight=1,limit=64;*:limit=10000"
    set_flag("trpc_qos_lanes", str(lanes))
    srv = Server()
    srv.register_native_echo("Echo.Echo")
    srv.set_qos(qos_spec)
    srv.start(0)
    addr = f"127.0.0.1:{srv.port}"

    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(os.path.abspath(__file__))

    # ---- record the mixed-tenant window -------------------------------
    # Load generators run in their OWN processes, TWO fg senders + one
    # bulk, matching the replay side's two worker processes — the
    # recorded baseline and the replayed window then see the same client
    # concurrency, so the p99 comparison is apples-to-apples.
    fg = Channel(addr, timeout_ms=5000, qos_tenant="fg", qos_priority=0)
    small = b"x" * 1024
    for _ in range(50):  # warm: connections, pools, lazy init
        fg.call("Echo.Echo", small)
    fg.close()
    record_secs = 5.0
    # Bulk is recorded OPEN-LOOP (Batch, fixed 100ms cadence, bounded
    # in-flight) — the replayer is open-loop too, so a closed-loop
    # recording would hand it a baseline that never self-overlaps and
    # every replayed overlap would read as a regression.
    bulk_code = (
        "import time\nfrom brpc_tpu.rpc import Batch, Channel\n"
        f"ch = Channel({addr!r}, timeout_ms=60000, "
        "connection_type='pooled', qos_tenant='bulk', qos_priority=3)\n"
        "b = Batch(ch)\n"
        f"buf = b'b' * {bulk_bytes}\n"
        f"end = time.time() + {record_secs}\n"
        "next_t = time.time()\n"
        "pending = 0\n"
        "while time.time() < end:\n"
        "    if time.time() >= next_t and pending < 4:\n"
        "        b.submit('Echo.Echo', [buf], timeout_ms=60000)\n"
        "        pending += 1\n"
        "        next_t += 0.1\n"
        "    pending -= len(b.poll(max_n=8, timeout_ms=10))\n"
        "while pending > 0:\n"
        "    got = len(b.poll(max_n=8, timeout_ms=1000))\n"
        "    if not got:\n        break\n"
        "    pending -= got\n"
        "b.close()\nch.close()\n")
    fg_code = (
        "import time\n"
        "from brpc_tpu.rpc import Channel, deadline_scope\n"
        f"ch = Channel({addr!r}, timeout_ms=5000, qos_tenant='fg', "
        "qos_priority=0)\n"
        "buf = b'x' * 1024\n"
        f"end = time.time() + {record_secs}\n"
        "i = 0\n"
        "while time.time() < end:\n"
        "    try:\n"
        "        if i % 5 == 0:\n"
        "            with deadline_scope(500):\n"
        "                ch.call('Echo.Echo', buf)\n"
        "        else:\n"
        "            ch.call('Echo.Echo', buf)\n"
        "    except Exception:\n"
        "        pass\n"
        "    i += 1\n"
        "    time.sleep(0.002)\n")
    cap.enable_capture(True)
    cap.reset_capture()
    procs = [subprocess.Popen([sys.executable, "-c", bulk_code], env=env)]
    procs += [subprocess.Popen([sys.executable, "-c", fg_code], env=env)
              for _ in range(2)]
    for p in procs:
        p.wait(timeout=120)
    recorded = cap.summary()
    cap_path = tempfile.mktemp(prefix="bench_replay_", suffix=".cap")
    n_records = cap.dump(cap_path)

    def _tool_row(extra: list) -> dict:
        out = subprocess.run(
            [sys.executable, tool, "--addr", addr, "--capture", cap_path,
             "--workers", "2", "--default-timeout-ms", "30000", *extra],
            env=env, capture_output=True, text=True, timeout=240)
        for ln in out.stdout.splitlines()[::-1]:
            if ln.startswith("{"):
                return json.loads(ln)
        raise RuntimeError(f"replayer produced no row:\n{out.stderr[-2000:]}")

    # ---- exact leg: capture stays armed to measure the replayed window
    cap.reset_capture()
    exact = _tool_row([])
    replayed = cap.summary()

    tenants = {}
    worst_p99_ratio = 0.0
    worst_rate_dev = 0.0
    for t, rec_t in recorded["summary"].get("tenants", {}).items():
        rep_t = replayed["summary"].get("tenants", {}).get(t, {})
        ex_t = exact.get("tenants", {}).get(t, {})
        p99_ratio = (rep_t.get("p99_us", 0) /
                     max(rec_t.get("p99_us", 0), 1.0))
        rate_ratio = (rep_t.get("est_rate_rps", 0.0) /
                      max(rec_t.get("est_rate_rps", 0.0), 1e-9))
        worst_p99_ratio = max(worst_p99_ratio, p99_ratio)
        worst_rate_dev = max(worst_rate_dev, abs(1.0 - rate_ratio))
        tenants[t] = {
            "recorded_p99_us": rec_t.get("p99_us", 0),
            "replayed_p99_us": rep_t.get("p99_us", 0),
            "p99_ratio": round(p99_ratio, 3),
            "recorded_rate_rps": round(rec_t.get("est_rate_rps", 0.0), 1),
            "replayed_rate_rps": round(rep_t.get("est_rate_rps", 0.0), 1),
            "rate_ratio": round(rate_ratio, 3),
            "client_errors": ex_t.get("errors", {}),
        }

    # ---- stat leg: 2x fitted rate + server-side chaos -----------------
    srv.set_faults("svr_delay=1:20")
    cap.reset_capture()
    stat = _tool_row(["--mode", "stat", "--rate-scale", "2.0",
                      "--duration", "4"])
    srv.set_faults("")
    stat_sheds = sum(sum(t.get("errors", {}).values())
                     for t in stat.get("tenants", {}).values())
    stat_sent = sum(t.get("sent", 0) for t in stat.get("tenants", {}).values())

    cap.enable_capture(False)
    try:
        os.unlink(cap_path)
    except OSError:
        pass
    srv.stop()
    print(json.dumps({
        "workload": "capture_replay_mixed_tenant",
        "captured_records": n_records,
        "capture_window_us": recorded["summary"].get("window_us", 0),
        "burstiness_cv": recorded["summary"].get("burstiness_cv", 0.0),
        "tenants": tenants,
        "worst_p99_ratio": round(worst_p99_ratio, 3),
        "worst_rate_deviation": round(worst_rate_dev, 3),
        "exact_untyped_errors": exact.get("untyped_errors", -1),
        "exact_typed_only": exact.get("typed_errors_only", False),
        "stat_rate_scale": 2.0,
        "stat_chaos": "svr_delay=1:20",
        "stat_sent": stat_sent,
        "stat_sheds": stat_sheds,
        "stat_errors": {t: d.get("errors", {})
                        for t, d in stat.get("tenants", {}).items()},
        "stat_untyped_errors": stat.get("untyped_errors", -1),
        "stat_typed_only": stat.get("typed_errors_only", False),
        "qos_lanes": lanes,
        "qos_spec": qos_spec,
        "bulk_bytes": bulk_bytes,
    }))


def _child_zerocopy() -> None:
    """Loopback RPC echo, three Python-boundary strategies at 4MB: the
    per-call bytes-copy path, the per-call dlpack zero-copy path, and the
    headline — the 8-deep batched pipeline (one GIL crossing per batch,
    zero-copy both directions).  All three run against a NATIVE echo
    server so the numbers measure the client data plane, not the server's
    GIL (the r05 row measured a Python handler on the far side)."""
    import numpy as np

    from brpc_tpu.rpc import zerocopy
    from brpc_tpu.rpc.client import Channel
    from brpc_tpu.rpc.server import Server

    depth = int(os.environ.get("BENCH_PIPELINE_DEPTH", "8"))
    srv = Server()
    srv.register_native_echo("Echo.Echo")
    srv.start(0)
    ch = Channel(f"127.0.0.1:{srv.port}", timeout_ms=10000)
    size = 4 << 20
    payload = np.arange(size // 4, dtype=np.uint32)
    iters = 30

    ch.call("Echo.Echo", payload.tobytes())  # warm both directions
    zerocopy.call_zero_copy(ch, "Echo.Echo", payload)

    t0 = time.perf_counter()
    for _ in range(iters):
        ch.call("Echo.Echo", payload.tobytes())
    copied_dt = time.perf_counter() - t0

    t0 = time.perf_counter()
    for _ in range(iters):
        zerocopy.call_zero_copy(ch, "Echo.Echo", payload)
    zc_dt = time.perf_counter() - t0
    ch.close()

    batched = _rpc_batch_goodput(size, depth=depth, target_s=1.5)
    row = {
        "kind": "py_loopback_4MB",
        "server": "native_echo",
        "copied_gbps": round(size * iters / copied_dt / 1e9, 3),
        "percall_zerocopy_gbps": round(size * iters / zc_dt / 1e9, 3),
        # Headline: the pipelined zero-copy plane (ISSUE 3 acceptance:
        # >= 1.5 GB/s at 4MB x 8-deep vs 0.293 per-call in r05).
        "zerocopy_gbps": batched["goodput_gbps"] if batched else None,
        "pipeline_depth": depth,
        "bytes_moved_per_iter": size * depth,
        "vars": (batched or {}).get("vars") or _observe_snapshot(),
    }
    print(json.dumps(row), flush=True)
    srv.stop()


# --------------------------------------------------------------- parent ----

def _cpp_rows() -> list:
    """Loopback numbers from the C++ runtime (multi_threaded_echo analogue);
    builds the binary on demand (works without cmake), else skips."""
    exe = os.path.join(os.path.dirname(os.path.abspath(__file__)), "build",
                       "bench_echo")
    try:
        from brpc_tpu.rpc._lib import ensure_bench_echo

        exe = str(ensure_bench_echo())
    except Exception:  # noqa: BLE001 — fall back to a prebuilt binary
        if not os.path.exists(exe):
            return []
    rows = []
    # Small-payload rows cover single AND multi-connection (pooled) so the
    # wait-free hot path (inline writes, batched dispatch, bulk wakeups)
    # is tracked per round; large rows guard against coalescing
    # regressions on the throughput path.
    for fibers, payload, conn in (
        (64, 1024, "single"),
        (64, 1024, "pooled"),
        (256, 1024, "pooled"),
        (8, 2 << 20, "single"),
        (8, 2 << 20, "pooled"),
        # Native anchor for the Python batch leg: same 4MB x 8-deep
        # geometry the zerocopy pipeline row runs, all-native — the gap
        # between the two IS the Python-boundary cost per round.
        (8, 4 << 20, "pooled"),
        # Mid-large band (ISSUE 5): the striped multi-rail path at native
        # sync-call geometry — the row the monolithic-frame collapse
        # (407 MB/s in r05) used to hide in.
        (8, 16 << 20, "pooled"),
    ):
        try:
            out = subprocess.run(
                [exe, str(fibers), str(payload), "2", conn],
                capture_output=True, text=True, timeout=60,
            )
            line = out.stdout.strip().splitlines()[-1]
            rows.append(json.loads(line))
        except Exception:  # noqa: BLE001 — bench must still print its line
            pass
    return rows


def _run_device_child(env_flags: dict[str, str], timeout: float) -> list[dict]:
    """Runs one device leg as a child of this script and returns its JSON
    rows.  The child is waited for — at the timeout it is killed AND
    reaped — so no two processes ever want the chip at once.  A leg that
    fails fails the bench: there is no other platform to re-run it on."""
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__)],
        env={**os.environ, **env_flags}, capture_output=True, text=True,
        timeout=timeout)
    if out.returncode != 0:
        raise SystemExit(
            f"device leg {env_flags} exited {out.returncode}:\n"
            f"{out.stderr[-4000:]}")
    return [json.loads(ln) for ln in out.stdout.splitlines()
            if ln.startswith("{")]


def _run_json_child(env_flags: dict[str, str], timeout: float) -> dict | None:
    """Runs this script as a host-only child with `env_flags` set; returns
    its last JSON line, or None when it produced none."""
    if timeout < 10:
        return None
    try:
        env = dict(os.environ)
        env.update(env_flags)
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__)], env=env,
            capture_output=True, text=True, timeout=timeout,
            start_new_session=True)
        for ln in out.stdout.splitlines()[::-1]:
            if ln.startswith("{"):
                return json.loads(ln)
    except Exception:  # noqa: BLE001 — bench must still print its line
        pass
    return None


def main() -> None:
    if os.environ.get("BENCH_ZC"):
        _child_zerocopy()
        return
    if os.environ.get("BENCH_QOS"):
        _child_qos_mixed()
        return
    if os.environ.get("BENCH_KV"):
        _child_kv_disagg()
        return
    if os.environ.get("BENCH_INFER"):
        _child_infer_serving()
        return
    if os.environ.get("BENCH_RR"):
        _child_rolling_restart()
        return
    if os.environ.get("BENCH_REPLAY"):
        _child_replay()
        return
    if os.environ.get("BENCH_COLL"):
        _child_collective()
        return
    if os.environ.get("BENCH_OVERLAP"):
        _child_pipeline_overlap()
        return
    if os.environ.get("BENCH_SLO_FLEET"):
        _child_slo_fleet()
        return
    if os.environ.get("BENCH_SELF_TUNE"):
        _child_self_tune()
        return
    if os.environ.get("BENCH_TPU_RPC"):
        _child_tpu_rpc()
        return
    if os.environ.get("BENCH_CHILD"):
        _child_sweep()
        return

    # Device legs first: if the chip is missing or a leg fails, the run
    # ends here, non-zero, before any host-only row is taken.
    sweep = _run_device_child({"BENCH_CHILD": "1"}, 900)
    if [r.get("size") for r in sweep] != SIZES:
        raise SystemExit(f"sweep returned rows for "
                         f"{[r.get('size') for r in sweep]}, not {SIZES}")
    (tpu_rpc,) = _run_device_child({"BENCH_TPU_RPC": "1"}, 600)

    zerocopy = _run_json_child({"BENCH_ZC": "1"}, 60)
    qos_mixed = _run_json_child({"BENCH_QOS": "1"}, 90)
    kv_disagg = _run_json_child({"BENCH_KV": "1"}, 240)
    # prefix_cache row (ISSUE 17): the content-addressed cache metrics
    # measured in the SAME kv_disagg run (the goodput/p99 floors and the
    # recompute drop must hold simultaneously), lifted into their own
    # headline row.
    prefix_cache = None
    if kv_disagg and "prefix_recompute_drop" in kv_disagg:
        prefix_cache = {
            "workload": "prefix_cache_zipf_multitenant",
            "same_run_as": "kv_disagg",
            "kv_goodput_gbps": kv_disagg["kv_goodput_gbps"],
            "ratio_p99": kv_disagg["ratio_p99"],
        }
        prefix_cache.update({k: v for k, v in kv_disagg.items()
                             if k.startswith(("prefix_", "lb_hint_"))})
    rolling_restart = _run_json_child({"BENCH_RR": "1"}, 240)
    replay = _run_json_child({"BENCH_REPLAY": "1"}, 300)
    coll = _run_json_child({"BENCH_COLL": "1"}, 240)
    pipeline_overlap = _run_json_child({"BENCH_OVERLAP": "1"}, 240)
    slo_fleet = _run_json_child({"BENCH_SLO_FLEET": "1"}, 240)
    self_tune = _run_json_child({"BENCH_SELF_TUNE": "1"}, 240)
    infer_serving = _run_json_child({"BENCH_INFER": "1"}, 600)

    head = sweep[-1]  # 64MB
    print(json.dumps({
        "metric": "echo_goodput_64MB",
        "value": head["goodput_gbps"],
        "unit": "GB/s",
        "vs_baseline": round(head["goodput_gbps"] / BASELINE_GBPS, 3),
        "platform": head["platform"],
        "device_kind": head["device_kind"],
        "device_count": head["device_count"],
        "sweep": sweep,
        "tpu_rpc": tpu_rpc,
        "cpp": _cpp_rows(),
        "zerocopy": zerocopy,
        "qos_mixed": qos_mixed,
        "kv_disagg": kv_disagg,
        "prefix_cache": prefix_cache,
        "rolling_restart": rolling_restart,
        "replay": replay,
        "collective": coll,
        "pipeline_overlap": pipeline_overlap,
        "slo_fleet": slo_fleet,
        "self_tune": self_tune,
        "infer_serving": infer_serving,
    }))


if __name__ == "__main__":
    main()
