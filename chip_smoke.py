#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, started as `python chip_smoke.py`, that holds the chip itself
and starts no child that needs it.  It drives the main path once through
the entry points a user would call — a payload that starts in HBM is served
by the RPC stack and ends in HBM, verified there — at the payload widths of
the reference's suite, 1 KB to 64 MB, plus the device plane's own echo
step, one hand-over of a cache of two kinds through the KV plane at
Kimi-Linear's record sizes, and on more than one chip the mesh plane.  It
exits 0 only if every leg ran on the chip and verified; a missing accelerator, a mismatch or any
exception is a non-zero exit and no result line.  It measures nothing that
may be claimed: the times it prints are facts about the machine for the
next reader, taken once.

Each leg prints one JSON line that names the device (platform, kind, count),
then a summary line that ends with `"claim": null`.  The last line of stdout
is the result the driver reads, exactly `{"ok": true, "device": {"platform":
..., "kind": ..., "count": ...}}`, and it is printed only after every leg
has verified.

The leg functions take their sizes and `interpret` as arguments so that
tests/test_chip_smoke.py can run them on the CPU mesh at tiny sizes with
`interpret=True`; `main()` is the only caller that passes the real sizes and
`interpret=False`.
"""

from __future__ import annotations

import ctypes
import functools
import importlib.metadata
import json
import shutil
import statistics
import sys
import time

SEED = 21
PLAIN_SIZES = (1 << 10, 1 << 16)             # single_chip_echo_step
FUSED_SIZES = (1 << 20, 1 << 24, 1 << 26)    # echo_fused: whole 512 KB blocks
SERVED_SIZES = (1 << 10, 1 << 20, 1 << 26)
PIPELINE_DEPTH = 8        # 512 MB in flight at 64 MB, the tensor64M mix's depth
EXCHANGE_BYTES_PER_PEER = 64 << 20           # rdma_performance's width
# A 1,024-token prompt's cache of Kimi-Linear-48B-A3B (the `kv_hybrid`
# configuration's widths): 8 pages of 128 tokens x 576 of its 7 MLA
# layers, and of its 20 KDA layers one state of 8,480 rows of 128 2-byte
# words each (2,170,880 B: 32 x 128 x 128 float32 and the convolution's).
HYBRID_LAYERS = 27
HYBRID_FULL_ATTN = (4, 8, 12, 16, 20, 24, 27)
HYBRID_PAGES = 8
HYBRID_PAGE = (128, 576)
HYBRID_STATE = (8480, 128)
# The shm ring, each shm/ici connection's two 256 MB one-sided windows and
# the 64 MB staging slab are shm_open+ftruncate files with no fallocate:
# on a tmpfs too small to back a touched page that is a SIGBUS, not an
# error.  So the room is checked first and a small tmpfs fails with words.
SHM_FREE_NEEDED = 2 << 30


def device_facts() -> dict:
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def report(leg: str, facts: dict) -> dict:
    """One line per leg, each naming the device it ran on."""
    print(json.dumps({"leg": leg, "device": device_facts(), **facts}),
          flush=True)
    return facts


def _seeded(seed: int, size: int):
    import numpy as np

    return np.random.default_rng(seed).integers(
        0, 1 << 32, size // 4, dtype=np.uint32)


def _same_on_device(a, b, what: str) -> None:
    import jax.numpy as jnp

    if a.shape != b.shape or not bool(jnp.array_equal(a, b)):
        raise AssertionError(f"{what}: device compare failed")


# ------------------------------------------------------------------ legs ----

def leg_environment() -> dict:
    """The chip is there, it is a kind the roofline table knows, and the
    host has the room the transports need."""
    import jax
    import jaxlib

    t0 = time.perf_counter()
    devices = jax.devices()
    backend_start_s = time.perf_counter() - t0
    first = devices[0]
    if first.platform != "tpu":
        raise SystemExit(
            f"chip_smoke needs a TPU; JAX found platform {first.platform!r} "
            f"(device_kind {first.device_kind!r}, {len(devices)} device(s))")

    from brpc_tpu.compile_cache import enable_compile_cache
    from brpc_tpu.ops.roofline import hbm_peak_gbps

    shm_free = shutil.disk_usage("/dev/shm").free
    if shm_free < SHM_FREE_NEEDED:
        raise SystemExit(
            f"/dev/shm has {shm_free} bytes free; the shm ring, the RMA "
            f"windows and the staging slab need {SHM_FREE_NEEDED} to be "
            "safe from SIGBUS")
    cache_dir = enable_compile_cache()  # before the first compilation
    return {
        "backend_start_s": round(backend_start_s, 3),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": importlib.metadata.version("libtpu"),
        "dev_shm_free_bytes": shm_free,
        "hbm_peak_gbps": hbm_peak_gbps(first.device_kind),
        "compile_cache_dir": cache_dir,
    }


def leg_native_runtime() -> dict:
    """build/libtpurpc.so, built from cpp/ as it stands (or reused when it
    is stamped as built from the same bytes), loaded."""
    from brpc_tpu.rpc import _lib

    built = _lib.ensure_built()
    _lib.load_library()
    return {"recipe": built["recipe"], "build_s": built["seconds"]}


def leg_device_plane(plain_sizes, fused_sizes, interpret: bool,
                     seed: int = SEED, chain: int = 20) -> dict:
    """The driver entry and the device plane's echo step at every width:
    each copy compared on the device with its input, each checksum with a
    value computed in numpy from the seed."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from __graft_entry__ import entry
    from brpc_tpu.models.echo import single_chip_echo_step
    from brpc_tpu.ops.echo_kernel import echo_fused

    # (name, jitted step, host payload): the driver entry with its own
    # payload, then both echo steps on seeded payloads at every width.
    fn, args = entry()
    cases = [("entry()", jax.jit(fn), np.asarray(args[0]))]
    cases += [("single_chip_echo_step", jax.jit(single_chip_echo_step),
               _seeded(seed, size)) for size in plain_sizes]
    cases += [("echo_fused",
               jax.jit(functools.partial(echo_fused, interpret=interpret)),
               _seeded(seed, size)) for size in fused_sizes]
    rows = []
    for name, step, host in cases:
        size = host.nbytes
        x = jax.device_put(host)
        t0 = time.perf_counter()
        compiled = step.lower(x).compile()
        compile_s = time.perf_counter() - t0
        resp, csum = compiled(x)
        want = host if name == "echo_fused" else np.roll(host, 1)
        _same_on_device(resp, jax.device_put(want), f"{name} {size}B copy")
        if int(csum) != int(host.sum(dtype=np.uint32)):
            raise AssertionError(
                f"{name} {size}B: checksum differs from numpy's")

        # The same data-dependent chain, ended two ways.  If
        # block_until_ready did not wait, its chain would cost a fraction
        # of the one that has to bring the checksum to the host.
        def run_chain(end):
            r = x
            t0 = time.perf_counter()
            for _ in range(chain):
                r, c = compiled(r)
            end(c)
            return (time.perf_counter() - t0) / chain

        run_chain(jax.block_until_ready)  # warm
        bur_step_s = run_chain(jax.block_until_ready)
        fetch_step_s = run_chain(int)
        fetches = []
        for _ in range(5):
            c = jax.block_until_ready(csum + jnp.uint32(1))
            t0 = time.perf_counter()
            int(c)
            fetches.append(time.perf_counter() - t0)
        rows.append({
            "bytes": size, "step": name, "interpret": interpret,
            "compile_s": round(compile_s, 3),
            "step_us_block_until_ready": round(bur_step_s * 1e6, 1),
            "step_us_host_fetch": round(fetch_step_s * 1e6, 1),
            "d2h_4B_us": round(statistics.median(fetches) * 1e6, 1),
        })
    return {
        "steps": rows,
        "compile_s": round(sum(r["compile_s"] for r in rows), 3),
        # Neither chain may be an enqueue-only time.
        "block_until_ready_waits": all(
            r["step_us_block_until_ready"] >= 0.5 * r["step_us_host_fetch"]
            for r in rows),
    }


def leg_served_path(sizes, depth: int, seed: int = SEED,
                    sync_calls: int = 3) -> dict:
    """Device array → D2H → Channel → wire → Server handler → response →
    H2D → compare on the device, over single-connection tcp, pooled tcp
    and the shm ring; synchronous zero-copy calls, then one pipelined
    window of `depth` requests with caller-owned response buffers."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from brpc_tpu.rpc import Channel, Server, zerocopy

    srv = Server()
    srv.register_native_echo("Echo.Echo")
    srv.register("Echo.PyEcho", lambda call, req: call.respond(req))
    srv.start(0)
    addr = f"127.0.0.1:{srv.port}"
    # name -> (channel, the live transport it must report).  A pooled
    # channel takes a tcp socket per call and holds none to ask, so it
    # reports "".
    channels = {
        "tcp_single": (Channel(addr, timeout_ms=60000), "tcp"),
        "tcp_pooled": (Channel(addr, timeout_ms=60000,
                               connection_type="pooled"), ""),
        "shm": (Channel(addr, timeout_ms=60000, use_shm=True), "shm_ring"),
    }

    def fresh(x, n):
        """n device arrays derived on the chip; none has a cached host
        copy, so every request pays its own D2H."""
        return [jax.block_until_ready(x + jnp.uint32(i + 1))
                for i in range(n)]

    def back_on_device(buf, original, what):
        t0 = time.perf_counter()
        back = jax.block_until_ready(
            jax.device_put(np.frombuffer(buf, dtype=np.uint32)))
        h2d_s = time.perf_counter() - t0
        _same_on_device(back, original, what)
        return h2d_s

    rows = []
    try:
        for size in sizes:
            x = jax.device_put(_seeded(seed, size))
            for name, (ch, transport) in channels.items():
                what = f"{name} {size}B"
                sync_s = []
                for req in fresh(x, sync_calls):
                    t0 = time.perf_counter()
                    resp = zerocopy.call_zero_copy(ch, "Echo.Echo", req)
                    sync_s.append(time.perf_counter() - t0)
                    back_on_device(resp, req, f"{what} sync call")
                # A smoke that passes over tcp while saying shm is the
                # kind of pass this script exists to prevent.
                if ch.transport != transport:
                    raise AssertionError(
                        f"{name}: live transport is {ch.transport!r}, "
                        f"expected {transport!r}")

                reqs = fresh(x, depth)
                d2h_s, views = [], []
                for req in reqs:
                    t0 = time.perf_counter()
                    views.append(zerocopy.host_bytes(req))
                    d2h_s.append(time.perf_counter() - t0)
                bufs = [np.empty(size, dtype=np.uint8) for _ in reqs]
                pipe = ch.pipeline()
                try:
                    t0 = time.perf_counter()
                    tokens = pipe.submit(
                        "Echo.Echo", [flat for flat, _ in views],
                        resp_bufs=bufs)
                    pending = set(tokens)
                    while pending:
                        done = pipe.poll(max_n=depth, timeout_ms=60000)
                        if not done:
                            raise TimeoutError(f"{what}: pipeline stalled")
                        for c in done:
                            if not c.ok or c.resp_len != size:
                                raise AssertionError(f"{what}: {c!r}")
                            pending.discard(c.token)
                    window_s = time.perf_counter() - t0
                finally:
                    pipe.close()
                h2d_s = [back_on_device(buf, req, f"{what} pipelined")
                         for buf, req in zip(bufs, reqs)]
                rows.append({
                    "channel": name, "transport": ch.transport,
                    "bytes": size,
                    "sync_call_ms": round(
                        statistics.median(sync_s) * 1e3, 3),
                    "pipeline_depth": depth,
                    "pipeline_window_ms": round(window_s * 1e3, 3),
                    "d2h_ms": round(statistics.median(d2h_s) * 1e3, 3),
                    "h2d_ms": round(statistics.median(h2d_s) * 1e3, 3),
                })
            (req,) = fresh(x, 1)
            resp = zerocopy.call_zero_copy(
                channels["tcp_single"][0], "Echo.PyEcho", req)
            back_on_device(resp, req, f"python handler {size}B")
    finally:
        for ch, _ in channels.values():
            ch.close()
        srv.stop()
    return {"calls": rows, "python_handler": "verified"}


def leg_staged_path(size: int, seed: int = SEED, iters: int = 4) -> dict:
    """The staged path of a device-origin RPC: D2H into a registered
    staging slab, the native echo loop over the ici ring, the shm ring
    and tcp, echoed bytes back on the device."""
    import jax
    import numpy as np

    from brpc_tpu.rpc import zerocopy
    from brpc_tpu.rpc._lib import load_library

    lib = load_library()
    x = jax.block_until_ready(jax.device_put(_seeded(seed, size)))
    slab = zerocopy.alloc_staging(size, lib)
    try:
        t0 = time.perf_counter()
        fetched = np.asarray(x).view(np.uint8)
        d2h_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        np.copyto(slab, fetched)
        land_s = time.perf_counter() - t0

        legs = {}
        for tr, want in (("ici", "ici_ring"), ("shm", "shm_ring"),
                         ("tcp", "tcp")):
            resp = np.empty(size, dtype=np.uint8)
            gbps = ctypes.c_double()
            used = ctypes.create_string_buffer(32)
            err = ctypes.create_string_buffer(256)
            _, zc_bytes0 = zerocopy.zero_copy_counters(lib)
            rc = lib.trpc_bench_echo_rpc(
                slab.ctypes.data, size, iters, 1, tr.encode(),
                resp.ctypes.data, ctypes.byref(gbps), used, 32, err, 256)
            if rc != 0:
                raise RuntimeError(
                    f"staged {tr} leg failed: {err.value.decode()}")
            if used.value.decode() != want:
                raise AssertionError(
                    f"staged {tr} leg ran over {used.value.decode()!r}")
            _, zc_bytes1 = zerocopy.zero_copy_counters(lib)
            covered = zc_bytes1 - zc_bytes0 >= size * iters
            if tr == "ici" and not covered:
                raise AssertionError(
                    "ici leg: the payload did not ride sender-owned "
                    "descriptors")
            t0 = time.perf_counter()
            back = jax.block_until_ready(
                jax.device_put(resp.view(np.uint32)))
            h2d_s = time.perf_counter() - t0
            _same_on_device(back, x, f"staged {tr} leg")
            legs[want] = {
                "call_ms": round(size / gbps.value / 1e6, 3),
                "payload_covered": covered,
                "h2d_ms": round(h2d_s * 1e3, 3),
            }
    finally:
        zerocopy.free_staging(slab, lib)
    return {"bytes": size, "loopback": True,
            "d2h_ms": round(d2h_s * 1e3, 3),
            "staging_land_ms": round(land_s * 1e3, 3), "legs": legs}


def leg_kv_hybrid(pages: int, page: tuple, state: tuple,
                  seed: int = SEED) -> dict:
    """One hand-over of a cache of two kinds through the KV plane's
    normal path: `pages` pages of the paged layers and one state of each
    snapshot layer go from a prefill rank's pools in HBM to a decode
    rank's, `publish_sequence` / `fetch_sequence` over the shm ring, and
    both kinds are compared on the device.  The pools are a few slots:
    the records are the published ones."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from brpc_tpu.models import kv_pool
    from brpc_tpu.rpc import (Channel, RmaBuffer, Server, kv, observe,
                              zerocopy)

    paged = HYBRID_FULL_ATTN
    layout = kv.KvCacheLayout(
        tuple(kv.PAGED if layer in paged else kv.SNAPSHOT
              for layer in range(1, HYBRID_LAYERS + 1)),
        tuple(2 * (page[0] * page[1] if layer in paged
                   else state[0] * state[1])
              for layer in range(1, HYBRID_LAYERS + 1)))
    snapshots = HYBRID_LAYERS - len(paged)
    prefill = (kv_pool.seeded_pool(seed, 2 * pages, len(paged), *page),
               kv_pool.seeded_pool(seed + 1, 2, snapshots, *state))
    decode = (kv_pool.seeded_pool(seed + 2, 2 * pages, len(paged), *page),
              kv_pool.seeded_pool(seed + 3, 2, snapshots, *state))
    from_slots = jnp.arange(pages, dtype=jnp.int32) * 2 + 1
    to_slots = jnp.arange(pages, dtype=jnp.int32) * 2
    sent = (kv_pool.read_pages(prefill[0], from_slots),
            kv_pool.read_page(prefill[1], 1))

    def large_bytes():
        dumped = observe.Vars.dump()
        return [dumped.get(k, 0) for k in (
            "rma_tx_bytes", "stripe_tx_bytes", "batch_resp_bytes",
            "batch_land_copy_bytes")]

    kv.reset()
    srv = Server()
    srv.enable_kv_store()
    srv.enable_kv_registry()
    srv.start(0)
    addr = f"127.0.0.1:{srv.port}"
    nbytes = layout.sequence_bytes(pages)
    slab, land = RmaBuffer(nbytes), RmaBuffer(nbytes)
    reg = kv.KvRegistryClient(Channel(addr, timeout_ms=30000),
                              owns_channel=True)
    cli = kv.KvClient(addr, use_shm=True, timeout_ms=30000)
    try:
        before = large_bytes()
        t0 = time.perf_counter()
        views = [zerocopy.host_view(x)[0] for x in sent]
        staged = isinstance(views[0], zerocopy.PendingView)
        metas = kv.publish_sequence(
            1, layout, *(views if staged else sent), slab, lease_ms=600000,
            node=addr, registry=reg)
        area = np.frombuffer(land.view, dtype=np.uint16)
        cut = sent[0].size
        landed = cli.fetch_sequence(
            1, layout, area[:cut].reshape(sent[0].shape),
            area[cut:].reshape(sent[1].shape))
        back = jax.device_put(landed)
        decode = (kv_pool.write_pages(decode[0], to_slots, back[0]),
                  kv_pool.write_page(decode[1], 0, back[1]))
        jax.block_until_ready(decode)
        handover_s = time.perf_counter() - t0
        moved = [b - a for a, b in zip(before, large_bytes())]
        _same_on_device(kv_pool.read_pages(decode[0], to_slots), sent[0],
                        "hybrid hand-over, pages")
        _same_on_device(kv_pool.read_page(decode[1], 0), sent[1],
                        "hybrid hand-over, states")
        kv.withdraw_sequence(1, layout, pages, registry=reg)
        transport = cli.transports()[addr]
        if transport != "shm_ring":
            raise AssertionError(f"hybrid hand-over ran over {transport!r}")
    finally:
        cli.close()
        reg.close()
        srv.stop()
        slab.free()
        land.free()
    return {
        "transport": transport, "staged": staged,
        "records": {"paged": pages * len(paged), "snapshot": snapshots,
                    "published": len(metas)},
        "record_bytes": {"paged": layout.record_bytes[paged[0] - 1],
                         "snapshot": 2 * state[0] * state[1]},
        "bytes": nbytes,
        "kvh_one_sided_share": (100.0 * moved[0] / (moved[0] + moved[1])
                                if moved[0] + moved[1] else None),
        "kvh_land_copy_share": 100.0 * moved[3] / moved[2],
        "handover_ms": round(handover_s * 1e3, 3),
    }


def leg_mesh_plane(interpret: bool, exchange_bytes_per_peer: int,
                   seed: int = SEED) -> dict:
    """Every sharded program over all local devices — the ring kernel
    compiled, not interpreted, when `interpret` is False — then the
    rdma_performance shape at real width.  One device is "not_run": the
    virtual CPU mesh is never a substitute."""
    import jax
    import numpy as np

    from __graft_entry__ import _check_spread, _dryrun_impl
    from brpc_tpu.models.echo import make_nton_exchange
    from brpc_tpu.parallel.fabric import Fabric

    devices = jax.devices()
    n = len(devices)
    if n < 2:
        return {"mesh": "not_run", "devices": n}

    t0 = time.perf_counter()
    _dryrun_impl(n, interpret=interpret)
    dryrun_s = time.perf_counter() - t0

    # N-to-N exchange, `exchange_bytes_per_peer` held and received by each
    # peer.  This leg is the XLA all_to_all: the pallas ring kernel keeps
    # its whole gather in VMEM and is exercised at tile size above.
    chunk = exchange_bytes_per_peer // 4 // n
    rows = _seeded(seed, n * n * chunk * 4).reshape(n * n, chunk)
    ring = Fabric.auto((n,), ("link",), devices=devices)
    local = ring.put(rows, "link")
    t0 = time.perf_counter()
    exchange = make_nton_exchange(ring, "link").lower(local).compile()
    compile_s = time.perf_counter() - t0
    jax.block_until_ready(exchange(local))  # warm
    t0 = time.perf_counter()
    recv, sums = jax.block_until_ready(exchange(local))
    exchange_s = time.perf_counter() - t0
    want = rows.reshape(n, n, chunk).transpose(1, 0, 2).reshape(rows.shape)
    _check_spread(recv, devices, (n, chunk), "64MB exchange")
    _check_spread(sums, devices, (1,), "64MB exchange checksums")
    _same_on_device(recv, ring.put(want, "link"), "64MB exchange")
    np.testing.assert_array_equal(
        np.asarray(sums),
        want.reshape(n, -1).sum(axis=1, dtype=np.uint32))
    return {
        "mesh": "verified", "devices": n, "interpret": interpret,
        "device_coords": [getattr(d, "coords", None) for d in devices],
        "dryrun_s": round(dryrun_s, 3),
        "ring_all_gather_pallas": "interpreted" if interpret else "mosaic",
        "exchange": {"collective": "xla_all_to_all",
                     "bytes_per_peer": n * chunk * 4,
                     "compile_s": round(compile_s, 3),
                     "exchange_ms": round(exchange_s * 1e3, 3),
                     "sharded_over": n},
    }


# ------------------------------------------------------------------ main ----

def main() -> int:
    t_start = time.perf_counter()
    env = report("environment", leg_environment())
    native = report("native_runtime", leg_native_runtime())
    plane = report("device_plane", leg_device_plane(
        PLAIN_SIZES, FUSED_SIZES, interpret=False))
    served = report("served_path", leg_served_path(
        SERVED_SIZES, PIPELINE_DEPTH))
    staged = report("staged_path", leg_staged_path(SERVED_SIZES[-1]))
    hybrid = report("kv_hybrid", leg_kv_hybrid(
        HYBRID_PAGES, HYBRID_PAGE, HYBRID_STATE))
    mesh = report("mesh_plane", leg_mesh_plane(
        interpret=False, exchange_bytes_per_peer=EXCHANGE_BYTES_PER_PEER))
    report("summary", {
        "backend_start_s": env["backend_start_s"],
        "native_build": native,
        "compile_s": plane["compile_s"],
        "block_until_ready_waits": plane["block_until_ready_waits"],
        "mosaic_accepted": ["echo_fused"] + (
            ["ring_all_gather_pallas"] if mesh["mesh"] == "verified"
            else []),
        "shm_transport": next(c["transport"] for c in served["calls"]
                              if c["channel"] == "shm"),
        "staged_ici_payload_covered":
            staged["legs"]["ici_ring"]["payload_covered"],
        "kv_hybrid": {k: hybrid[k] for k in (
            "transport", "bytes", "kvh_one_sided_share",
            "kvh_land_copy_share")},
        "mesh": mesh["mesh"],
        "seconds": round(time.perf_counter() - t_start, 1),
        "claim": None,
    })
    # The result line: these keys and no others.
    print(json.dumps({"ok": True, "device": device_facts()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
