#!/usr/bin/env python3
"""Probe: from what response size does a direct put into the caller's
registered landing beat the copy path on the shm ring?

One process holds a KV store and its registry (`Server`) and a
`KvClient` over the shm ring.  For each body size it publishes N blocks
and lands them, all N `Kv.Fetch` in flight on the node channel's
pipeline, in two kinds of landing area, a window at a time, the two
kinds taking turns:

- `direct`: the rows of one `RmaBuffer`.  A body at or over
  `kRmaDirectFloor` (`cpp/net/rma.h`) is put by the server straight into
  its row, one descriptor a call, and only a control frame crosses the
  ring.
- `copy`: the rows of a plain numpy array, in no registered region.  A
  body under `trpc_stripe_threshold` rides one frame of the ring and is
  copied out of it; over the threshold it is put into the connection's
  one-sided window and copied out of that.

It prints one JSON line a size: the median window's time a body in each
kind (`direct_us`, `copy_us`), their ratio, and which path the bytes
took (`direct_share`: `rma_direct_tx_bytes` over the bytes fetched;
`copy_share`: `batch_land_copy_bytes` over them), then the sizes at which
`direct` was faster.  Every landed row is compared with its block.

Usage: python3 tools/rma_floor_probe.py [--sizes 147456,1032192]
       [--windows 20] [--out chiprun_out/rma_floor_probe.jsonl]
Run it on the host whose floor is being set: on the CPU of another
machine it answers for that machine.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from brpc_tpu.rpc import Channel, RmaBuffer, Server, kv, observe  # noqa: E402

SIZES = (16384, 65536, 147456, 524288, 1032192, 2170880, 8994816)


def window_of(size: int) -> int:
    """Calls in flight: 64, as many as a window of the prefix cache's
    pages, or what 144 MB holds (the one-sided window is 256 MB)."""
    return max(1, min(64, (144 << 20) // size))


def counters() -> dict:
    dump = observe.Vars.dump()
    return {k: dump.get(k, 0) for k in
            ("rma_direct_tx_bytes", "batch_land_copy_bytes",
             "rma_direct_fallback")}


def probe(addr: str, reg, cli, size: int, windows: int,
          block0: int) -> dict:
    n = window_of(size)
    src = RmaBuffer(n * size)
    want = np.frombuffer(src.view, dtype=np.uint8).reshape(n, size)
    want[:] = np.random.default_rng(size).integers(0, 256, want.shape,
                                                   dtype=np.uint8)
    ids = [block0 + i for i in range(n)]
    for i, block in enumerate(ids):
        reg.register(kv.publish(block, src, offset=i * size, length=size,
                                lease_ms=600000, node=addr))
    land = RmaBuffer(n * size)
    kinds = {
        "direct": np.frombuffer(land.view, dtype=np.uint8).reshape(n, size),
        "copy": np.zeros((n, size), dtype=np.uint8),
    }
    times = {kind: [] for kind in kinds}
    moved = {kind: {} for kind in kinds}
    for kind, rows in kinds.items():  # one untimed window each
        cli.fetch_many(ids, list(rows))
    for _ in range(windows):
        for kind, rows in kinds.items():
            rows[:, :8] = 0
            before = counters()
            t0 = time.perf_counter()
            got = cli.fetch_many(ids, list(rows))
            times[kind].append(time.perf_counter() - t0)
            after = counters()
            for k in after:
                moved[kind][k] = moved[kind].get(k, 0) + after[k] - before[k]
            if got != [size] * n or not np.array_equal(rows, want):
                raise SystemExit(f"{kind} landing at {size} B differs")
    fetched = windows * n * size
    out = {"size": size, "calls_in_flight": n, "windows": windows}
    for kind in kinds:
        out[f"{kind}_us"] = statistics.median(times[kind]) / n * 1e6
        out[f"{kind}_direct_share"] = (
            100.0 * moved[kind]["rma_direct_tx_bytes"] / fetched)
        out[f"{kind}_copy_share"] = (
            100.0 * moved[kind]["batch_land_copy_bytes"] / fetched)
        out[f"{kind}_fallbacks"] = moved[kind]["rma_direct_fallback"]
    out["copy_over_direct"] = out["copy_us"] / out["direct_us"]
    for block in ids:
        kv.withdraw(block)
    land.free()
    src.free()
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--sizes", default=",".join(map(str, SIZES)))
    ap.add_argument("--windows", type=int, default=20)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    kv.reset()
    srv = Server()
    srv.enable_kv_store()
    srv.enable_kv_registry()
    srv.start(0)
    addr = f"127.0.0.1:{srv.port}"
    reg = kv.KvRegistryClient(Channel(addr, timeout_ms=60000),
                              owns_channel=True)
    cli = kv.KvClient(addr, timeout_ms=60000, use_shm=True)
    lines = []
    try:
        for i, size in enumerate(int(s) for s in args.sizes.split(",")):
            line = probe(addr, reg, cli, size, args.windows,
                         block0=(i + 1) << 20)
            lines.append(line)
            print(json.dumps(line), flush=True)
    finally:
        cli.close()
        reg.close()
        srv.stop()
        kv.reset()
    faster = [x["size"] for x in lines if x["direct_us"] < x["copy_us"]]
    print(json.dumps({"direct_faster_at": faster}), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            for line in lines:
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
