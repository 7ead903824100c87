#!/usr/bin/env python3
"""Cross-node trace stitcher: rpcz span sets → ONE Chrome trace-event
JSON.

Given N node endpoints and a trace_id, pulls every node's spans from
`/rpcz?format=json&trace_id=...`, joins parent/child links across hops,
and emits Chrome trace-event JSON loadable in Perfetto (ui.perfetto.dev)
or chrome://tracing: one process track per node, spans as complete
events (ph "X", server vs client on separate thread tracks), span
annotations as instant events (ph "i").

Clock model: span times are each node's CLOCK_MONOTONIC, mutually
meaningless across processes.  Every rpcz dump carries a
{"now_mono_us","now_wall_us"} pair read back-to-back, so each node's
spans first map onto its own wall clock (wall = t + now_wall - now_mono).
Residual inter-node wall skew is then corrected by containment: for each
parent/child pair that crosses nodes, the child's node is shifted so the
child span's midpoint centers inside its parent (the classic rpcz
alignment — a child RPC physically runs within its parent's window),
averaged over all cross-node links and propagated breadth-first from an
anchor node, so chains (client → A → B) come out consistent.

Usage:
    python tools/trace_stitch.py --trace-id 1f00d... \\
        --out trace.json host1:port1 host2:port2
    # merge spans of THIS process (e.g. the client side of the trace):
    python tools/trace_stitch.py --trace-id 1f00d... --local client ...

Importable pieces (used by tests/test_observe.py): `fetch_rpcz`,
`local_rpcz`, `stitch`.
"""

from __future__ import annotations

import argparse
import json
import sys
import urllib.request
from collections import defaultdict


def fetch_rpcz(endpoint: str, trace_id: str | None = None,
               limit: int = 4096, timeout: float = 5.0) -> dict:
    """One node's structured span dump ({"pid","now_mono_us",
    "now_wall_us","spans":[...]}) via its builtin HTTP service."""
    url = f"http://{endpoint}/rpcz?format=json&limit={limit}"
    if trace_id:
        url += f"&trace_id={trace_id}"
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read().decode())


def local_rpcz(trace_id: str | None = None, limit: int = 4096) -> dict:
    """THIS process's span dump (no server needed) — the client side of a
    trace usually lives here."""
    from brpc_tpu.rpc import observe

    return observe.rpcz_dump(limit=limit, trace_id=trace_id)


def _mid(s: dict) -> float:
    return (float(s["start_us"]) + float(s["end_us"])) / 2.0


def _node_offsets(dumps: dict[str, dict]) -> dict[str, float]:
    """Per-node correction (us) applied ON TOP of the mono→wall mapping,
    aligning nodes via cross-node parent/child containment."""
    # Wall-clock midpoints per span, per node.
    wall_mid: dict[str, dict[str, float]] = {}
    span_node: dict[str, str] = {}
    for node, dump in dumps.items():
        base = float(dump.get("now_wall_us", 0)) - \
            float(dump.get("now_mono_us", 0))
        mids = {}
        for s in dump.get("spans", []):
            mids[s["span_id"]] = _mid(s) + base
            span_node[s["span_id"]] = node
        wall_mid[node] = mids
    # Desired inter-node deltas from cross-node links: moving the child
    # node by (parent_mid - child_mid) centers the child in its parent.
    deltas: dict[tuple[str, str], list[float]] = defaultdict(list)
    for node, dump in dumps.items():
        for s in dump.get("spans", []):
            parent = s.get("parent_span_id", "")
            pnode = span_node.get(parent)
            if pnode is None or pnode == node:
                continue
            want = wall_mid[pnode][parent] - wall_mid[node][s["span_id"]]
            deltas[(pnode, node)].append(want)
    # Propagate from an anchor breadth-first so client → A → B chains
    # shift consistently even though B never links to the client.
    offsets = {}
    nodes = list(dumps)
    if not nodes:
        return offsets
    anchor = nodes[0]
    offsets[anchor] = 0.0
    frontier = [anchor]
    while frontier:
        u = frontier.pop(0)
        for (p, c), ds in deltas.items():
            mean = sum(ds) / len(ds)
            for known, other, sign in ((p, c, 1.0), (c, p, -1.0)):
                if known == u and other not in offsets:
                    offsets[other] = offsets[u] + sign * mean
                    frontier.append(other)
    for n in nodes:  # unlinked nodes ride on their own wall clock
        offsets.setdefault(n, 0.0)
    return offsets


def stitch(dumps: dict[str, dict], trace_id: str | None = None) -> dict:
    """Joins {node_name: rpcz_dump} into one Chrome trace-event object.

    Returns {"traceEvents": [...], "displayTimeUnit": "ms", "stitch":
    {summary}} — JSON-dumpable straight into Perfetto.  When `trace_id`
    is given, spans from other traces are dropped (belt + braces for
    dumps fetched without the server-side filter)."""
    offsets = _node_offsets(dumps)
    # Global index for parent-link accounting (across ALL nodes).
    all_ids = set()
    for dump in dumps.values():
        for s in dump.get("spans", []):
            if trace_id and s["trace_id"] != trace_id:
                continue
            all_ids.add(s["span_id"])
    events = []
    parent_linked = 0
    spans_total = 0
    for pid, (node, dump) in enumerate(sorted(dumps.items())):
        base = float(dump.get("now_wall_us", 0)) - \
            float(dump.get("now_mono_us", 0)) + offsets[node]
        events.append({
            "ph": "M", "name": "process_name", "pid": pid,
            "args": {"name": f"{node} (pid {dump.get('pid', '?')})"},
        })
        for tid, tname in ((0, "server spans"), (1, "client spans")):
            events.append({
                "ph": "M", "name": "thread_name", "pid": pid, "tid": tid,
                "args": {"name": tname},
            })
        for s in dump.get("spans", []):
            if trace_id and s["trace_id"] != trace_id:
                continue
            spans_total += 1
            start = float(s["start_us"]) + base
            dur = max(float(s["end_us"]) - float(s["start_us"]), 1.0)
            linked = s.get("parent_span_id", "0" * 16) in all_ids
            parent_linked += 1 if linked else 0
            tid = 0 if s["side"] == "server" else 1
            events.append({
                "ph": "X", "name": s["method"], "cat": s["side"],
                "pid": pid, "tid": tid, "ts": start, "dur": dur,
                "args": {
                    "trace_id": s["trace_id"], "span_id": s["span_id"],
                    "parent_span_id": s["parent_span_id"],
                    "parent_linked": linked,
                    "error_code": s["error_code"],
                    "request_bytes": s["request_bytes"],
                    "response_bytes": s["response_bytes"],
                },
            })
            for a in s.get("annotations", []):
                events.append({
                    "ph": "i", "name": a["text"], "s": "t",
                    "pid": pid, "tid": tid,
                    "ts": float(a["ts_us"]) + base,
                })
    # Rebase so the trace starts near 0 (Perfetto-friendly).
    t0 = min((e["ts"] for e in events if "ts" in e), default=None)
    if t0 is not None:
        for e in events:
            if "ts" in e:
                e["ts"] -= t0
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "stitch": {
            "trace_id": trace_id,
            "nodes": sorted(dumps),
            "spans": spans_total,
            "parent_linked": parent_linked,
            "node_offsets_us": {n: round(v, 1)
                                for n, v in offsets.items()},
        },
    }


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description="stitch rpcz spans from N nodes into Chrome trace "
                    "JSON (Perfetto)")
    ap.add_argument("endpoints", nargs="*",
                    help="host:port of each node's builtin service")
    ap.add_argument("--trace-id", default=None,
                    help="hex trace id to stitch (default: everything)")
    ap.add_argument("--limit", type=int, default=4096,
                    help="max spans pulled per node")
    ap.add_argument("--local", metavar="NAME", default=None,
                    help="also merge THIS process's spans as node NAME")
    ap.add_argument("--out", default="-",
                    help="output path (default: stdout)")
    args = ap.parse_args(argv)
    dumps: dict[str, dict] = {}
    for ep in args.endpoints:
        dumps[ep] = fetch_rpcz(ep, args.trace_id, args.limit)
    if args.local:
        dumps[args.local] = local_rpcz(args.trace_id, args.limit)
    if not dumps:
        ap.error("no endpoints given (and --local not set)")
    trace = stitch(dumps, args.trace_id)
    text = json.dumps(trace)
    if args.out == "-":
        print(text)
    else:
        with open(args.out, "w") as f:
            f.write(text)
        s = trace["stitch"]
        print(f"wrote {args.out}: {s['spans']} spans "
              f"({s['parent_linked']} parent-linked) from "
              f"{len(s['nodes'])} nodes", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
