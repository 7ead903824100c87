// rpcz tracing spans — per-RPC timing records with trace propagation.
//
// Parity: the reference's Span machinery (/root/reference/src/brpc/
// span.h:52-88: CreateClientSpan/CreateServerSpan wired at
// channel.cpp:506-527 and baidu_rpc_protocol.cpp:648-661; trace context
// trace_id/span_id/parent_span_id rides inside the RpcMeta; spans browsed
// via /rpcz, builtin/rpcz_service.*).  Redesigned condensed: spans land in
// an in-memory ring (the reference persists to a per-process
// leveldb — an embedded KV store is out of scope; the ring holds the
// recent window /rpcz actually shows) whose capacity is the reloadable
// flag `trpc_rpcz_ring_size` (default 4096; flip via
// /flags/trpc_rpcz_ring_size?setvalue=N so a busy server does not evict
// the span being hunted before it can be read), collection is gated by
// the reloadable flag `rpcz_enabled`, and the ambient trace context
// lives in fiber-local storage so nested client calls inherit the
// server span.
#pragma once

#include <cstdint>
#include <string>
#include <utility>
#include <vector>

namespace trpc {

struct Span {
  uint64_t trace_id = 0;
  uint64_t span_id = 0;
  uint64_t parent_span_id = 0;
  bool server_side = false;
  std::string method;
  int64_t start_us = 0;
  int64_t end_us = 0;
  int32_t error_code = 0;
  uint64_t request_bytes = 0;
  uint64_t response_bytes = 0;
  std::vector<std::pair<int64_t, std::string>> annotations;
};

// True when span collection is on (flag `rpcz_enabled`, default off —
// same default as the reference's -enable_rpcz).
bool rpcz_enabled();

// Starts a span.  trace_id/parent resolution order: explicit args (from
// wire meta) > ambient fiber context > fresh trace.  The returned span is
// owned by the caller until submit_span.
Span* start_span(bool server_side, const std::string& method,
                 uint64_t trace_id = 0, uint64_t parent_span_id = 0);
void span_annotate(Span* s, const std::string& text);
// Finishes the span and moves it into the ring (frees it).
void submit_span(Span* s, int32_t error_code);

// Ambient trace context: the server span a request handler runs under;
// client spans started in this context become its children.  Storage is
// fiber-local on fibers and falls back to plain thread-local off them,
// so a ctypes caller (Python, a non-fiber pthread) can install a trace
// around `trpc_channel_call` and have the client span inherit it.
void set_ambient_span(const Span* s);  // nullptr clears
void set_ambient_trace(uint64_t trace_id, uint64_t span_id);  // 0,0 clears
void get_ambient_trace(uint64_t* trace_id, uint64_t* span_id);

// /rpcz support: most-recent spans, newest first (bounded by ring size);
// trace_id filter when nonzero.
std::vector<Span> recent_spans(size_t limit, uint64_t trace_id = 0);

// Structured span dump shared by /rpcz?format=json and trpc_rpcz_dump:
// {"pid":n,"now_mono_us":n,"now_wall_us":n,"spans":[...]} with 64-bit ids
// as 16-hex-digit strings (doubles would truncate them) and annotations
// as [{"ts_us":n,"text":s}].  The mono/wall clock pair lets a cross-node
// stitcher (tools/trace_stitch.py) map each node's monotonic span times
// onto one wall-clock timeline.
std::string rpcz_dump_json(size_t limit, uint64_t trace_id = 0);

// Live span-ring capacity (the `trpc_rpcz_ring_size` flag's value;
// touching this also registers the flag).  Resizing preserves the
// newest spans that fit.
size_t rpcz_ring_capacity();

uint64_t new_span_id();

}  // namespace trpc
