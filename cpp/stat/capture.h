// Production traffic capture (ISSUE 16) — a sampled per-request metadata
// recorder behind the default-off reloadable `trpc_capture` flag.
//
// Server::EnableDump (rpc_dump parity) keeps request BODIES; this tier
// keeps the TRAFFIC: per-request arrival timestamps, method, tenant and
// priority (tail-group 5), deadline budget (tail-group 7), trace/span
// ids, request/response sizes, status code, and queue + handler latency.
// That is exactly the set a replayer (tools/traffic_replay.py,
// cpp/tools/rpc_replay.cc) needs to regenerate the arrival process,
// tenant mix and size distribution that actually break a serving fleet —
// bodies alone replay *requests*, not *traffic*.
//
// Memory model: a per-tenant stratified reservoir bounded by
// `trpc_capture_max_records` records, each clamped to ~100 bytes of
// metadata regardless of body size (a 64MB request contributes 8 bytes
// of `request_bytes`).  Admission is a deterministic seeded hash of the
// per-window decision index (`trpc_capture_sample_permille`,
// `trpc_capture_seed`) so a seeded stream keeps/drops the same records
// on every run; within a full stratum, Algorithm R keeps a uniform
// sample.  Every sampled-but-not-retained record counts in
// `capture_dropped_total` — a capture that silently thins would lie
// about coverage and poison every downstream regression run.
//
// Off-cost contract (same as trpc_analysis): with the
// flag off every hook is one relaxed atomic load + branch, and the
// capture_* vars are provably frozen at 0.
//
// Readers: the /capture builtin (JSON summary + optional records +
// server-side file dump), the trpc_capture_* C API
// (brpc_tpu/rpc/capture.py), and the recordio capture file consumed by
// tools/traffic_replay.py and cpp/tools/rpc_replay.cc.
#pragma once

#include <atomic>
#include <cstdint>
#include <string>

#include "base/iobuf.h"

namespace trpc {
namespace capture {

// One captured request's metadata.  Strings are clamped at record time
// (method/tenant <= 64 bytes) so reservoir memory is bounded by record
// COUNT, never by body size.
struct Sample {
  int64_t arrival_mono_us = 0;  // monotonic arrival (parse or dispatch)
  int64_t arrival_wall_us = 0;  // wall-clock arrival (0 = derive at record)
  uint64_t trace_id = 0;
  uint64_t parent_span_id = 0;  // caller's span — fan-out tree edges
  uint64_t request_bytes = 0;
  uint64_t response_bytes = 0;
  int32_t status = 0;            // 0 ok, else kE* error code
  uint32_t queue_us = 0;         // request whole -> handler entered
  uint32_t handler_us = 0;       // handler entered -> response handed off
  uint32_t deadline_budget_us = 0;  // wire tail-group 7 budget (0 = none)
  uint8_t priority = 0;          // tail-group 5
  std::string method;
  std::string tenant;            // tail-group 5 ("" = untagged)
};

// Capture-file record 0 starts with this magic, followed by a JSON
// header; records 1..N are serialize_record() payloads.  Distinguishes
// capture files from legacy EnableDump body files (whose record 0 is a
// tstd frame starting "TRP1") inside the same recordio envelope.
inline constexpr char kFileMagic[] = "TRPCCAP1";  // 8 bytes, no NUL on wire

// Backing switch for the reloadable trpc_capture flag (the flag's
// on_update hook writes it; hot-path gates inline to one relaxed load).
extern std::atomic<bool> g_enabled;

inline bool enabled() {
  return g_enabled.load(std::memory_order_relaxed);
}

// Registers flags + vars (idempotent); eager-registered at load so
// /flags can flip trpc_capture before any traffic.
void ensure_registered();

// Offers one request record to the reservoir.  Call sites MUST gate on
// enabled() themselves — record() re-checks, but the call itself should
// cost nothing when the flag is off.  Thread-safe.
void record(Sample&& s);

// JSON dump shared by /capture and trpc_capture_dump: flag state,
// lifetime + window counters, and the arrival-process summary
// (per-second rate series, burstiness CV, log2 size histograms,
// per-tenant rate/latency/error-mix, fan-out stats from trace ids).
// When max_records > 0 the newest records themselves are embedded
// (arrival order) for debugging; the binary capture file is the
// replayer's format.
std::string dump_json(size_t max_records);

// Writes the reservoir to a recordio capture file (header record +
// binary records, arrival order).  Returns records written, or -1 on
// I/O error.  The header embeds the arrival-process summary and the
// recorded per-tenant latency baseline the replay bench compares
// against.
int64_t dump_file(const std::string& path);

// Serializes one record into the capture-file binary layout (packed
// little-endian, struct format "<BqqQQQQiIIIBBB" + method + tenant).
void serialize_record(const Sample& s, IOBuf* out);
// Parses one record payload; false on truncation/bad version.  Shared
// with cpp/tools/rpc_replay.cc and the roundtrip tests.
bool parse_record(const IOBuf& in, Sample* out);

// Clears the reservoir, the window counters and the sampling decision
// index (a fresh capture window; lifetime capture_*_total vars keep
// counting — Prometheus counters never rewind).
void reset();

// Lifetime admission counters (the capture_* vars; provably frozen at 0
// while the flag has never been on).
uint64_t seen_total();     // records offered while enabled
uint64_t sampled_total();  // passed the permille sampling gate
uint64_t dropped_total();  // sampled but not retained (reservoir full)
// Records currently held / their approximate heap footprint (bounded-
// memory test support).
size_t records_held();
size_t approx_bytes();

}  // namespace capture
}  // namespace trpc
