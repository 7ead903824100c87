// C ABI for the observability plane (Python ctypes binding surface —
// brpc_tpu/rpc/observe.py).
//
// Everything the builtin HTTP pages show is readable IN-PROCESS here:
// the var registry (JSON + Prometheus), per-recorder latency quantiles,
// the rpcz span ring as structured JSON, and the ambient trace context
// (read/install/clear around fiber-side calls).  Python can also
// REGISTER metrics — latency recorders and gauges — into the same
// registry, so client-side series appear in /vars and /brpc_metrics
// exactly like server methods do.
//
// Buffer protocol for the dump calls: the return value is the FULL
// byte length of the rendered text (excluding the NUL); the buffer
// receives min(full, out_len-1) bytes plus a NUL.  A caller seeing
// ret >= out_len re-calls with a bigger buffer — no truncated JSON is
// ever parsed by accident.
#include <cstdint>
#include <cstring>
#include <string>

#include "base/json.h"
#include "base/proc.h"
#include "capi/capi_util.h"
#include "net/span.h"
#include "stat/latency_recorder.h"
#include "stat/variable.h"

using namespace trpc;
using trpc::capi::copy_out;

namespace {

// An explicit span handle: the span itself plus the ambient context it
// displaced, restored at end so nested trace()/span scopes unwind
// correctly on one thread/fiber.
struct CapiSpan {
  Span* span = nullptr;
  uint64_t prev_trace = 0;
  uint64_t prev_span = 0;
};

}  // namespace

extern "C" {

// ---- var registry -------------------------------------------------------

// format 0: JSON object {name: number-or-string} (the /vars?format=json
// shape); format 1: Prometheus text exposition (the /brpc_metrics body).
size_t trpc_vars_dump(int format, char* out, size_t out_len) {
  if (format == 1) {
    return copy_out(Variable::dump_prometheus(), out, out_len);
  }
  Json j = Json::object();
  for (auto& [name, value] : Variable::dump_exposed()) {
    double num = 0;
    if (parse_plain_number(value.c_str(), &num)) {
      j.set(name, Json::number(num));
    } else {
      j.set(name, Json::str(value));
    }
  }
  return copy_out(j.dump(), out, out_len);
}

// One variable's value_str.  Returns 0 on success, -1 unknown var, -2
// when the value does not fit (nothing useful written; retry bigger).
int trpc_var_read(const char* name, char* out, size_t out_len) {
  if (name == nullptr) {
    return -1;
  }
  std::string v;
  if (!Variable::read_exposed(name, &v)) {
    return -1;
  }
  if (out == nullptr || out_len == 0 || v.size() + 1 > out_len) {
    return -2;
  }
  memcpy(out, v.c_str(), v.size() + 1);
  return 0;
}

// Reads a registered LatencyRecorder's window in one crossing.
// out[8] = {count, qps, avg_us, p50_us, p90_us, p99_us, p999_us, max_us}.
// Returns 0 ok, -1 unknown var, -2 the var is not a latency recorder.
int trpc_latency_read(const char* name, double* out) {
  if (name == nullptr || out == nullptr) {
    return -1;
  }
  int rc = -2;
  // with_exposed pins the recorder alive (registry lock); read_stats
  // takes the window lock once for all four quantiles so that global
  // critical section stays short.
  const bool found = Variable::with_exposed(name, [&](Variable* v) {
    auto* lat = dynamic_cast<LatencyRecorder*>(v);
    if (lat == nullptr) {
      return;
    }
    lat->read_stats(out);
    rc = 0;
  });
  return found ? rc : -1;
}

// 1 when a variable is registered under `name`, else 0 — a pure
// registry probe (no value rendering; unique_var_name polls this).
int trpc_var_exists(const char* name) {
  return name != nullptr && Variable::read_exposed(name, nullptr) ? 1 : 0;
}

// ---- rpcz ---------------------------------------------------------------

// Recent spans as structured JSON (net/span.h rpcz_dump_json — the same
// shape /rpcz?format=json serves): newest first, at most `limit`,
// filtered to `trace_id` when nonzero.  `format` is reserved (0 = JSON).
size_t trpc_rpcz_dump(size_t limit, uint64_t trace_id, int format,
                      char* out, size_t out_len) {
  (void)format;
  if (limit == 0 || limit > (1 << 16)) {
    // Same cap as /rpcz?format=json: the span copy runs under the
    // submit-side ring mutex.
    limit = limit == 0 ? 200 : (1 << 16);
  }
  return copy_out(rpcz_dump_json(limit, trace_id), out, out_len);
}

// ---- ambient trace context ----------------------------------------------

// The context client spans inherit as their parent.  Works on fibers
// (handler-side) AND plain pthreads (Python callers) — span.cc falls
// back to thread-local storage off-fiber.
void trpc_trace_get(uint64_t* trace_id, uint64_t* span_id) {
  uint64_t t = 0;
  uint64_t s = 0;
  get_ambient_trace(&t, &s);
  if (trace_id != nullptr) {
    *trace_id = t;
  }
  if (span_id != nullptr) {
    *span_id = s;
  }
}

void trpc_trace_set(uint64_t trace_id, uint64_t span_id) {
  set_ambient_trace(trace_id, span_id);
}

void trpc_trace_clear() { set_ambient_trace(0, 0); }

// A fresh nonzero 64-bit id (for minting root trace ids in Python).
uint64_t trpc_trace_new_id() { return new_span_id(); }

// ---- explicit spans (the trace() context manager's substrate) -----------

// Starts a span named `name` and installs it as the ambient context
// (children inherit); parent resolution = current ambient, else a fresh
// trace rooted here.  Explicit spans always record — the caller asked
// for them — unlike the automatic per-RPC spans gated on rpcz_enabled.
void* trpc_span_start(const char* name, int server_side) {
  auto* h = new CapiSpan();
  get_ambient_trace(&h->prev_trace, &h->prev_span);
  h->span = start_span(server_side != 0,
                       name != nullptr ? name : "span");
  set_ambient_span(h->span);
  return h;
}

void trpc_span_annotate(void* handle, const char* text) {
  auto* h = static_cast<CapiSpan*>(handle);
  if (h != nullptr && h->span != nullptr && text != nullptr) {
    span_annotate(h->span, text);
  }
}

void trpc_span_ids(void* handle, uint64_t* trace_id, uint64_t* span_id) {
  auto* h = static_cast<CapiSpan*>(handle);
  if (h == nullptr || h->span == nullptr) {
    return;
  }
  if (trace_id != nullptr) {
    *trace_id = h->span->trace_id;
  }
  if (span_id != nullptr) {
    *span_id = h->span->span_id;
  }
}

// Ends the span: restores the ambient context it displaced, submits it
// into the rpcz ring, frees the handle.
void trpc_span_end(void* handle, int error_code) {
  auto* h = static_cast<CapiSpan*>(handle);
  if (h == nullptr) {
    return;
  }
  set_ambient_trace(h->prev_trace, h->prev_span);
  submit_span(h->span, error_code);
  delete h;
}

// ---- Python-registered metrics ------------------------------------------

// A latency recorder owned by the caller, exposed under `name` in the
// shared registry (shows in /vars, /brpc_metrics, trpc_latency_read).
void* trpc_latency_create(const char* name, const char* desc) {
  if (name == nullptr || name[0] == '\0') {
    return nullptr;
  }
  auto* lat = new LatencyRecorder();
  lat->expose(name, desc != nullptr ? desc : "");
  return lat;
}

void trpc_latency_record(void* handle, int64_t latency_us) {
  if (handle != nullptr) {
    *static_cast<LatencyRecorder*>(handle) << latency_us;
  }
}

void trpc_latency_destroy(void* handle) {
  delete static_cast<LatencyRecorder*>(handle);
}

// A push-based scalar gauge (pipeline depth, inflight, window size).
void* trpc_gauge_create(const char* name, const char* desc) {
  if (name == nullptr || name[0] == '\0') {
    return nullptr;
  }
  auto* g = new IntGauge();
  g->expose(name, desc != nullptr ? desc : "");
  return g;
}

void trpc_gauge_set(void* handle, int64_t value) {
  if (handle != nullptr) {
    static_cast<IntGauge*>(handle)->set(value);
  }
}

int64_t trpc_gauge_add(void* handle, int64_t delta) {
  return handle != nullptr ? static_cast<IntGauge*>(handle)->add(delta)
                           : 0;
}

void trpc_gauge_destroy(void* handle) {
  delete static_cast<IntGauge*>(handle);
}

}  // extern "C"
