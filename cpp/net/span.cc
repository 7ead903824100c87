#include "net/span.h"

#include <unistd.h>

#include <cstdio>
#include <cstdlib>
#include <mutex>

#include "base/flags.h"
#include "base/json.h"
#include "base/rand.h"
#include "base/time.h"
#include "fiber/scheduler.h"

namespace trpc {

namespace {

constexpr size_t kDefaultRingSize = 4096;

Flag* rpcz_ring_size_flag();

Flag* rpcz_flag() {
  static Flag* f = [] {
    // Register the companion ring-size knob alongside, so any process
    // that can flip rpcz_enabled (every server's /flags) can also widen
    // the span window without a separate lazy touch.
    rpcz_ring_size_flag();
    return Flag::define_bool(
        "rpcz_enabled", false,
        "collect per-RPC spans, browsable via /rpcz "
        "(reference: -enable_rpcz)");
  }();
  return f;
}

// Leaked ring of finished spans (runtime registries outlive statics).
std::mutex& ring_mu() {
  static std::mutex* mu = new std::mutex();
  return *mu;
}
struct SpanRing {
  std::vector<Span> slots{kDefaultRingSize};
  size_t next = 0;
  size_t count = 0;
};
SpanRing& ring() {
  static SpanRing* r = new SpanRing();
  return *r;
}

// Rebuilds the ring at `cap` slots, keeping the newest spans that fit
// (oldest-of-kept lands at slot 0 so the walk order is unchanged).
void resize_ring(size_t cap) {
  std::lock_guard<std::mutex> g(ring_mu());
  SpanRing& r = ring();
  if (cap == r.slots.size()) {
    return;
  }
  std::vector<Span> fresh(cap);
  const size_t keep = r.count < cap ? r.count : cap;
  for (size_t i = 0; i < keep; ++i) {
    const size_t idx =
        (r.next + r.slots.size() - keep + i) % r.slots.size();
    fresh[i] = std::move(r.slots[idx]);
  }
  r.slots = std::move(fresh);
  r.count = keep;
  r.next = keep % cap;
}

// Reloadable ring capacity: a busy server at the default 4096 evicts a
// hunted span in well under a second; /flags/trpc_rpcz_ring_size lets an
// operator widen the window live without a restart.
Flag* rpcz_ring_size_flag() {
  static Flag* f = [] {
    Flag* fl = Flag::define_int64(
        "trpc_rpcz_ring_size", kDefaultRingSize,
        "rpcz span ring capacity (spans kept for /rpcz); reloadable, "
        "16..1048576, newest spans survive a resize");
    fl->set_validator([](const std::string& v) {
      if (v.empty()) {
        return false;
      }
      char* end = nullptr;
      const long n = strtol(v.c_str(), &end, 10);
      return end != nullptr && *end == '\0' && n >= 16 && n <= (1 << 20);
    });
    fl->on_update([](Flag* f2) {
      resize_ring(static_cast<size_t>(f2->int64_value()));
    });
    return fl;
  }();
  return f;
}

// Ambient (fiber-local) trace context, stored by VALUE directly on the
// FiberMeta (two relaxed-atomic u64 fields — no per-RPC allocation, no
// destructor, and the Span object may die before a child fiber reads
// the context).

// Off-fiber fallback: ctypes callers (Python threads) have no fiber
// context, but must still be able to install a trace around their sync
// calls — trpc_trace_set / trpc_span_start land here.
thread_local uint64_t tls_ambient_trace = 0;
thread_local uint64_t tls_ambient_span = 0;

}  // namespace

bool rpcz_enabled() { return rpcz_flag()->bool_value(); }

uint64_t new_span_id() {
  uint64_t id;
  do {
    id = fast_rand();
  } while (id == 0);
  return id;
}

Span* start_span(bool server_side, const std::string& method,
                 uint64_t trace_id, uint64_t parent_span_id) {
  auto* s = new Span();
  s->server_side = server_side;
  s->method = method;
  s->start_us = monotonic_time_us();
  s->span_id = new_span_id();
  if (trace_id != 0) {
    s->trace_id = trace_id;
    s->parent_span_id = parent_span_id;
  } else {
    uint64_t amb_trace = 0;
    uint64_t amb_span = 0;
    get_ambient_trace(&amb_trace, &amb_span);
    if (amb_trace != 0) {
      s->trace_id = amb_trace;
      s->parent_span_id = amb_span;
    } else {
      s->trace_id = new_span_id();  // fresh trace rooted here
    }
  }
  return s;
}

void span_annotate(Span* s, const std::string& text) {
  if (s != nullptr) {
    s->annotations.emplace_back(monotonic_time_us(), text);
  }
}

void submit_span(Span* s, int32_t error_code) {
  if (s == nullptr) {
    return;
  }
  s->end_us = monotonic_time_us();
  s->error_code = error_code;
  {
    std::lock_guard<std::mutex> g(ring_mu());
    SpanRing& r = ring();
    const size_t cap = r.slots.size();
    r.slots[r.next] = std::move(*s);
    r.next = (r.next + 1) % cap;
    if (r.count < cap) {
      ++r.count;
    }
  }
  delete s;
}

void set_ambient_span(const Span* s) {
  set_ambient_trace(s != nullptr ? s->trace_id : 0,
                    s != nullptr ? s->span_id : 0);
}

void set_ambient_trace(uint64_t trace_id, uint64_t span_id) {
  Worker* w = tls_worker;
  if (w != nullptr && w->current() != nullptr) {
    // Relaxed: same-fiber reads are program-ordered (migration rides the
    // scheduler's queue handoff; see scheduler.h).
    w->current()->ambient_trace.store(trace_id, std::memory_order_relaxed);
    w->current()->ambient_span.store(span_id, std::memory_order_relaxed);
  } else {
    tls_ambient_trace = trace_id;
    tls_ambient_span = span_id;
  }
}

void get_ambient_trace(uint64_t* trace_id, uint64_t* span_id) {
  Worker* w = tls_worker;
  if (w != nullptr && w->current() != nullptr) {
    // Relaxed: own-fiber context read (see set_ambient_trace).
    *trace_id = w->current()->ambient_trace.load(std::memory_order_relaxed);
    *span_id = w->current()->ambient_span.load(std::memory_order_relaxed);
  } else {
    *trace_id = tls_ambient_trace;
    *span_id = tls_ambient_span;
  }
}

std::vector<Span> recent_spans(size_t limit, uint64_t trace_id) {
  std::vector<Span> out;
  std::lock_guard<std::mutex> g(ring_mu());
  const SpanRing& r = ring();
  const size_t cap = r.slots.size();
  for (size_t i = 0; i < r.count && out.size() < limit; ++i) {
    // Newest first: walk backward from next-1.
    const size_t idx = (r.next + cap - 1 - i) % cap;
    const Span& s = r.slots[idx];
    if (trace_id == 0 || s.trace_id == trace_id) {
      out.push_back(s);
    }
  }
  return out;
}

size_t rpcz_ring_capacity() {
  rpcz_ring_size_flag();  // ensure registration
  std::lock_guard<std::mutex> g(ring_mu());
  return ring().slots.size();
}

namespace {
std::string hex_id(uint64_t id) {
  char buf[20];
  snprintf(buf, sizeof(buf), "%016llx",
           static_cast<unsigned long long>(id));
  return buf;
}
}  // namespace

std::string rpcz_dump_json(size_t limit, uint64_t trace_id) {
  Json root = Json::object();
  root.set("pid", Json::number(getpid()));
  // The mono/wall pair is read as close together as possible so the
  // stitcher's monotonic→wall mapping error is bounded by this gap.
  root.set("now_mono_us", Json::number(
      static_cast<double>(monotonic_time_us())));
  root.set("now_wall_us", Json::number(
      static_cast<double>(realtime_us())));
  Json spans = Json::array();
  for (const Span& s : recent_spans(limit, trace_id)) {
    Json j = Json::object();
    j.set("trace_id", Json::str(hex_id(s.trace_id)));
    j.set("span_id", Json::str(hex_id(s.span_id)));
    j.set("parent_span_id", Json::str(hex_id(s.parent_span_id)));
    j.set("side", Json::str(s.server_side ? "server" : "client"));
    j.set("method", Json::str(s.method));
    j.set("start_us", Json::number(static_cast<double>(s.start_us)));
    j.set("end_us", Json::number(static_cast<double>(s.end_us)));
    j.set("latency_us",
          Json::number(static_cast<double>(s.end_us - s.start_us)));
    j.set("error_code", Json::number(s.error_code));
    j.set("request_bytes",
          Json::number(static_cast<double>(s.request_bytes)));
    j.set("response_bytes",
          Json::number(static_cast<double>(s.response_bytes)));
    Json anns = Json::array();
    for (const auto& [ts, text] : s.annotations) {
      Json a = Json::object();
      a.set("ts_us", Json::number(static_cast<double>(ts)));
      a.set("text", Json::str(text));
      anns.push_back(std::move(a));
    }
    j.set("annotations", std::move(anns));
    spans.push_back(std::move(j));
  }
  root.set("spans", std::move(spans));
  return root.dump();
}

}  // namespace trpc
