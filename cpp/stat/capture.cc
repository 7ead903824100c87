#include "stat/capture.h"

#include <unistd.h>

#include <algorithm>
#include <cmath>
#include <cstdio>
#include <cstring>
#include <map>
#include <memory>
#include <mutex>
#include <vector>

#include "base/flags.h"
#include "base/json.h"
#include "base/recordio.h"
#include "base/time.h"
#include "stat/reducer.h"
#include "stat/variable.h"

namespace trpc {
namespace capture {

std::atomic<bool> g_enabled{false};

namespace {

// Strings in a retained record are clamped to this many bytes so
// reservoir memory is bounded by record count alone.
constexpr size_t kMaxStringBytes = 64;
// Binary record layout version (first byte of every record payload).
constexpr uint8_t kRecordVersion = 1;
// Fixed-width prefix of a serialized record before the two strings.
constexpr size_t kRecordFixedBytes = 68;

uint64_t splitmix64(uint64_t x) {
  x += 0x9e3779b97f4a7c15ULL;
  x = (x ^ (x >> 30)) * 0xbf58476d1ce4e5b9ULL;
  x = (x ^ (x >> 27)) * 0x94d049bb133111ebULL;
  return x ^ (x >> 31);
}

Flag* max_records_flag() {
  static Flag* f = [] {
    Flag* flag = Flag::define_int64(
        "trpc_capture_max_records", 65536,
        "traffic-capture reservoir capacity in records (~100 bytes of "
        "metadata each regardless of body size; per-tenant stratified — "
        "each tenant gets capacity/strata slots)");
    if (flag != nullptr) {
      // Range validator + introspectable bounds in one declaration.
      flag->set_int_range(256, 1 << 20);
    }
    return flag;
  }();
  return f;
}

Flag* sample_permille_flag() {
  static Flag* f = [] {
    Flag* flag = Flag::define_int64(
        "trpc_capture_sample_permille", 1000,
        "traffic-capture admission sampling rate in permille (1000 = "
        "record every request; sampling is a deterministic seeded hash "
        "of the per-window request index, so a seeded stream keeps the "
        "same records on every run)");
    if (flag != nullptr) {
      flag->set_int_range(0, 1000);
    }
    return flag;
  }();
  return f;
}

Flag* seed_flag() {
  static Flag* f = [] {
    Flag* flag = Flag::define_int64(
        "trpc_capture_seed", 1,
        "traffic-capture sampling seed (deterministic admission + "
        "reservoir eviction for a fixed request stream)");
    if (flag != nullptr) {
      flag->set_int_range(1, 1 << 30);
    }
    return flag;
  }();
  return f;
}

Flag* capture_flag() {
  static Flag* f = [] {
    max_records_flag();  // companion knobs register alongside
    sample_permille_flag();
    seed_flag();
    Flag* flag = Flag::define_bool(
        "trpc_capture", false,
        "traffic capture: sampled per-request metadata records (arrival "
        "time, method, tenant/priority, deadline budget, trace ids, "
        "sizes, status, queue+handler latency) in a per-tenant "
        "stratified reservoir, browsable via /capture and replayable by "
        "tools/traffic_replay.py (default off; flag-off cost is one "
        "relaxed load per request)");
    if (flag != nullptr) {
      flag->set_validator([](const std::string& v) {
        return v == "true" || v == "false" || v == "1" || v == "0" ||
               v == "on" || v == "off";
      });
      flag->on_update([](Flag* self) {
        g_enabled.store(self->bool_value(), std::memory_order_release);
      });
    }
    return flag;
  }();
  return f;
}

struct CaptureVars {
  Adder seen;
  Adder sampled;
  Adder dropped;
  std::unique_ptr<PassiveStatus<long>> records;

  CaptureVars() {
    seen.expose(
        "capture_seen_total",
        "requests offered to the traffic-capture reservoir while "
        "trpc_capture was on (frozen at 0 while it has never been on)");
    sampled.expose(
        "capture_sampled_total",
        "requests that passed the trpc_capture_sample_permille "
        "admission gate");
    dropped.expose(
        "capture_dropped_total",
        "sampled requests not retained because the capture reservoir "
        "was full (reservoir eviction or stratum quota) — nonzero means "
        "the capture is a uniform sample, not a complete record");
    records = std::make_unique<PassiveStatus<long>>(
        [] { return static_cast<long>(records_held()); });
    records->expose(
        "capture_records",
        "records currently held in the traffic-capture reservoir");
  }
};

CaptureVars* vars() {
  // Deliberately leaked: the var registry outlives statics.
  static CaptureVars* v = new CaptureVars();
  return v;
}

// Per-tenant stratum: an independent Algorithm-R reservoir.
struct Stratum {
  uint64_t seen = 0;  // sampled admissions for this tenant (window)
  std::vector<Sample> recs;
};

struct Buf {
  std::mutex mu;
  std::map<std::string, Stratum> strata;
  size_t total = 0;         // records across all strata
  uint64_t decision_idx = 0;  // per-window admission index (reset() zeroes)
  // Window counters — reset() zeroes these; the lifetime Adders never
  // rewind (Prometheus counter contract).
  uint64_t w_seen = 0;
  uint64_t w_sampled = 0;
  uint64_t w_dropped = 0;
};

Buf& buf() {
  static Buf* b = new Buf();  // leaked: dumps may outlive static teardown
  return *b;
}

void clamp_strings(Sample* s) {
  if (s->method.size() > kMaxStringBytes) {
    s->method.resize(kMaxStringBytes);
  }
  if (s->tenant.size() > kMaxStringBytes) {
    s->tenant.resize(kMaxStringBytes);
  }
}

// Evicts one record (seeded-random slot) from the largest stratum that
// holds more than `quota` records, making room for an under-quota
// stratum.  Returns false when no stratum is over quota.
bool steal_slot(Buf* b, size_t quota, uint64_t rnd) {
  Stratum* victim = nullptr;
  for (auto& kv : b->strata) {
    if (kv.second.recs.size() > quota &&
        (victim == nullptr ||
         kv.second.recs.size() > victim->recs.size())) {
      victim = &kv.second;
    }
  }
  if (victim == nullptr) {
    return false;
  }
  const size_t j = rnd % victim->recs.size();
  victim->recs[j] = std::move(victim->recs.back());
  victim->recs.pop_back();
  b->total--;
  return true;
}

template <typename T>
void append_le(std::string* out, T v) {
  char tmp[sizeof(T)];
  memcpy(tmp, &v, sizeof(T));
  out->append(tmp, sizeof(T));
}

template <typename T>
T read_le(const char* p) {
  T v;
  memcpy(&v, p, sizeof(T));
  return v;
}

std::string hex_id(uint64_t id) {
  char tmp[20];
  snprintf(tmp, sizeof(tmp), "%016llx",
           static_cast<unsigned long long>(id));
  return tmp;
}

struct WindowSnapshot {
  std::vector<Sample> recs;  // arrival order
  uint64_t w_seen = 0;
  uint64_t w_sampled = 0;
  uint64_t w_dropped = 0;
  std::map<std::string, uint64_t> stratum_seen;
};

WindowSnapshot snapshot() {
  WindowSnapshot out;
  Buf& b = buf();
  std::lock_guard<std::mutex> g(b.mu);
  out.w_seen = b.w_seen;
  out.w_sampled = b.w_sampled;
  out.w_dropped = b.w_dropped;
  out.recs.reserve(b.total);
  for (const auto& kv : b.strata) {
    out.stratum_seen[kv.first] = kv.second.seen;
    for (const Sample& s : kv.second.recs) {
      out.recs.push_back(s);
    }
  }
  std::sort(out.recs.begin(), out.recs.end(),
            [](const Sample& a, const Sample& c) {
              return a.arrival_mono_us < c.arrival_mono_us;
            });
  return out;
}

double percentile(std::vector<uint64_t>* v, double p) {
  if (v->empty()) {
    return 0;
  }
  std::sort(v->begin(), v->end());
  const size_t idx = std::min(
      v->size() - 1, static_cast<size_t>(p * (v->size() - 1) + 0.5));
  return static_cast<double>((*v)[idx]);
}

// Arrival-process summary over the kept records: per-second rate series
// + burstiness CV, log2 size histograms, per-tenant rate/latency/error
// mix, and fan-out stats reconstructed from trace ids.  Shared by the
// /capture JSON and the capture-file header (where it doubles as the
// recorded baseline the replay bench compares against).
Json build_summary(const WindowSnapshot& w) {
  Json out = Json::object();
  const size_t n = w.recs.size();
  out.set("kept", Json::number(static_cast<double>(n)));
  const int64_t permille = sample_permille_flag()->int64_value();
  out.set("sample_permille", Json::number(static_cast<double>(permille)));
  if (n == 0) {
    out.set("window_us", Json::number(0));
    return out;
  }
  const int64_t first = w.recs.front().arrival_mono_us;
  const int64_t last = w.recs.back().arrival_mono_us;
  const int64_t window_us = std::max<int64_t>(1, last - first);
  out.set("window_us", Json::number(static_cast<double>(window_us)));
  out.set("start_mono_us", Json::number(static_cast<double>(first)));
  out.set("start_wall_us",
          Json::number(static_cast<double>(w.recs.front().arrival_wall_us)));
  // Scale sampled counts back to offered rates (admission is permille).
  const double scale = permille > 0 ? 1000.0 / permille : 1.0;
  out.set("est_rate_rps",
          Json::number(n * scale * 1e6 / window_us));

  // Per-bucket rate series; bucket widens past 600 buckets so the JSON
  // stays bounded for long windows.
  const int64_t bucket_us =
      std::max<int64_t>(1000000, (window_us + 599) / 600);
  const size_t nbuckets =
      static_cast<size_t>((window_us + bucket_us - 1) / bucket_us) + 1;
  std::vector<uint64_t> series(nbuckets, 0);
  for (const Sample& s : w.recs) {
    series[static_cast<size_t>((s.arrival_mono_us - first) / bucket_us)]++;
  }
  out.set("rate_bucket_us", Json::number(static_cast<double>(bucket_us)));
  Json rate = Json::array();
  double mean = 0;
  for (uint64_t c : series) {
    rate.push_back(Json::number(static_cast<double>(c)));
    mean += static_cast<double>(c);
  }
  mean /= static_cast<double>(series.size());
  double var = 0;
  for (uint64_t c : series) {
    var += (c - mean) * (c - mean);
  }
  var /= static_cast<double>(series.size());
  out.set("rate_series", std::move(rate));
  // Coefficient of variation of the per-bucket counts — ~0 for constant
  // load, ~1 for Poisson-at-1/bucket, >1 for bursty arrivals.
  out.set("burstiness_cv",
          Json::number(mean > 0 ? std::sqrt(var) / mean : 0));

  // Log2 size histograms (bucket k = sizes in [2^(k-1), 2^k), bucket 0
  // = zero bytes), trimmed to the highest non-empty bucket.
  auto log2_bucket = [](uint64_t v) {
    size_t k = 0;
    while (v > 0) {
      v >>= 1;
      k++;
    }
    return k;
  };
  std::vector<uint64_t> req_hist(65, 0);
  std::vector<uint64_t> resp_hist(65, 0);
  for (const Sample& s : w.recs) {
    req_hist[log2_bucket(s.request_bytes)]++;
    resp_hist[log2_bucket(s.response_bytes)]++;
  }
  auto emit_hist = [](const std::vector<uint64_t>& h) {
    size_t hi = h.size();
    while (hi > 0 && h[hi - 1] == 0) {
      hi--;
    }
    Json arr = Json::array();
    for (size_t i = 0; i < hi; ++i) {
      arr.push_back(Json::number(static_cast<double>(h[i])));
    }
    return arr;
  };
  out.set("req_bytes_log2_hist", emit_hist(req_hist));
  out.set("resp_bytes_log2_hist", emit_hist(resp_hist));

  // Per-tenant baseline: rate, sizes, server-side latency percentiles
  // (queue + handler — what the replay bench compares loaded p99
  // against), and the recorded error mix.
  struct TenantAgg {
    uint64_t kept = 0;
    uint64_t req_bytes = 0;
    std::vector<uint64_t> total_us;
    std::vector<uint64_t> handler_us;
    std::map<int32_t, uint64_t> errors;
  };
  std::map<std::string, TenantAgg> agg;
  for (const Sample& s : w.recs) {
    TenantAgg& t = agg[s.tenant];
    t.kept++;
    t.req_bytes += s.request_bytes;
    t.total_us.push_back(static_cast<uint64_t>(s.queue_us) + s.handler_us);
    t.handler_us.push_back(s.handler_us);
    if (s.status != 0) {
      t.errors[s.status]++;
    }
  }
  Json tenants = Json::object();
  for (auto& kv : agg) {
    TenantAgg& t = kv.second;
    Json tj = Json::object();
    tj.set("kept", Json::number(static_cast<double>(t.kept)));
    auto it = w.stratum_seen.find(kv.first);
    const uint64_t seen = it != w.stratum_seen.end() ? it->second : t.kept;
    tj.set("sampled", Json::number(static_cast<double>(seen)));
    tj.set("est_rate_rps",
           Json::number(seen * scale * 1e6 / window_us));
    tj.set("mean_req_bytes",
           Json::number(static_cast<double>(t.req_bytes) / t.kept));
    tj.set("p50_us", Json::number(percentile(&t.total_us, 0.50)));
    tj.set("p99_us", Json::number(percentile(&t.total_us, 0.99)));
    tj.set("handler_p99_us", Json::number(percentile(&t.handler_us, 0.99)));
    Json errs = Json::object();
    for (const auto& e : t.errors) {
      errs.set(std::to_string(e.first),
               Json::number(static_cast<double>(e.second)));
    }
    tj.set("errors", std::move(errs));
    tenants.set(kv.first.empty() ? "*" : kv.first, std::move(tj));
  }
  out.set("tenants", std::move(tenants));

  // Fan-out shape from trace ids: records sharing a trace_id are nodes
  // of one logical request tree; parent_span_id != 0 marks an edge from
  // an upstream RPC.
  std::map<uint64_t, uint64_t> per_trace;
  uint64_t edge_records = 0;
  for (const Sample& s : w.recs) {
    if (s.trace_id != 0) {
      per_trace[s.trace_id]++;
    }
    if (s.parent_span_id != 0) {
      edge_records++;
    }
  }
  uint64_t multi = 0;
  uint64_t max_nodes = 0;
  uint64_t nodes = 0;
  for (const auto& kv : per_trace) {
    nodes += kv.second;
    max_nodes = std::max(max_nodes, kv.second);
    multi += kv.second > 1;
  }
  Json fanout = Json::object();
  fanout.set("traces", Json::number(static_cast<double>(per_trace.size())));
  fanout.set("multi_record_traces",
             Json::number(static_cast<double>(multi)));
  fanout.set("max_records_per_trace",
             Json::number(static_cast<double>(max_nodes)));
  fanout.set("mean_records_per_trace",
             Json::number(per_trace.empty()
                              ? 0
                              : static_cast<double>(nodes) /
                                    static_cast<double>(per_trace.size())));
  fanout.set("edge_records",
             Json::number(static_cast<double>(edge_records)));
  out.set("fanout", std::move(fanout));
  return out;
}

Json record_json(const Sample& s) {
  Json j = Json::object();
  j.set("arrival_mono_us",
        Json::number(static_cast<double>(s.arrival_mono_us)));
  j.set("arrival_wall_us",
        Json::number(static_cast<double>(s.arrival_wall_us)));
  j.set("method", Json::str(s.method));
  j.set("tenant", Json::str(s.tenant));
  j.set("priority", Json::number(s.priority));
  j.set("request_bytes",
        Json::number(static_cast<double>(s.request_bytes)));
  j.set("response_bytes",
        Json::number(static_cast<double>(s.response_bytes)));
  j.set("status", Json::number(s.status));
  j.set("queue_us", Json::number(s.queue_us));
  j.set("handler_us", Json::number(s.handler_us));
  j.set("deadline_budget_us", Json::number(s.deadline_budget_us));
  // Hex strings: 64-bit ids lose low bits as JSON doubles past 2^53.
  j.set("trace_id", Json::str(hex_id(s.trace_id)));
  j.set("parent_span_id", Json::str(hex_id(s.parent_span_id)));
  return j;
}

// Eager registration: /flags can list+flip trpc_capture and /vars shows
// the zeroed series before any traffic.
[[maybe_unused]] const bool g_capture_eager = [] {
  ensure_registered();
  return true;
}();

}  // namespace

void ensure_registered() {
  capture_flag();
  vars();
}

void record(Sample&& s) {
  if (!enabled()) {
    return;  // call sites gate too; this is belt-and-braces
  }
  ensure_registered();
  clamp_strings(&s);
  if (s.arrival_mono_us == 0) {
    s.arrival_mono_us = monotonic_time_us();
  }
  if (s.arrival_wall_us == 0) {
    // Derive the wall-clock arrival from the mono timestamp so the pair
    // stays coherent even when the record lands long after arrival.
    s.arrival_wall_us =
        realtime_us() - (monotonic_time_us() - s.arrival_mono_us);
  }
  const uint64_t seed =
      static_cast<uint64_t>(seed_flag()->int64_value());
  const int64_t permille = sample_permille_flag()->int64_value();
  const size_t cap = std::max<int64_t>(
      256, max_records_flag()->int64_value());
  bool kept = false;
  {
    Buf& b = buf();
    std::lock_guard<std::mutex> g(b.mu);
    vars()->seen << 1;
    b.w_seen++;
    const uint64_t idx = b.decision_idx++;
    if (permille < 1000 &&
        splitmix64(seed ^ (idx + 1)) % 1000 >=
            static_cast<uint64_t>(permille)) {
      return;  // not sampled: by design, not a coverage loss
    }
    vars()->sampled << 1;
    b.w_sampled++;
    Stratum& st = b.strata[s.tenant];
    st.seen++;
    const size_t quota =
        std::max<size_t>(1, cap / std::max<size_t>(1, b.strata.size()));
    if (st.recs.size() < quota) {
      // A late-arriving tenant may find the reservoir full of earlier
      // strata; steal a slot from the largest over-quota stratum so
      // every tenant converges to its fair share.
      bool room = b.total < cap;
      if (!room) {
        room = steal_slot(&b, quota, splitmix64(seed ^ ~idx));
        if (room) {
          vars()->dropped << 1;  // the stolen record is the drop
          b.w_dropped++;
        }
      }
      if (room) {
        st.recs.push_back(std::move(s));
        b.total++;
        kept = true;
      }
    }
    if (!kept) {
      // Stratum at quota (or nothing to steal): Algorithm R keeps a
      // uniform sample of this tenant's window — either the incoming
      // record replaces a uniformly-chosen slot, or it is the drop.
      const uint64_t j =
          splitmix64(seed ^ (idx * 0x9e3779b97f4a7c15ULL)) % st.seen;
      if (j < st.recs.size()) {
        st.recs[j] = std::move(s);
      }
      vars()->dropped << 1;  // exactly one record (old or new) dropped
      b.w_dropped++;
    }
  }
}

void serialize_record(const Sample& s, IOBuf* out) {
  std::string payload;
  payload.reserve(kRecordFixedBytes + s.method.size() + s.tenant.size());
  append_le<uint8_t>(&payload, kRecordVersion);
  append_le<int64_t>(&payload, s.arrival_mono_us);
  append_le<int64_t>(&payload, s.arrival_wall_us);
  append_le<uint64_t>(&payload, s.trace_id);
  append_le<uint64_t>(&payload, s.parent_span_id);
  append_le<uint64_t>(&payload, s.request_bytes);
  append_le<uint64_t>(&payload, s.response_bytes);
  append_le<int32_t>(&payload, s.status);
  append_le<uint32_t>(&payload, s.queue_us);
  append_le<uint32_t>(&payload, s.handler_us);
  append_le<uint32_t>(&payload, s.deadline_budget_us);
  append_le<uint8_t>(&payload, s.priority);
  append_le<uint8_t>(&payload, static_cast<uint8_t>(s.method.size()));
  append_le<uint8_t>(&payload, static_cast<uint8_t>(s.tenant.size()));
  payload += s.method;
  payload += s.tenant;
  out->append(payload);
}

bool parse_record(const IOBuf& in, Sample* out) {
  const size_t n = in.size();
  if (n < kRecordFixedBytes) {
    return false;
  }
  std::string flat = in.to_string();
  const char* p = flat.data();
  if (static_cast<uint8_t>(p[0]) != kRecordVersion) {
    return false;
  }
  out->arrival_mono_us = read_le<int64_t>(p + 1);
  out->arrival_wall_us = read_le<int64_t>(p + 9);
  out->trace_id = read_le<uint64_t>(p + 17);
  out->parent_span_id = read_le<uint64_t>(p + 25);
  out->request_bytes = read_le<uint64_t>(p + 33);
  out->response_bytes = read_le<uint64_t>(p + 41);
  out->status = read_le<int32_t>(p + 49);
  out->queue_us = read_le<uint32_t>(p + 53);
  out->handler_us = read_le<uint32_t>(p + 57);
  out->deadline_budget_us = read_le<uint32_t>(p + 61);
  out->priority = read_le<uint8_t>(p + 65);
  const size_t mlen = static_cast<uint8_t>(p[66]);
  const size_t tlen = static_cast<uint8_t>(p[67]);
  if (n < kRecordFixedBytes + mlen + tlen) {
    return false;
  }
  out->method.assign(p + kRecordFixedBytes, mlen);
  out->tenant.assign(p + kRecordFixedBytes + mlen, tlen);
  return true;
}

std::string dump_json(size_t max_records) {
  ensure_registered();
  const WindowSnapshot w = snapshot();
  Json root = Json::object();
  root.set("pid", Json::number(getpid()));
  // Mono/wall pair read back-to-back (same contract as rpcz): maps
  // this node's monotonic arrival times onto wall clock.
  root.set("now_mono_us",
           Json::number(static_cast<double>(monotonic_time_us())));
  root.set("now_wall_us",
           Json::number(static_cast<double>(realtime_us())));
  root.set("enabled", Json::boolean(enabled()));
  Json counters = Json::object();
  counters.set("seen_total",
               Json::number(static_cast<double>(seen_total())));
  counters.set("sampled_total",
               Json::number(static_cast<double>(sampled_total())));
  counters.set("dropped_total",
               Json::number(static_cast<double>(dropped_total())));
  counters.set("window_seen",
               Json::number(static_cast<double>(w.w_seen)));
  counters.set("window_sampled",
               Json::number(static_cast<double>(w.w_sampled)));
  counters.set("window_dropped",
               Json::number(static_cast<double>(w.w_dropped)));
  root.set("counters", std::move(counters));
  Json flags = Json::object();
  flags.set("max_records",
            Json::number(static_cast<double>(
                max_records_flag()->int64_value())));
  flags.set("sample_permille",
            Json::number(static_cast<double>(
                sample_permille_flag()->int64_value())));
  flags.set("seed",
            Json::number(static_cast<double>(seed_flag()->int64_value())));
  root.set("flags", std::move(flags));
  root.set("summary", build_summary(w));
  if (max_records > 0) {
    Json recs = Json::array();
    const size_t start =
        w.recs.size() > max_records ? w.recs.size() - max_records : 0;
    for (size_t i = start; i < w.recs.size(); ++i) {
      recs.push_back(record_json(w.recs[i]));
    }
    root.set("records", std::move(recs));
  }
  return root.dump();
}

int64_t dump_file(const std::string& path) {
  ensure_registered();
  const WindowSnapshot w = snapshot();
  // RecordWriter appends (rpc_dump semantics); a capture file is a
  // self-contained window — replace, never append a second header.
  std::remove(path.c_str());
  RecordWriter writer(path);
  if (!writer.valid()) {
    return -1;
  }
  Json header = Json::object();
  header.set("version", Json::number(kRecordVersion));
  header.set("pid", Json::number(getpid()));
  header.set("now_mono_us",
             Json::number(static_cast<double>(monotonic_time_us())));
  header.set("now_wall_us",
             Json::number(static_cast<double>(realtime_us())));
  Json counters = Json::object();
  counters.set("window_seen",
               Json::number(static_cast<double>(w.w_seen)));
  counters.set("window_sampled",
               Json::number(static_cast<double>(w.w_sampled)));
  counters.set("window_dropped",
               Json::number(static_cast<double>(w.w_dropped)));
  header.set("counters", std::move(counters));
  header.set("summary", build_summary(w));
  IOBuf head;
  head.append(kFileMagic, 8);
  head.append(header.dump());
  if (!writer.write(head)) {
    return -1;
  }
  for (const Sample& s : w.recs) {
    IOBuf rec;
    serialize_record(s, &rec);
    if (!writer.write(rec)) {
      return -1;
    }
  }
  writer.flush();
  return static_cast<int64_t>(w.recs.size());
}

void reset() {
  Buf& b = buf();
  std::lock_guard<std::mutex> g(b.mu);
  b.strata.clear();
  b.total = 0;
  b.decision_idx = 0;
  b.w_seen = 0;
  b.w_sampled = 0;
  b.w_dropped = 0;
}

uint64_t seen_total() {
  ensure_registered();
  return static_cast<uint64_t>(vars()->seen.get_value());
}

uint64_t sampled_total() {
  ensure_registered();
  return static_cast<uint64_t>(vars()->sampled.get_value());
}

uint64_t dropped_total() {
  ensure_registered();
  return static_cast<uint64_t>(vars()->dropped.get_value());
}

size_t records_held() {
  Buf& b = buf();
  std::lock_guard<std::mutex> g(b.mu);
  return b.total;
}

size_t approx_bytes() {
  Buf& b = buf();
  std::lock_guard<std::mutex> g(b.mu);
  size_t n = 0;
  for (const auto& kv : b.strata) {
    n += kv.second.recs.capacity() * sizeof(Sample);
    for (const Sample& s : kv.second.recs) {
      n += s.method.capacity() + s.tenant.capacity();
    }
  }
  return n;
}

}  // namespace capture
}  // namespace trpc
