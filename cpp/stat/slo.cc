#include "stat/slo.h"

#include <algorithm>
#include <array>
#include <cmath>
#include <cstdlib>
#include <cstring>
#include <mutex>

#include "base/flags.h"
#include "base/json.h"
#include "base/time.h"
#include "stat/digest.h"
#include "stat/latency_recorder.h"
#include "stat/reducer.h"
#include "stat/variable.h"

namespace trpc {

namespace slo {

std::atomic<bool> g_enabled{false};

namespace {

Flag* fast_window_flag() {
  static Flag* f = [] {
    Flag* flag = Flag::define_int64(
        "trpc_slo_fast_window_ms", 300000,
        "SLO fast burn-rate window in ms (~5m scale; the 'is it still "
        "happening' window — breaches fire and clear within one of "
        "these).  Captured by Server::SetSlo at install time, so "
        "compress it BEFORE installing a spec in tests");
    if (flag != nullptr) {
      flag->set_int_range(200, 3600000);
    }
    return flag;
  }();
  return f;
}

Flag* slow_window_flag() {
  static Flag* f = [] {
    Flag* flag = Flag::define_int64(
        "trpc_slo_slow_window_ms", 3600000,
        "SLO slow burn-rate window in ms (~1h scale; the 'sustained "
        "damage' window — both windows must burn >= trpc_slo_burn_alert "
        "for a breach).  Captured at SetSlo install time");
    if (flag != nullptr) {
      flag->set_int_range(1000, 86400000);
    }
    return flag;
  }();
  return f;
}

Flag* burn_alert_flag() {
  static Flag* f = [] {
    Flag* flag = Flag::define_double(
        "trpc_slo_burn_alert", 2.0,
        "error-budget burn-rate threshold: a tenant breaches when BOTH "
        "its fast and slow windows burn budget at >= this multiple of "
        "the sustainable rate (1.0 = spending exactly the budget; the "
        "SRE-book fast-page default is ~2x)");
    if (flag != nullptr) {
      flag->set_validator([](const std::string& v) {
        char* end = nullptr;
        const double d = strtod(v.c_str(), &end);
        return end != v.c_str() && *end == '\0' && d >= 1.0 && d <= 1000.0;
      });
    }
    return flag;
  }();
  return f;
}

Flag* slo_flag() {
  static Flag* f = [] {
    fast_window_flag();  // companion knobs register alongside
    slow_window_flag();
    burn_alert_flag();
    Flag* flag = Flag::define_bool(
        "trpc_slo", false,
        "per-tenant SLO engine: windowed attainment + multi-window "
        "error-budget burn rates over Server::SetSlo targets, surfaced "
        "as slo_* vars, /slo and the naming:// fleet "
        "publication (default off; flag-off cost is one relaxed load "
        "per response and every slo_* var stays frozen at 0)");
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

struct SloVars {
  Adder breaches;
  Adder observed;

  SloVars() {
    breaches.expose(
        "slo_breach_total",
        "burn-rate breach EDGES fired across all SLO engines (a tenant "
        "entering breach counts once; clears don't count — frozen at 0 "
        "while trpc_slo has never been on)");
    observed.expose(
        "slo_observed_total",
        "responses scored against an SLO target while trpc_slo was on");
  }
};

SloVars* slo_vars() {
  // Deliberately leaked: the var registry outlives statics.
  static SloVars* v = new SloVars();
  return v;
}

std::string var_safe(const std::string& tenant) {
  if (tenant == "*") {
    return "default";  // "slo_tenant__" would be unreadable in /vars
  }
  std::string s = tenant;
  for (char& c : s) {
    if (!((c >= 'a' && c <= 'z') || (c >= 'A' && c <= 'Z') ||
          (c >= '0' && c <= '9') || c == '_')) {
      c = '_';
    }
  }
  return s;
}

std::string unique_name(const std::string& base) {
  std::string probe;
  std::string name = base;
  for (int i = 2; Variable::read_exposed(name, &probe); ++i) {
    name = base + "_" + std::to_string(i);
  }
  return name;
}

bool valid_tenant_name(const std::string& s) {
  if (s.empty() || s.size() > 64) {
    return false;
  }
  for (char c : s) {
    const bool ok = (c >= 'a' && c <= 'z') || (c >= 'A' && c <= 'Z') ||
                    (c >= '0' && c <= '9') || c == '_' || c == '-' ||
                    c == '.' || c == '*';
    if (!ok) {
      return false;
    }
  }
  return true;
}

template <typename T>
void put(std::string* out, T v) {
  out->append(reinterpret_cast<const char*>(&v), sizeof(T));
}

}  // namespace

void ensure_registered() {
  slo_flag();
  slo_vars();
}

int64_t fast_window_ms() { return fast_window_flag()->int64_value(); }
int64_t slow_window_ms() { return slow_window_flag()->int64_value(); }
double burn_alert() { return burn_alert_flag()->double_value(); }

uint64_t breach_total() {
  return static_cast<uint64_t>(slo_vars()->breaches.get_value());
}

}  // namespace slo

namespace {

// One burn-rate window: a bucketed ring with cached running sums, lazily
// advanced by the wall of the monotonic clock.  64 buckets keep the
// advance cheap and the expiry granularity at width/64.
struct BurnWindow {
  static constexpr int kBuckets = 64;
  struct B {
    int64_t total = 0, bad = 0, err = 0;
  };

  int64_t width_us = 0;
  int64_t bucket_us = 0;
  std::array<B, kBuckets> ring;
  int64_t head = -1;  // absolute bucket index at ring head
  int64_t total = 0, bad = 0, err = 0;

  void init(int64_t width_ms) {
    width_us = width_ms * 1000;
    bucket_us = std::max<int64_t>(1, width_us / kBuckets);
  }

  void advance(int64_t now_us) {
    const int64_t abs = now_us / bucket_us;
    if (head < 0) {
      head = abs;
      return;
    }
    if (abs - head >= kBuckets) {
      for (auto& b : ring) {
        b = B();
      }
      total = bad = err = 0;
      head = abs;
      return;
    }
    while (head < abs) {
      ++head;
      B& b = ring[head % kBuckets];
      total -= b.total;
      bad -= b.bad;
      err -= b.err;
      b = B();
    }
  }

  void add(bool is_bad, bool is_err) {
    B& b = ring[head % kBuckets];
    ++b.total;
    ++total;
    if (is_bad) {
      ++b.bad;
      ++bad;
    }
    if (is_err) {
      ++b.err;
      ++err;
    }
  }

  double bad_frac() const {
    return total > 0 ? static_cast<double>(bad) / total : 0.0;
  }

  double burn(double allowed) const { return bad_frac() / allowed; }
};

}  // namespace

struct SloEngine::Entry {
  std::string name;
  int64_t p99_target_us = 0;  // INT64_MAX when only avail was declared
  double avail_target = 0;    // fraction, e.g. 0.999
  double allowed = 0;         // error budget = max(1 - avail_target, 1e-6)

  mutable std::mutex mu;
  BurnWindow fast, slow;
  std::atomic<bool> breached{false};

  std::shared_ptr<LatencyRecorder> latency;
  std::vector<std::unique_ptr<Variable>> status_vars;
};

SloEngine::~SloEngine() = default;

SloEngine::Entry* SloEngine::find(const std::string& tenant) const {
  for (const auto& e : entries_) {
    if (e->name == tenant) {
      return e.get();
    }
  }
  return default_entry_;
}

namespace {

// Breach-state transition: both windows must burn to fire; either window
// recovering clears (fast recovers within one fast window of the fault
// ending — the "clear within one fast window" contract).  Only EDGES
// bump slo_breach_total.
void evaluate_breach(SloEngine::Entry* e, double burn_fast,
                     double burn_slow);

}  // namespace

void SloEngine::on_response(const std::string& tenant, int64_t latency_us,
                            bool error) {
  if (!slo::enabled()) {
    return;
  }
  Entry* e = find(tenant);
  if (e == nullptr) {
    return;
  }
  if (latency_us < 0) {
    latency_us = 0;
  }
  slo::slo_vars()->observed << 1;
  *e->latency << latency_us;
  const bool is_bad = error || latency_us > e->p99_target_us;
  const int64_t now = monotonic_time_us();
  double burn_fast, burn_slow;
  {
    std::lock_guard<std::mutex> g(e->mu);
    e->fast.advance(now);
    e->slow.advance(now);
    e->fast.add(is_bad, error);
    e->slow.add(is_bad, error);
    burn_fast = e->fast.burn(e->allowed);
    burn_slow = e->slow.burn(e->allowed);
  }
  evaluate_breach(e, burn_fast, burn_slow);
}

namespace {

void evaluate_breach(SloEngine::Entry* e, double burn_fast,
                     double burn_slow) {
  const double alert = slo::burn_alert();
  const bool now_breached = burn_fast >= alert && burn_slow >= alert;
  bool was = e->breached.load(std::memory_order_relaxed);
  if (now_breached == was ||
      !e->breached.compare_exchange_strong(was, now_breached,
                                           std::memory_order_relaxed)) {
    return;
  }
  if (now_breached) {
    slo::slo_vars()->breaches << 1;
  }
}

// Advances both windows to now and re-evaluates the breach state — read
// paths use this so a tenant whose traffic STOPPED after recovery still
// clears (on_response alone would leave the stale burn frozen).
struct EntrySnap {
  int64_t fast_total, fast_bad, fast_err;
  int64_t slow_total, slow_bad, slow_err;
  double burn_fast, burn_slow;
  bool breached;
};

EntrySnap snap_entry(SloEngine::Entry* e) {
  EntrySnap s;
  const int64_t now = monotonic_time_us();
  {
    std::lock_guard<std::mutex> g(e->mu);
    e->fast.advance(now);
    e->slow.advance(now);
    s.fast_total = e->fast.total;
    s.fast_bad = e->fast.bad;
    s.fast_err = e->fast.err;
    s.slow_total = e->slow.total;
    s.slow_bad = e->slow.bad;
    s.slow_err = e->slow.err;
    s.burn_fast = e->fast.burn(e->allowed);
    s.burn_slow = e->slow.burn(e->allowed);
  }
  evaluate_breach(e, s.burn_fast, s.burn_slow);
  s.breached = e->breached.load(std::memory_order_relaxed);
  return s;
}

}  // namespace

std::shared_ptr<SloEngine> SloEngine::parse(const std::string& spec,
                                            std::string* err) {
  err->clear();
  if (spec.empty()) {
    return nullptr;
  }
  slo::ensure_registered();
  std::shared_ptr<SloEngine> eng(new SloEngine());
  const int64_t fast_ms = slo::fast_window_ms();
  const int64_t slow_ms = slo::slow_window_ms();
  size_t pos = 0;
  while (pos < spec.size()) {
    size_t end = spec.find(';', pos);
    if (end == std::string::npos) {
      end = spec.size();
    }
    const std::string clause = spec.substr(pos, end - pos);
    pos = end + 1;
    if (clause.empty()) {
      continue;
    }
    const size_t colon = clause.find(':');
    if (colon == std::string::npos) {
      *err = "clause missing ':': " + clause;
      return nullptr;
    }
    auto entry = std::make_unique<Entry>();
    entry->name = clause.substr(0, colon);
    if (!slo::valid_tenant_name(entry->name)) {
      *err = "bad tenant name: " + entry->name;
      return nullptr;
    }
    for (const auto& prior : eng->entries_) {
      if (prior->name == entry->name) {
        *err = "duplicate tenant clause: " + entry->name;
        return nullptr;
      }
    }
    entry->p99_target_us = INT64_MAX;  // avail-only clause: latency never bad
    entry->avail_target = 0.99;       // default when only p99_us is given
    bool any_key = false;
    size_t kp = colon + 1;
    while (kp < clause.size()) {
      size_t ke = clause.find(',', kp);
      if (ke == std::string::npos) {
        ke = clause.size();
      }
      const std::string kv = clause.substr(kp, ke - kp);
      kp = ke + 1;
      if (kv.empty()) {
        continue;
      }
      const size_t eq = kv.find('=');
      if (eq == std::string::npos) {
        *err = "bad key=val: " + kv;
        return nullptr;
      }
      const std::string key = kv.substr(0, eq);
      const std::string val = kv.substr(eq + 1);
      if (key == "p99_us") {
        char* vend = nullptr;
        const long long t = strtoll(val.c_str(), &vend, 10);
        if (vend == val.c_str() || *vend != '\0' || t < 1) {
          *err = "bad p99_us: " + val;
          return nullptr;
        }
        entry->p99_target_us = t;
        any_key = true;
      } else if (key == "avail") {
        char* vend = nullptr;
        const double pct = strtod(val.c_str(), &vend);
        if (vend == val.c_str() || *vend != '\0' || pct <= 0.0 ||
            pct >= 100.0) {
          *err = "bad avail (percent in (0,100)): " + val;
          return nullptr;
        }
        entry->avail_target = pct / 100.0;
        any_key = true;
      } else {
        *err = "unknown key: " + key;
        return nullptr;
      }
    }
    if (!any_key) {
      *err = "clause declares no target: " + clause;
      return nullptr;
    }
    entry->allowed = std::max(1.0 - entry->avail_target, 1e-6);
    entry->fast.init(fast_ms);
    entry->slow.init(slow_ms);
    const std::string base = "slo_tenant_" + slo::var_safe(entry->name);
    entry->latency = std::make_shared<LatencyRecorder>();
    entry->latency->expose(
        slo::unique_name(base),
        "per-tenant SLO latency/qps feed of tenant '" + entry->name +
            "' (frozen while trpc_slo is off; snapshot published to the "
            "fleet as a mergeable digest)");
    Entry* raw = entry.get();
    auto burn_fast_var = std::make_unique<PassiveStatus<long>>([raw] {
      return static_cast<long>(snap_entry(raw).burn_fast * 1000.0);
    });
    burn_fast_var->expose(
        slo::unique_name(base + "_burn_fast_milli"),
        "fast-window error-budget burn rate of tenant '" + entry->name +
            "' in milli (1000 = burning exactly the budget)");
    entry->status_vars.push_back(std::move(burn_fast_var));
    auto burn_slow_var = std::make_unique<PassiveStatus<long>>([raw] {
      return static_cast<long>(snap_entry(raw).burn_slow * 1000.0);
    });
    burn_slow_var->expose(
        slo::unique_name(base + "_burn_slow_milli"),
        "slow-window error-budget burn rate of tenant '" + entry->name +
            "' in milli");
    entry->status_vars.push_back(std::move(burn_slow_var));
    auto attain_var = std::make_unique<PassiveStatus<long>>([raw] {
      const EntrySnap s = snap_entry(raw);
      return s.slow_total > 0
                 ? static_cast<long>(
                       (1.0 - static_cast<double>(s.slow_bad) /
                                  s.slow_total) *
                       1e6)
                 : 0L;  // no traffic in window — stays 0 (flag-off frozen)
    });
    attain_var->expose(
        slo::unique_name(base + "_attainment_ppm"),
        "slow-window SLO attainment of tenant '" + entry->name +
            "' in ppm (999000 = 99.9% of responses met the target; 0 "
            "when the window holds no traffic)");
    entry->status_vars.push_back(std::move(attain_var));
    auto breached_var = std::make_unique<PassiveStatus<long>>([raw] {
      return static_cast<long>(snap_entry(raw).breached ? 1 : 0);
    });
    breached_var->expose(
        slo::unique_name(base + "_breached"),
        "1 while tenant '" + entry->name +
            "' is in burn-rate breach (both windows >= "
            "trpc_slo_burn_alert), else 0");
    entry->status_vars.push_back(std::move(breached_var));
    if (entry->name == "*") {
      eng->default_entry_ = raw;
    }
    eng->entries_.push_back(std::move(entry));
  }
  if (eng->entries_.empty()) {
    *err = "empty spec";
    return nullptr;
  }
  return eng;
}

std::string SloEngine::dump_json() const {
  Json root = Json::object();
  root.set("enabled", Json::boolean(slo::enabled()));
  root.set("burn_alert", Json::number(slo::burn_alert()));
  root.set("breach_total",
           Json::number(static_cast<double>(slo::breach_total())));
  Json tenants = Json::array();
  for (const auto& e : entries_) {
    const EntrySnap s = snap_entry(e.get());
    Json t = Json::object();
    t.set("tenant", Json::str(e->name));
    t.set("p99_target_us",
          Json::number(e->p99_target_us == INT64_MAX
                           ? -1.0
                           : static_cast<double>(e->p99_target_us)));
    t.set("avail_target", Json::number(e->avail_target));
    Json fast = Json::object();
    fast.set("total", Json::number(static_cast<double>(s.fast_total)));
    fast.set("bad", Json::number(static_cast<double>(s.fast_bad)));
    fast.set("err", Json::number(static_cast<double>(s.fast_err)));
    fast.set("window_ms",
             Json::number(static_cast<double>(e->fast.width_us / 1000)));
    t.set("fast", std::move(fast));
    Json slow = Json::object();
    slow.set("total", Json::number(static_cast<double>(s.slow_total)));
    slow.set("bad", Json::number(static_cast<double>(s.slow_bad)));
    slow.set("err", Json::number(static_cast<double>(s.slow_err)));
    slow.set("window_ms",
             Json::number(static_cast<double>(e->slow.width_us / 1000)));
    t.set("slow", std::move(slow));
    t.set("burn_fast", Json::number(s.burn_fast));
    t.set("burn_slow", Json::number(s.burn_slow));
    const double attain =
        s.slow_total > 0
            ? 1.0 - static_cast<double>(s.slow_bad) / s.slow_total
            : 1.0;
    t.set("attainment", Json::number(attain));
    const double budget =
        std::max(0.0, std::min(1.0, 1.0 - s.burn_slow *
                                              (s.slow_total > 0 ? 1.0 : 0.0)));
    t.set("budget_remaining", Json::number(budget));
    t.set("breached", Json::boolean(s.breached));
    LatencyDigest d;
    e->latency->snapshot_digest(&d);
    Json lat = Json::object();
    lat.set("qps", Json::number(d.qps()));
    lat.set("avg_us", Json::number(d.avg_us()));
    lat.set("p50_us",
            Json::number(static_cast<double>(digest_percentile_us(d, 0.5))));
    lat.set("p99_us",
            Json::number(static_cast<double>(digest_percentile_us(d, 0.99))));
    lat.set("max_us", Json::number(static_cast<double>(d.max_us)));
    lat.set("count", Json::number(static_cast<double>(d.total_count)));
    t.set("latency", std::move(lat));
    tenants.push_back(std::move(t));
  }
  root.set("tenants", std::move(tenants));
  return root.dump();
}

std::string SloEngine::encode_blob(int64_t wall_us) const {
  // digest-wire 2 (TRPCFL01) — layout documented in stat/digest.h.
  std::string out;
  out.append("TRPCFL01", 8);
  slo::put<int64_t>(&out, wall_us);
  slo::put<uint32_t>(&out, static_cast<uint32_t>(entries_.size()));
  for (const auto& e : entries_) {
    const EntrySnap s = snap_entry(e.get());
    slo::put<uint16_t>(&out, static_cast<uint16_t>(e->name.size()));
    out.append(e->name);
    slo::put<int64_t>(&out, e->p99_target_us);
    slo::put<double>(&out, e->avail_target);
    slo::put<int64_t>(&out, e->fast.width_us / 1000);
    slo::put<int64_t>(&out, e->slow.width_us / 1000);
    slo::put<int64_t>(&out, s.fast_total);
    slo::put<int64_t>(&out, s.fast_bad);
    slo::put<int64_t>(&out, s.fast_err);
    slo::put<int64_t>(&out, s.slow_total);
    slo::put<int64_t>(&out, s.slow_bad);
    slo::put<int64_t>(&out, s.slow_err);
    slo::put<double>(&out, s.burn_fast);
    slo::put<double>(&out, s.burn_slow);
    slo::put<uint8_t>(&out, s.breached ? 1 : 0);
    LatencyDigest d;
    e->latency->snapshot_digest(&d);
    out += digest_encode(d);
  }
  return out;
}

namespace {

template <typename T>
bool take(const uint8_t*& p, const uint8_t* end, T* v) {
  if (static_cast<size_t>(end - p) < sizeof(T)) {
    return false;
  }
  memcpy(v, p, sizeof(T));
  p += sizeof(T);
  return true;
}

}  // namespace

bool fleet_blob_decode(const void* data, size_t len, FleetNodeBlob* out) {
  const uint8_t* p = static_cast<const uint8_t*>(data);
  const uint8_t* end = p + len;
  if (len < 8 || memcmp(p, "TRPCFL01", 8) != 0) {
    return false;
  }
  p += 8;
  *out = FleetNodeBlob();
  uint32_t nentries = 0;
  if (!take(p, end, &out->wall_us) || !take(p, end, &nentries) ||
      nentries > 4096) {
    return false;
  }
  out->tenants.reserve(nentries);
  for (uint32_t i = 0; i < nentries; ++i) {
    FleetTenantRecord r;
    uint16_t name_len = 0;
    if (!take(p, end, &name_len) ||
        static_cast<size_t>(end - p) < name_len) {
      return false;
    }
    r.tenant.assign(reinterpret_cast<const char*>(p), name_len);
    p += name_len;
    uint8_t breached = 0;
    if (!take(p, end, &r.p99_target_us) || !take(p, end, &r.avail_target) ||
        !take(p, end, &r.fast_window_ms) ||
        !take(p, end, &r.slow_window_ms) || !take(p, end, &r.fast_total) ||
        !take(p, end, &r.fast_bad) || !take(p, end, &r.fast_err) ||
        !take(p, end, &r.slow_total) || !take(p, end, &r.slow_bad) ||
        !take(p, end, &r.slow_err) || !take(p, end, &r.burn_fast) ||
        !take(p, end, &r.burn_slow) || !take(p, end, &breached)) {
      return false;
    }
    r.breached = breached != 0;
    const size_t used =
        digest_decode(p, static_cast<size_t>(end - p), &r.digest);
    if (used == 0) {
      return false;
    }
    p += used;
    out->tenants.push_back(std::move(r));
  }
  return true;
}

bool SloEngine::any_breached() const {
  for (const auto& e : entries_) {
    if (snap_entry(e.get()).breached) {
      return true;
    }
  }
  return false;
}

bool SloEngine::tenant_breached(const std::string& tenant) const {
  Entry* e = find(tenant);
  return e != nullptr && snap_entry(e).breached;
}

size_t SloEngine::tenant_count() const { return entries_.size(); }

}  // namespace trpc
