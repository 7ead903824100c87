#include "net/qos.h"

#include <atomic>
#include <cstdlib>
#include <cstring>
#include <deque>
#include <map>
#include <mutex>

#include "base/flags.h"
#include "base/logging.h"
#include "fiber/analysis.h"
#include "fiber/fiber.h"
#include "net/protocol.h"
#include "stat/variable.h"

namespace trpc {

extern std::atomic<int64_t> g_socket_count;  // net/builtin.cc

namespace {

// ---- flags --------------------------------------------------------------

Flag* lanes_flag() {
  static Flag* f = [] {
    Flag* flag = Flag::define_int64(
        "trpc_qos_lanes", 0,
        "active QoS priority lanes (0 = subsystem off; 2..4 routes tagged "
        "requests through weighted-fair dispatch lanes)");
    if (flag != nullptr) {
      flag->set_validator([](const std::string& v) {
        char* end = nullptr;
        const long n = strtol(v.c_str(), &end, 10);
        return end != v.c_str() && *end == '\0' &&
               (n == 0 || (n >= 2 && n <= kQosMaxLanes));
      });
    }
    return flag;
  }();
  return f;
}

bool valid_weights(const std::string& v) {
  int count = 0;
  const char* p = v.c_str();
  while (*p != '\0') {
    char* end = nullptr;
    const long w = strtol(p, &end, 10);
    if (end == p || w < 1 || w > 4096) {
      return false;
    }
    ++count;
    p = end;
    if (*p == ',') {
      ++p;
      if (*p == '\0') {
        return false;  // trailing comma
      }
    } else if (*p != '\0') {
      return false;
    }
  }
  return count >= 1 && count <= kQosMaxLanes;
}

Flag* weights_flag() {
  static Flag* f = [] {
    Flag* flag = Flag::define_string(
        "trpc_qos_lane_weights", "8,4,2,1",
        "per-lane DRR weights, highest lane first (CSV; lanes beyond the "
        "list weigh 1)");
    if (flag != nullptr) {
      flag->set_validator(valid_weights);
    }
    return flag;
  }();
  return f;
}

// Eager definitions so /flags?setvalue can set them before first traffic.
[[maybe_unused]] Flag* const g_lanes_eager = lanes_flag();
[[maybe_unused]] Flag* const g_weights_eager = weights_flag();

void parse_weights(int64_t out[kQosMaxLanes]) {
  for (int i = 0; i < kQosMaxLanes; ++i) {
    out[i] = 1;
  }
  const std::string s = weights_flag()->string_value();
  const char* p = s.c_str();
  for (int i = 0; i < kQosMaxLanes && *p != '\0'; ++i) {
    char* end = nullptr;
    const long w = strtol(p, &end, 10);
    if (end == p) {
      break;  // validator keeps this unreachable; belt and braces
    }
    out[i] = w;
    p = *end == ',' ? end + 1 : end;
  }
}

// ---- tenant weight registry --------------------------------------------

std::mutex& weight_mu() {
  static std::mutex* mu = new std::mutex();
  return *mu;
}

std::map<std::string, int>& weight_map() {
  static auto* m = new std::map<std::string, int>();
  return *m;
}

// ---- lanes --------------------------------------------------------------

// Messages per unit of lane weight handed out each DRR round.  Small
// enough that a starved low lane waits at most one round's worth of
// higher-lane quanta, large enough to amortize the round bookkeeping.
constexpr int kQuantumUnit = 4;
constexpr size_t kQosDispatchBatch = 64;  // messenger's kDispatchBatch

struct Shard {
  std::mutex mu;
  std::deque<InputMessage*> q;
  // Max weight among tenants enqueued since the shard last drained empty:
  // the shard's DRR quantum inside its lane, so a weight-8 tenant's shard
  // is popped 8x per cursor pass of a weight-1 tenant's.
  int weight_hint = 1;
};

struct Lane {
  Shard shards[kQosLaneShards];
  std::atomic<int64_t> depth{0};
  // Drainer-owned DRR state (only the role holder touches these).
  int64_t deficit = 0;
  int cursor = 0;
  int credit = 0;
};

struct QosState {
  Lane lanes[kQosMaxLanes];
  std::atomic<bool> draining{false};
  std::atomic<bool> paused{false};
  std::atomic<void (*)(int, const std::string&)> tap{nullptr};
};

QosState& state() {
  static QosState* s = new QosState();
  return *s;
}

int64_t total_depth() {
  int64_t n = 0;
  for (Lane& lane : state().lanes) {
    // Acquire: pairs with the enqueue's depth increment so a drainer
    // deciding "empty" cannot miss a message already published.
    n += lane.depth.load(std::memory_order_acquire);
  }
  return n;
}

// Pops the next message of `lane` under its shard DRR (cursor advances
// after `credit = weight_hint` pops or when the shard empties).  Drainer
// role holder only.  nullptr when the whole lane is empty.
InputMessage* lane_pop(Lane& lane) {
  for (int scanned = 0; scanned < kQosLaneShards;) {
    Shard& sh = lane.shards[lane.cursor];
    std::unique_lock<std::mutex> g(sh.mu);
    if (sh.q.empty()) {
      sh.weight_hint = 1;  // decays once the backlog clears
      g.unlock();
      lane.cursor = (lane.cursor + 1) % kQosLaneShards;
      lane.credit = 0;
      ++scanned;
      continue;
    }
    if (lane.credit == 0) {
      lane.credit = sh.weight_hint;
    }
    InputMessage* m = sh.q.front();
    sh.q.pop_front();
    const bool emptied = sh.q.empty();
    g.unlock();
    if (--lane.credit == 0 || emptied) {
      lane.cursor = (lane.cursor + 1) % kQosLaneShards;
      lane.credit = 0;
    }
    lane.depth.fetch_sub(1, std::memory_order_acq_rel);
    return m;
  }
  return nullptr;
}

// Dispatch batch mirroring the messenger's bulk fiber spawn.  Messages
// the exhausted pool could not start are NOT run inline here — the
// caller holds the process-wide drainer role, and an inline handler that
// parks would wedge dispatch for every lane and socket at once.  They
// spill to `overflow` instead, processed by drive() AFTER the role is
// released (stalling only the one enqueuing fiber, exactly like the
// direct messenger path's exhaustion fallback).
struct QosBatch {
  void* args[kQosDispatchBatch];
  size_t n = 0;

  void flush(void (*process)(void*), std::vector<void*>* overflow) {
    if (n == 0) {
      return;
    }
    const size_t started = fiber_start_batch(process, args, n, 0);
    for (size_t i = started; i < n; ++i) {
      overflow->push_back(args[i]);
    }
    n = 0;
  }
};

// Pops one drainer acquisition may make before handing the role to a
// fresh fiber: the drainer runs INSIDE a read fiber's sweep, and without
// a budget one fiber could be pinned servicing the whole server's lanes
// while its own socket's remaining buffered frames go unparsed — the
// same head-of-line class trpc_messenger_cut_budget bounds on the
// direct path.
constexpr int64_t kDrainBudgetPops = 1024;

// Weighted-fair drain: DRR rounds across lanes (per-lane quantum = lane
// weight x kQuantumUnit; classic deficit reset when a lane runs dry)
// until every lane is empty, the pop budget is spent, or the test pause
// lands.  Returns false when it stopped on budget (backlog remains).
// Drainer role holder only.
bool drain_all(void (*process)(void*), std::vector<void*>* overflow) {
  QosState& st = state();
  int64_t weights[kQosMaxLanes];
  parse_weights(weights);
  QosVars& vars = qos_vars();
  QosBatch batch;
  int64_t budget = kDrainBudgetPops;
  bool any = true;
  // Acquire on paused/depth: the drainer must observe the test pause
  // flag and enqueue publications from other threads, not cached zeros.
  while (any && budget > 0 && !st.paused.load(std::memory_order_acquire)) {
    any = false;
    for (int i = 0; i < kQosMaxLanes; ++i) {
      Lane& lane = st.lanes[i];
      // Acquire: pairs with enqueue publication (see loop header).
      if (lane.depth.load(std::memory_order_acquire) == 0) {
        lane.deficit = 0;  // an idle lane accrues no credit (DRR)
        continue;
      }
      any = true;
      lane.deficit += weights[i] * kQuantumUnit;
      while (lane.deficit > 0) {
        InputMessage* m = lane_pop(lane);
        if (m == nullptr) {
          lane.deficit = 0;
          break;
        }
        --lane.deficit;
        --budget;
        vars.lane_dispatch[i] << 1;
        // Acquire: the test tap's callable must be fully constructed
        // before this drainer invokes it.
        auto tap = st.tap.load(std::memory_order_acquire);
        if (tap != nullptr) {
          tap(i, m->meta.qos_tenant);
        }
        batch.args[batch.n++] = m;
        if (batch.n == kQosDispatchBatch) {
          batch.flush(process, overflow);
        }
      }
    }
  }
  batch.flush(process, overflow);
  return budget > 0;
}

void drive(void (*process)(void*));

struct DrainHandoff {
  void (*process)(void*);
};

void drain_handoff_fiber(void* p) {
  std::unique_ptr<DrainHandoff> h(static_cast<DrainHandoff*>(p));
  drive(h->process);
}

// Claims the drainer role and drains; loops to close the race where a
// producer enqueued after the drain finished but saw the role taken.
// When an acquisition stops on its pop budget, the remaining backlog is
// handed to a FRESH fiber so the enqueuing read fiber gets back to its
// own socket's sweep (on fiber-pool exhaustion it keeps draining here —
// slow beats stranded).
void drive(void (*process)(void*)) {
  QosState& st = state();
  for (;;) {
    if (st.paused.load(std::memory_order_acquire)) {
      return;
    }
    if (st.draining.exchange(true, std::memory_order_acq_rel)) {
      return;  // current drainer will observe our message
    }
    std::vector<void*> overflow;
    bool finished;
    {
      // The drainer role is process-wide: a park while holding it wedges
      // every lane and socket at once — dispatch scope for the analysis
      // blocking detector (ISSUE 7).
      analysis::ScopedDispatch scope("qos drainer role");
      finished = drain_all(process, &overflow);
    }
    st.draining.store(false, std::memory_order_release);
    // Pool-exhaustion stragglers run AFTER the role release: a parking
    // handler now stalls only this fiber, never global lane dispatch.
    for (void* m : overflow) {
      process(m);
    }
    if (st.paused.load(std::memory_order_acquire) ||
        total_depth() == 0) {
      return;
    }
    if (!finished) {
      auto* h = new DrainHandoff{process};
      if (fiber_start(nullptr, drain_handoff_fiber, h, 0) == 0) {
        return;
      }
      delete h;
    }
  }
}

size_t shard_for(const std::string& tenant) {
  if (tenant.empty()) {
    // Untagged traffic round-robins so it cannot collapse onto (and then
    // monopolize) a single shard.
    static thread_local uint32_t rr = 0;
    return (rr++) % kQosLaneShards;
  }
  return std::hash<std::string>{}(tenant) % kQosLaneShards;
}

}  // namespace

int qos_lane_count() {
  const int64_t n = lanes_flag()->int64_value();
  return n >= 2 ? static_cast<int>(n) : 0;
}

int qos_lane_for(uint8_t priority, int lanes) {
  if (lanes <= 0) {
    return 0;
  }
  return priority >= lanes ? lanes - 1 : priority;
}

void qos_enqueue(int lane_idx, const std::string& tenant, InputMessage* msg,
                 void (*process)(void*)) {
  if (lane_idx < 0 || lane_idx >= kQosMaxLanes) {
    lane_idx = kQosMaxLanes - 1;
  }
  Lane& lane = state().lanes[lane_idx];
  Shard& sh = lane.shards[shard_for(tenant)];
  const int w = qos_tenant_weight(tenant);
  {
    std::lock_guard<std::mutex> g(sh.mu);
    sh.q.push_back(msg);
    if (w > sh.weight_hint) {
      sh.weight_hint = w;
    }
  }
  lane.depth.fetch_add(1, std::memory_order_acq_rel);
  qos_vars().enqueued << 1;
  drive(process);
}

int64_t qos_lane_depth(int lane) {
  if (lane < 0 || lane >= kQosMaxLanes) {
    return 0;
  }
  // Acquire: vars/tests reading depth pair with enqueue publication.
  return state().lanes[lane].depth.load(std::memory_order_acquire);
}

void qos_set_tenant_weight(const std::string& tenant, int weight) {
  weight = weight < 1 ? 1 : (weight > 1024 ? 1024 : weight);
  std::lock_guard<std::mutex> g(weight_mu());
  weight_map()[tenant] = weight;
}

int qos_tenant_weight(const std::string& tenant) {
  if (tenant.empty()) {
    return 1;
  }
  std::lock_guard<std::mutex> g(weight_mu());
  auto it = weight_map().find(tenant);
  return it != weight_map().end() ? it->second : 1;
}

void qos_test_pause(bool paused) {
  state().paused.store(paused, std::memory_order_release);
}

void qos_test_tap(void (*tap)(int, const std::string&)) {
  state().tap.store(tap, std::memory_order_release);
}

void qos_test_drive(void (*process)(void*)) { drive(process); }

// ---- vars ---------------------------------------------------------------

QosVars::QosVars() {
  enqueued.expose("qos_enqueue_total",
                  "requests routed through the QoS priority lanes");
  shed_total.expose(
      "qos_shed_total",
      "requests shed by per-tenant admission control (kEOverloaded)");
  for (int i = 0; i < kQosMaxLanes; ++i) {
    // No "_total" here: the Prometheus renderer appends it to counters.
    lane_dispatch[i].expose(
        "qos_lane_dispatch_" + std::to_string(i),
        "requests dispatched from QoS lane " + std::to_string(i));
    lane_depth.push_back(std::make_unique<PassiveStatus<long>>(
        [i] { return static_cast<long>(qos_lane_depth(i)); }));
    lane_depth.back()->expose(
        "qos_lane_depth_" + std::to_string(i),
        "requests currently queued in QoS lane " + std::to_string(i));
  }
  live_sockets = std::make_unique<PassiveStatus<long>>([] {
    // Relaxed: a monotonic-ish diagnostic gauge — off-by-a-few during a
    // churn burst is fine, no data hangs off the count.
    return static_cast<long>(
        g_socket_count.load(std::memory_order_relaxed));
  });
  live_sockets->expose(
      "rpc_socket_live",
      "live sockets in the socket map (the 100k-connection front door's "
      "memory driver; pair with process_memory_rss_kb)");
}

QosVars& qos_vars() {
  static QosVars* v = new QosVars();
  return *v;
}

void expose_qos_variables() { qos_vars(); }

// ---- TenantGovernor -----------------------------------------------------

namespace {

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

std::string var_safe(const std::string& tenant) {
  if (tenant == "*") {
    return "default";  // "qos_tenant__" would be unreadable in /vars
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

// Servers are long-lived but tests create many: suffix duplicate names
// like observe.py's unique_var_name so a second governor's recorder never
// shadows the first's series.
std::string unique_name(const std::string& base) {
  std::string probe;
  std::string name = base;
  for (int i = 2; Variable::read_exposed(name, &probe); ++i) {
    name = base + "_" + std::to_string(i);
  }
  return name;
}

}  // namespace

std::shared_ptr<TenantGovernor> TenantGovernor::parse(
    const std::string& spec, std::string* err) {
  err->clear();
  if (spec.empty()) {
    return nullptr;
  }
  auto gov = std::make_shared<TenantGovernor>();
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
    if (!valid_tenant_name(entry->name)) {
      *err = "bad tenant name: " + entry->name;
      return nullptr;
    }
    // key=val pairs.
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
      if (key == "weight") {
        char* wend = nullptr;
        const long w = strtol(val.c_str(), &wend, 10);
        if (wend == val.c_str() || *wend != '\0' || w < 1 || w > 1024) {
          *err = "bad weight: " + val;
          return nullptr;
        }
        entry->weight = static_cast<int>(w);
      } else if (key == "limit") {
        auto [ok, limiter] = parse_concurrency_spec(val);
        if (!ok) {
          *err = "bad limit spec: " + val;
          return nullptr;
        }
        entry->limiter = std::move(limiter);
      } else {
        *err = "unknown key: " + key;
        return nullptr;
      }
    }
    const std::string base = "qos_tenant_" + var_safe(entry->name);
    entry->latency = std::make_shared<LatencyRecorder>();
    entry->latency->expose(
        unique_name(base),
        "per-tenant QoS latency/qps of tenant '" + entry->name + "'");
    entry->shed = std::make_shared<Adder>();
    entry->shed->expose(
        unique_name(base + "_shed_total"),
        "requests shed for tenant '" + entry->name + "' by admission "
        "control");
    if (entry->name == "*") {
      gov->default_entry_ = entry.get();
    }
    gov->entries_.push_back(std::move(entry));
  }
  if (gov->entries_.empty()) {
    *err = "empty spec";
    return nullptr;
  }
  // Weights land in the process-global registry (the weighted-fair
  // dequeue reads it at enqueue time) only once the WHOLE spec
  // validated — a rejected spec must not leave half its weights behind.
  // The registry is process-global by design (the messenger has no
  // server context at enqueue time): governors on two servers sharing a
  // tenant name share its weight, last SetQos wins.
  for (const auto& e : gov->entries_) {
    if (e->name != "*") {
      qos_set_tenant_weight(e->name, e->weight);
    }
  }
  return gov;
}

TenantGovernor::Entry* TenantGovernor::find(const std::string& tenant) {
  if (!tenant.empty()) {
    for (const auto& e : entries_) {
      if (e->name == tenant) {
        return e.get();
      }
    }
  }
  return default_entry_;
}

TenantGovernor::Entry* TenantGovernor::admit(const std::string& tenant,
                                             bool* admitted) {
  Entry* e = find(tenant);
  if (e == nullptr) {
    *admitted = true;  // no clause: unlimited
    return nullptr;
  }
  if (e->limiter != nullptr && !e->limiter->on_request()) {
    *e->shed << 1;
    qos_vars().shed_total << 1;
    *admitted = false;
    return e;
  }
  *admitted = true;
  return e;
}

void TenantGovernor::on_response(Entry* e, int64_t latency_us, bool error) {
  if (e == nullptr) {
    return;
  }
  if (e->limiter != nullptr) {
    e->limiter->on_response(latency_us, error);
  }
  if (latency_us > 0) {
    *e->latency << latency_us;
  }
}

}  // namespace trpc
