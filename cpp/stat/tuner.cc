#include "stat/tuner.h"

#include <unistd.h>

#include <algorithm>
#include <atomic>
#include <cmath>
#include <cstdlib>
#include <cstring>
#include <deque>
#include <map>
#include <memory>
#include <mutex>
#include <string>
#include <thread>
#include <vector>

#include "base/flags.h"
#include "base/json.h"
#include "base/time.h"
#include "stat/variable.h"

namespace trpc {
namespace tuner {

namespace {

// ---- flags ---------------------------------------------------------------

std::atomic<bool> g_enabled{false};
void start_loop_if_needed();  // defined with the loop below

Flag* interval_flag() {
  static Flag* f = [] {
    Flag* flag = Flag::define_int64(
        "trpc_tuner_interval_ms", 100,
        "self-tuning controller sampling tick in ms ([10, 3600000]); "
        "rules evaluate every trpc_tuner_eval_ticks ticks");
    if (flag != nullptr) {
      flag->set_int_range(10, 3600000);
    }
    return flag;
  }();
  return f;
}

Flag* eval_ticks_flag() {
  static Flag* f = [] {
    Flag* flag = Flag::define_int64(
        "trpc_tuner_eval_ticks", 3,
        "sampling ticks per tuner evaluation window ([1, 1000]); one "
        "window = one pending-change verdict and at most one new knob "
        "move process-wide");
    if (flag != nullptr) {
      flag->set_int_range(1, 1000);
    }
    return flag;
  }();
  return f;
}

Flag* hysteresis_flag() {
  static Flag* f = [] {
    Flag* flag = Flag::define_int64(
        "trpc_tuner_hysteresis_pct", 5,
        "percentage band a metric must move past before the tuner "
        "calls a change better or worse ([0, 90]); inside the band a "
        "probe is neutral and simply kept");
    if (flag != nullptr) {
      flag->set_int_range(0, 90);
    }
    return flag;
  }();
  return f;
}

Flag* freeze_flag() {
  static Flag* f = [] {
    Flag* flag = Flag::define_int64(
        "trpc_tuner_freeze_ticks", 20,
        "base evaluation windows a knob stays frozen after the "
        "revert-on-regression guard trips ([1, 100000]); doubles per "
        "consecutive trip up to 64x");
    if (flag != nullptr) {
      flag->set_int_range(1, 100000);
    }
    return flag;
  }();
  return f;
}

Flag* tuner_flag() {
  static Flag* f = [] {
    interval_flag();
    eval_ticks_flag();
    hysteresis_flag();
    freeze_flag();
    Flag* flag = Flag::define_bool(
        "trpc_tuner", false,
        "self-tuning controller: samples the var surfaces and drives "
        "per-knob feedback rules (hill-climb/AIMD with hysteresis, "
        "cooldown, revert-on-regression + freeze) through the validated "
        "flag-reload path; decisions journal to /tuner (default off; "
        "while off no thread runs and nothing is sampled)");
    if (flag != nullptr) {
      flag->set_validator([](const std::string& v) {
        return v == "true" || v == "false" || v == "1" || v == "0" ||
               v == "on" || v == "off";
      });
      flag->on_update([](Flag* self) {
        const bool on = self->bool_value();
        g_enabled.store(on, std::memory_order_release);
        if (on) {
          start_loop_if_needed();
        }
      });
    }
    return flag;
  }();
  return f;
}

// ---- vars the controller samples ----------------------------------------
// Rule targets/signals plus the status inputs /tuner reports.
// lint_trpc.py's tuner-rule check requires every entry to be an exposed
// var carrying Prometheus HELP (names ending in '_' match dynamically-
// suffixed families, e.g. qos_lane_depth_<n>).
constexpr const char* kTunerInputs[] = {
    "stripe_rx_bytes",               // tuner-input
    "stripe_tx_bytes",               // tuner-input
    "stripe_reassembled",            // tuner-input
    "messenger_cut_budget_yields",   // tuner-input
    "messenger_dispatch_messages",   // tuner-input
    "socket_inline_write_attempts",  // tuner-input
    "socket_inline_write_hits",      // tuner-input
    "qos_lane_depth_",               // tuner-input (one var per lane)
    "qos_lane_dispatch_",            // tuner-input (one var per lane)
    "rma_window_full",               // tuner-input
    "rma_tx_bytes",                  // tuner-input
    "coll_put_bytes",                // tuner-input
    "messenger_probe_stall_skips",   // tuner-input
};

// ---- built-in rule table -------------------------------------------------
// Every knob below must be a defined, validated, *reloadable* trpc_*
// flag — lint_trpc.py's tuner-rule check parses the tuner-knob markers
// against the flag definitions in cpp/.
std::vector<Rule> builtin_rules() {
  std::vector<Rule> v;
  {
    // Stripe chunk geometry: bigger chunks amortize per-frame cost,
    // smaller ones pipeline rails deeper — the optimum is the box's.
    Rule r;
    r.knob = "trpc_stripe_chunk_bytes";  // tuner-knob (trpc_stripe_chunk_bytes)
    r.mode = Mode::kHillClimb;
    r.target = "stripe_rx_bytes";
    r.min_activity = 8e6;  // act only while striping >= 8 MB/s
    r.step_mul = 2.0;
    v.push_back(r);
  }
  {
    Rule r;
    r.knob = "trpc_stripe_rails";  // tuner-knob (trpc_stripe_rails)
    r.mode = Mode::kHillClimb;
    r.target = "stripe_rx_bytes";
    r.min_activity = 8e6;
    r.step_add = 1;
    v.push_back(r);
  }
  {
    // Messenger cut budget, AIMD like the concurrency limiter: a backed-
    // up priority lane (HOL pressure) halves it; sustained cut-budget
    // yields while the lane is quiet double it back.
    Rule r;
    r.knob = "trpc_messenger_cut_budget";  // tuner-knob (trpc_messenger_cut_budget)
    r.mode = Mode::kAimd;
    r.pressure = "qos_lane_depth_0";
    r.pressure_is_level = true;
    r.pressure_high = 4.0;
    r.grow = "messenger_cut_budget_yields";
    r.grow_min = 20.0;  // yields/s before the budget is called binding
    // Growth is judged on dispatch throughput, not on the yields it
    // trivially erases: a bigger budget that doesn't move messages
    // faster is retracted (on this box a small budget often WINS —
    // yields interleave small RPCs better).
    r.objective = "messenger_dispatch_messages";
    r.relief_dir = -1;
    r.step_mul = 2.0;
    r.min = 64 << 10;
    r.max = 256ll << 20;
    r.skip_at_value = 0;  // 0 = never yield, an operator's deliberate
                          // choice the tuner must not override
    v.push_back(r);
  }
  {
    // RMA receive window: window-full fallbacks mean one-sided sends are
    // degrading to the copy path — double the window (new connections
    // pick it up; power-of-two preserved by exact doubling).
    Rule r;
    r.knob = "trpc_rma_window_bytes";  // tuner-knob (trpc_rma_window_bytes)
    r.mode = Mode::kAimd;
    r.pressure = "rma_window_full";
    r.pressure_is_level = false;  // fallbacks/s
    r.pressure_high = 0.5;
    r.relief_dir = 1;
    r.step_mul = 2.0;
    r.skip_at_value = 0;  // 0 = rma plane disabled: never re-enable
    v.push_back(r);
  }
  {
    Rule r;
    r.knob = "trpc_coll_chunk_bytes";  // tuner-knob (trpc_coll_chunk_bytes)
    r.mode = Mode::kHillClimb;
    r.target = "coll_put_bytes";
    r.min_activity = 8e6;
    r.step_mul = 2.0;
    v.push_back(r);
  }
  {
    Rule r;
    r.knob = "trpc_coll_inflight";  // tuner-knob (trpc_coll_inflight)
    r.mode = Mode::kHillClimb;
    r.target = "coll_put_bytes";
    r.min_activity = 8e6;
    r.step_add = 1;
    v.push_back(r);
  }
  {
    // QoS lane weights: while the highest-priority lane stays backed up,
    // double its DRR weight (CSV rewrite through the validated path).
    Rule r;
    r.knob = "trpc_qos_lane_weights";  // tuner-knob (trpc_qos_lane_weights)
    r.mode = Mode::kQosWeights;
    r.pressure = "qos_lane_depth_0";
    r.pressure_is_level = true;
    r.pressure_high = 2.0;
    v.push_back(r);
  }
  return v;
}

// ---- engine --------------------------------------------------------------

struct VarSeries {
  double last_raw = 0.0;
  bool have_raw = false;
  double ema = 0.0;  // rate/s for counters, level for gauges
  bool have_ema = false;
};

struct Decision {
  uint64_t seq;
  int64_t ts_mono_us;
  int64_t ts_wall_us;
  std::string knob;
  int64_t old_num;
  int64_t new_num;
  std::string old_str;  // string knobs (qos weights); empty for ints
  std::string new_str;
  std::string action;  // apply | revert | freeze
  std::string reason;
  double metric_before;
  double metric_after;
};

struct RuleState {
  Rule rule;
  Flag* flag = nullptr;
  int64_t lo = 0;
  int64_t hi = 0;
  int64_t dflt = 0;
  int dir = 0;
  int64_t prev_num = 0;
  std::string prev_str;
  double metric_at_change = 0.0;
  // Which series the pending change is judged on, and in which sense —
  // an AIMD growth move guards its growth signal (minimize), a pressure
  // move its pressure signal (minimize), a hill-climb its target
  // (maximize).
  std::string pending_metric;
  bool pending_maximize = false;
  bool pending = false;
  int cooldown = 0;  // evaluation windows to skip before acting again
  int freeze = 0;    // frozen evaluation windows left
  int backoff = 1;   // freeze multiplier (doubles per guard trip)
  int fails = 0;     // consecutive worsened probes (both directions)
  int neutral_streak = 0;  // consecutive no-effect probes (re-probe pacing)
};

struct Engine {
  std::mutex mu;  // ticks come from the loop thread OR tick_once_for_test
  bool builtins_installed = false;
  std::vector<RuleState> rules;
  std::vector<Rule> extra_rules;  // added before install; merged on tick
  // Rules whose knob flag wasn't registered yet (lazily-defined net/
  // flags, e.g. the collective knobs): re-tried each tick so a plane
  // that comes up AFTER the tuner still gets its rules.
  std::vector<Rule> unresolved_rules;
  size_t rr = 0;
  int64_t last_tick_us = 0;
  int ticks_in_window = 0;
  std::map<std::string, VarSeries> series;
  std::deque<Decision> journal;
  uint64_t seq = 0;
  // Lifetime counters (the tuner_* vars read these; relaxed — pure
  // monotonic telemetry, no data hangs off them).
  std::atomic<uint64_t> ticks{0};
  std::atomic<uint64_t> decisions{0};
  std::atomic<uint64_t> reverts{0};
  std::atomic<uint64_t> freezes{0};
  std::atomic<uint64_t> rejected{0};  // validated set refused (must stay 0)
  // Maintained by the tick so the /vars PassiveStatus can read it
  // WITHOUT taking mu — dump_exposed evaluates vars under the registry
  // lock, and a lambda taking mu there would invert the tick's
  // mu -> registry-lock order (ABBA).
  std::atomic<long> frozen_now{0};
};

Engine& engine() {
  static Engine* e = new Engine();  // leaked with the registries
  return *e;
}

struct TunerVars {
  std::unique_ptr<PassiveStatus<long>> ticks, decisions, reverts, freezes,
      frozen, rejected;
  TunerVars() {
    ticks = std::make_unique<PassiveStatus<long>>(
        [] { return static_cast<long>(ticks_total()); });
    ticks->expose("tuner_ticks_total",
                  "self-tuning controller sampling ticks (frozen at 0 "
                  "while trpc_tuner has never been on)");
    decisions = std::make_unique<PassiveStatus<long>>(
        [] { return static_cast<long>(decisions_total()); });
    decisions->expose("tuner_decisions_total",
                      "knob changes the tuner applied through the "
                      "validated flag-reload path");
    reverts = std::make_unique<PassiveStatus<long>>(
        [] { return static_cast<long>(reverts_total()); });
    reverts->expose("tuner_reverts_total",
                    "tuner changes rolled back by the revert-on-"
                    "regression guard");
    freezes = std::make_unique<PassiveStatus<long>>(
        [] { return static_cast<long>(freezes_total()); });
    freezes->expose("tuner_freezes_total",
                    "knobs frozen for a backoff period after repeated "
                    "regressing probes");
    frozen = std::make_unique<PassiveStatus<long>>([] {
      // Relaxed: gauge maintained by the tick (see Engine::frozen_now —
      // taking the engine mutex here would deadlock against /vars).
      return engine().frozen_now.load(std::memory_order_relaxed);
    });
    frozen->expose("tuner_frozen_knobs",
                   "knobs currently held frozen by the regression guard");
    rejected = std::make_unique<PassiveStatus<long>>([] {
      // Relaxed: telemetry counter read.
      return static_cast<long>(
          engine().rejected.load(std::memory_order_relaxed));
    });
    rejected->expose("tuner_set_rejected",
                     "tuner actuations refused by a flag validator — "
                     "bounds clamping makes this provably 0");
  }
};

// ---- sampling ------------------------------------------------------------

bool read_var_number(const std::string& name, double* out) {
  std::string s;
  if (!Variable::read_exposed(name, &s)) {
    return false;
  }
  char* end = nullptr;
  const double v = strtod(s.c_str(), &end);
  if (end == s.c_str()) {
    return false;
  }
  *out = v;
  return true;
}

// Updates one var's series for this tick; counters become rates/s.
void sample_var(Engine& e, const std::string& name, bool is_level,
                double dt_s) {
  if (name.empty()) {
    return;
  }
  VarSeries& vs = e.series[name];
  double raw = 0.0;
  if (!read_var_number(name, &raw)) {
    vs.have_raw = false;
    vs.have_ema = false;
    return;
  }
  double sample = raw;
  if (!is_level) {
    if (!vs.have_raw || dt_s <= 0.0) {
      vs.last_raw = raw;
      vs.have_raw = true;
      return;  // first observation: no rate yet
    }
    sample = (raw - vs.last_raw) / dt_s;
    if (sample < 0.0) {
      sample = 0.0;  // counter reset (tests): treat as idle
    }
    vs.last_raw = raw;
  }
  vs.have_raw = true;
  // Responsive EMA: ~87% new weight across a 3-tick window.
  vs.ema = vs.have_ema ? 0.5 * vs.ema + 0.5 * sample : sample;
  vs.have_ema = true;
}

bool series_value(Engine& e, const std::string& name, double* out) {
  auto it = e.series.find(name);
  if (it == e.series.end() || !it->second.have_ema) {
    return false;
  }
  *out = it->second.ema;
  return true;
}

// ---- journal + actuation -------------------------------------------------

void journal_decision(Engine& e, const std::string& knob, int64_t old_num,
                      int64_t new_num, const std::string& old_str,
                      const std::string& new_str, const char* action,
                      std::string reason, double before, double after) {
  Decision d;
  d.seq = ++e.seq;
  d.ts_mono_us = monotonic_time_us();
  d.ts_wall_us = realtime_us();
  d.knob = knob;
  d.old_num = old_num;
  d.new_num = new_num;
  d.old_str = old_str;
  d.new_str = new_str;
  d.action = action;
  d.reason = std::move(reason);
  d.metric_before = before;
  d.metric_after = after;
  e.journal.push_back(std::move(d));
  while (e.journal.size() > 512) {
    e.journal.pop_front();
  }
  // Relaxed: pure telemetry counters.  Only APPLIED changes count —
  // reverts/freezes journal too but have their own counters;
  // tuner_decisions_total must mean "the tuner retuned something", not
  // "the journal grew".
  if (strcmp(action, "apply") == 0) {
    e.decisions.fetch_add(1, std::memory_order_relaxed);
  }
}

// Validated set; clamping upstream makes rejection impossible — the
// tuner_set_rejected var proves it at test time.
bool apply_set(Engine& e, RuleState& s, const std::string& value) {
  if (Flag::set(s.rule.knob, value) != 0) {
    e.rejected.fetch_add(1, std::memory_order_relaxed);
    return false;
  }
  return true;
}

int64_t clamp_knob(const RuleState& s, int64_t v) {
  return std::min(s.hi, std::max(s.lo, v));
}

int64_t step_value(const RuleState& s, int64_t cur, int dir) {
  int64_t next;
  if (s.rule.step_add > 0) {
    next = cur + dir * s.rule.step_add;
  } else if (dir > 0) {
    next = static_cast<int64_t>(std::llround(cur * s.rule.step_mul));
  } else {
    next = static_cast<int64_t>(std::llround(cur / s.rule.step_mul));
  }
  return clamp_knob(s, next);
}

// ---- rule installation ---------------------------------------------------

bool install_rule(Engine& e, const Rule& r, bool quiet) {
  Flag* f = Flag::find(r.knob);
  if (f == nullptr || !f->reloadable()) {
    return false;
  }
  // Mode/type agreement: numeric modes actuate int64 flags only (a
  // hill-climb on a string flag would clobber it with a number the
  // validator might happen to accept); the qos-weights rule is the one
  // string actuator.
  if (r.mode == Mode::kQosWeights) {
    if (f->type() != Flag::Type::kString) {
      return false;
    }
  } else if (f->type() != Flag::Type::kInt64) {
    return false;
  }
  RuleState s;
  s.rule = r;
  s.flag = f;
  int64_t flo = 0;
  int64_t fhi = 0;
  const bool declared = f->bounds(&flo, &fhi);
  // Effective bounds: rule bounds intersected with the flag's declared
  // bounds; a numeric rule without its own bounds REQUIRES declared
  // ones (no bounds means no safe actuation range).  The qos-weights
  // rule rewrites a CSV string — its validator bounds each weight.
  if (r.mode != Mode::kQosWeights) {
    if (r.min == 0 && r.max == 0) {
      if (!declared) {
        return false;
      }
      s.lo = flo;
      s.hi = fhi;
    } else {
      s.lo = declared ? std::max(r.min, flo) : r.min;
      s.hi = declared ? std::min(r.max, fhi) : r.max;
    }
  }
  if (f->type() == Flag::Type::kInt64) {
    s.dflt = strtoll(f->default_value().c_str(), nullptr, 10);
  }
  e.rules.push_back(std::move(s));
  (void)quiet;
  return true;
}

void install_builtins(Engine& e) {
  if (!e.builtins_installed) {
    e.builtins_installed = true;
    for (const Rule& r : builtin_rules()) {
      // A TYPO'd knob here is a lint failure (tuner-rule), not a silent
      // skip; a knob whose defining plane hasn't initialized yet (the
      // lazily-registered collective flags) parks in unresolved_rules
      // and retries below.
      if (!install_rule(e, r, /*quiet=*/true)) {
        e.unresolved_rules.push_back(r);
      }
    }
    for (const Rule& r : e.extra_rules) {
      if (!install_rule(e, r, /*quiet=*/true)) {
        e.unresolved_rules.push_back(r);
      }
    }
    e.extra_rules.clear();
  }
  if (!e.unresolved_rules.empty()) {
    std::vector<Rule> still;
    for (const Rule& r : e.unresolved_rules) {
      if (!install_rule(e, r, /*quiet=*/true)) {
        still.push_back(r);
      }
    }
    e.unresolved_rules.swap(still);
  }
}

// ---- evaluation ----------------------------------------------------------

double hysteresis_frac() {
  return hysteresis_flag()->int64_value() / 100.0;
}

void freeze_rule(Engine& e, RuleState& s, const char* why, double before,
                 double after) {
  s.freeze = static_cast<int>(freeze_flag()->int64_value()) * s.backoff;
  s.backoff = std::min(s.backoff * 2, 64);
  s.fails = 0;
  e.freezes.fetch_add(1, std::memory_order_relaxed);
  const int64_t cur =
      s.flag->type() == Flag::Type::kInt64 ? s.flag->int64_value() : 0;
  journal_decision(e, s.rule.knob, cur, cur, "", "", "freeze",
                   std::string(why) + " (frozen " +
                       std::to_string(s.freeze) + " windows)",
                   before, after);
}

// Verdict on a pending change.  Returns true when the change survived.
bool evaluate_pending(Engine& e, RuleState& s) {
  double now = 0.0;
  if (!series_value(e, s.pending_metric, &now)) {
    // Signal vanished (lanes off, load gone): keep the change, no
    // verdict possible.
    s.pending = false;
    return true;
  }
  const double before = s.metric_at_change;
  const double hyst = hysteresis_frac();
  const bool maximize = s.pending_maximize;
  const bool worsened = maximize
                            ? now < before * (1.0 - hyst)
                            : now > before * (1.0 + hyst) + 1e-9;
  const bool improved = maximize
                            ? now > before * (1.0 + hyst)
                            : now < before * (1.0 - hyst) - 1e-9;
  s.pending = false;
  if (worsened) {
    // Revert-on-regression: roll the knob back through the validated
    // path, flip the probe direction, and freeze after two consecutive
    // failed probes (both directions worsened).
    const int64_t cur = s.flag->type() == Flag::Type::kInt64
                            ? s.flag->int64_value()
                            : 0;
    if (s.flag->type() == Flag::Type::kString) {
      const std::string cur_str = s.flag->string_value();
      apply_set(e, s, s.prev_str);
      journal_decision(e, s.rule.knob, 0, 0, cur_str, s.prev_str,
                       "revert", "metric worsened past hysteresis",
                       before, now);
    } else {
      apply_set(e, s, std::to_string(s.prev_num));
      journal_decision(e, s.rule.knob, cur, s.prev_num, "", "", "revert",
                       "metric worsened past hysteresis", before, now);
    }
    e.reverts.fetch_add(1, std::memory_order_relaxed);
    s.dir = -s.dir;
    s.cooldown = 1;
    if (++s.fails >= 2) {
      freeze_rule(e, s, "both probe directions regressed", before, now);
    }
    return false;
  }
  if (improved) {
    s.fails = 0;
    s.backoff = 1;
    s.neutral_streak = 0;
    return true;
  }
  // Neutral verdict.  A maximize-guarded probe (hill-climb, or an AIMD
  // growth move with a declared objective) that bought nothing
  // measurable is RETRACTED — keeping it would let a flat metric drift
  // the knob to a bound 5% at a time, below the hysteresis radar — and
  // re-probes back off exponentially so a settled knob stops churning.
  // AIMD relief moves keep instead: their effect can be legitimately
  // deferred (a bigger rma window only helps connections opened after
  // it), and the pressure signal re-triggering is the escalation path.
  if (s.rule.mode == Mode::kHillClimb ||
      (s.rule.mode == Mode::kAimd && s.pending_maximize)) {
    const int64_t cur = s.flag->int64_value();
    apply_set(e, s, std::to_string(s.prev_num));
    journal_decision(e, s.rule.knob, cur, s.prev_num, "", "", "revert",
                     "no measurable improvement: probe retracted",
                     before, now);
    e.reverts.fetch_add(1, std::memory_order_relaxed);
    s.dir = -s.dir;
    s.neutral_streak = std::min(s.neutral_streak + 1, 8);
    s.cooldown = 2 * s.neutral_streak;
    return false;
  }
  s.cooldown = 1;
  return true;
}

// Attempts a new action for rule `s`.  Returns true when a knob changed.
bool act(Engine& e, RuleState& s) {
  if (s.rule.mode == Mode::kQosWeights) {
    double depth = 0.0;
    if (!series_value(e, s.rule.pressure, &depth) ||
        depth <= s.rule.pressure_high) {
      return false;
    }
    const std::string cur = s.flag->string_value();
    // Double the highest-priority lane's weight, capped at the
    // validator's 4096 ceiling.
    const char* p = cur.c_str();
    char* end = nullptr;
    const long w0 = strtol(p, &end, 10);
    if (end == p || w0 >= 4096) {
      return false;
    }
    const long nw0 = std::min<long>(w0 * 2, 4096);
    std::string next = std::to_string(nw0) + std::string(end);
    s.prev_str = cur;
    s.metric_at_change = depth;
    s.pending_metric = s.rule.pressure;
    s.pending_maximize = false;  // a weight boost must DRAIN the lane
    if (!apply_set(e, s, next)) {
      return false;
    }
    s.pending = true;
    journal_decision(e, s.rule.knob, w0, nw0, cur, next, "apply",
                     "priority lane backed up: doubling lane-0 weight",
                     depth, 0.0);
    return true;
  }

  const int64_t cur = s.flag->int64_value();
  if (s.rule.skip_at_value >= 0 && cur == s.rule.skip_at_value) {
    return false;  // deliberately-disabled plane: never re-enable it
  }
  if (s.rule.mode == Mode::kAimd) {
    double pressure = 0.0;
    const bool have_pressure =
        series_value(e, s.rule.pressure, &pressure);
    if (have_pressure && pressure > s.rule.pressure_high) {
      const int64_t next = step_value(s, cur, s.rule.relief_dir);
      if (next == cur) {
        return false;
      }
      s.prev_num = cur;
      s.metric_at_change = pressure;
      s.pending_metric = s.rule.pressure;
      s.pending_maximize = false;  // relief must LOWER the pressure
      if (!apply_set(e, s, std::to_string(next))) {
        return false;
      }
      s.pending = true;
      journal_decision(e, s.rule.knob, cur, next, "", "", "apply",
                       "pressure " + s.rule.pressure + " above " +
                           std::to_string(s.rule.pressure_high),
                       pressure, 0.0);
      return true;
    }
    double grow = 0.0;
    if (!s.rule.grow.empty() && series_value(e, s.rule.grow, &grow) &&
        grow > s.rule.grow_min &&
        (!have_pressure || pressure <= s.rule.pressure_high)) {
      const int64_t next = step_value(s, cur, -s.rule.relief_dir);
      if (next == cur) {
        return false;
      }
      s.prev_num = cur;
      // Guard metric: the declared objective (maximize) when the rule
      // names one, else the growth signal itself (minimize).
      if (!s.rule.objective.empty()) {
        double obj = 0.0;
        if (!series_value(e, s.rule.objective, &obj)) {
          return false;  // objective not flowing: no evidence to act on
        }
        s.metric_at_change = obj;
        s.pending_metric = s.rule.objective;
        s.pending_maximize = true;
      } else {
        s.metric_at_change = grow;
        s.pending_metric = s.rule.grow;
        s.pending_maximize = false;
      }
      if (!apply_set(e, s, std::to_string(next))) {
        return false;
      }
      s.pending = true;
      journal_decision(e, s.rule.knob, cur, next, "", "", "apply",
                       "growth signal " + s.rule.grow + " above " +
                           std::to_string(s.rule.grow_min),
                       grow, 0.0);
      return true;
    }
    return false;
  }

  // Hill-climb.
  double metric = 0.0;
  if (!series_value(e, s.rule.target, &metric) ||
      metric < s.rule.min_activity) {
    return false;  // activity gate: idle traffic never random-walks knobs
  }
  if (s.dir == 0) {
    // First probe heads toward the compiled default (the hand-tuned
    // value) — recovery from a deliberately-wrong seed takes the short
    // way, and the metric verdict still vetoes a wrong guess.
    s.dir = cur < s.dflt ? 1 : (cur > s.dflt ? -1 : 1);
  }
  int64_t next = step_value(s, cur, s.dir);
  if (next == cur) {  // pinned at a bound: turn around
    s.dir = -s.dir;
    next = step_value(s, cur, s.dir);
    if (next == cur) {
      return false;  // lo == hi: nothing to tune
    }
  }
  s.prev_num = cur;
  s.metric_at_change = metric;
  s.pending_metric = s.rule.target;
  s.pending_maximize = true;
  if (!apply_set(e, s, std::to_string(next))) {
    return false;
  }
  s.pending = true;
  journal_decision(e, s.rule.knob, cur, next, "", "", "apply",
                   std::string("hill-climb probe ") +
                       (s.dir > 0 ? "up" : "down") + " on " +
                       s.rule.target,
                   metric, 0.0);
  return true;
}

void tick_locked(Engine& e) {
  install_builtins(e);
  const int64_t now = monotonic_time_us();
  const double dt_s =
      e.last_tick_us > 0 ? (now - e.last_tick_us) / 1e6 : 0.0;
  e.last_tick_us = now;
  e.ticks.fetch_add(1, std::memory_order_relaxed);

  // Sample every var any rule references — each name exactly ONCE per
  // tick (two rules sharing a counter would otherwise zero the second
  // rate computation).  A name claimed as a level anywhere samples as a
  // level.
  std::map<std::string, bool> wanted;  // name -> is_level
  for (const RuleState& s : e.rules) {
    if (!s.rule.target.empty()) {
      wanted[s.rule.target] |= s.rule.target_is_level;
    }
    if (!s.rule.pressure.empty()) {
      wanted[s.rule.pressure] |= s.rule.pressure_is_level;
    }
    if (!s.rule.grow.empty()) {
      wanted[s.rule.grow] |= false;
    }
    if (!s.rule.objective.empty()) {
      wanted[s.rule.objective] |= false;
    }
  }
  for (const auto& [name, is_level] : wanted) {
    sample_var(e, name, is_level, dt_s);
  }

  if (++e.ticks_in_window <
      static_cast<int>(eval_ticks_flag()->int64_value())) {
    return;
  }
  e.ticks_in_window = 0;

  // Evaluation window: verdicts on pending changes first, then at most
  // ONE new knob move process-wide (clean attribution).
  for (RuleState& s : e.rules) {
    if (s.freeze > 0) {
      --s.freeze;
      continue;
    }
    if (s.pending) {
      evaluate_pending(e, s);
    }
  }
  if (e.rules.empty()) {
    return;
  }
  for (size_t i = 0; i < e.rules.size(); ++i) {
    RuleState& s = e.rules[(e.rr + i) % e.rules.size()];
    if (s.freeze > 0 || s.pending) {
      continue;
    }
    if (s.cooldown > 0) {
      --s.cooldown;
      continue;
    }
    if (act(e, s)) {
      e.rr = (e.rr + i + 1) % e.rules.size();
      break;
    }
  }
  long frozen = 0;
  for (const RuleState& s : e.rules) {
    frozen += s.freeze > 0 ? 1 : 0;
  }
  // Relaxed: gauge published for the /vars PassiveStatus (which must
  // not take mu — see Engine::frozen_now).
  e.frozen_now.store(frozen, std::memory_order_relaxed);
}

// ---- control loop --------------------------------------------------------

std::atomic<bool> g_loop_started{false};

// Sliced-sleep control loop (same shape as the stat sampler thread: a
// detached pthread polling an atomic — no condvar, nothing for a
// sanitizer to model).  Sleeps the interval in <=50ms slices, so a
// disable stops ticking within one slice and an interval flip takes
// effect without a stale 1h sleep outliving it.  Ticks come AFTER a
// full interval, never immediately on enable — tests park the loop by
// pinning the interval high and drive tick_once_for_test instead.
void loop_body() {
  int64_t slept_ms = 0;
  for (;;) {
    if (!g_enabled.load(std::memory_order_acquire)) {
      slept_ms = 0;
      usleep(100 * 1000);  // idle poll: one relaxed load per 100ms
      continue;
    }
    const int64_t interval = interval_flag()->int64_value();
    if (slept_ms < interval) {
      const int64_t slice = std::min<int64_t>(50, interval - slept_ms);
      usleep(static_cast<useconds_t>(slice * 1000));
      slept_ms += slice;
      continue;
    }
    slept_ms = 0;
    Engine& e = engine();
    std::lock_guard<std::mutex> g(e.mu);
    if (g_enabled.load(std::memory_order_acquire)) {
      tick_locked(e);
    }
  }
}

void start_loop_if_needed() {
  // Acq_rel exchange: exactly one caller starts the (detached, leaked)
  // controller thread; later enables just let the running loop see
  // g_enabled flip.
  if (!g_loop_started.exchange(true, std::memory_order_acq_rel)) {
    std::thread(loop_body).detach();
  }
}

// Eager registration: /flags can list+flip trpc_tuner before traffic
// (same pattern as the stripe eager definitions).
[[maybe_unused]] const bool g_tuner_eager = [] {
  ensure_registered();
  return true;
}();

}  // namespace

void ensure_registered() {
  tuner_flag();
  // Deliberately leaked (registry outlives statics); volatile keeps the
  // otherwise-unread pointer store alive so LSan sees a root.
  static TunerVars* volatile vars = new TunerVars();
  (void)vars;
}

bool enabled() { return g_enabled.load(std::memory_order_acquire); }

int add_rule(const Rule& r) {
  Flag* f = Flag::find(r.knob);
  if (f == nullptr || !f->reloadable()) {
    return -1;
  }
  Engine& e = engine();
  std::lock_guard<std::mutex> g(e.mu);
  if (!e.builtins_installed) {
    e.extra_rules.push_back(r);
    return 0;
  }
  return install_rule(e, r, /*quiet=*/false) ? 0 : -1;
}

std::string dump_json(size_t limit) {
  ensure_registered();
  Engine& e = engine();
  Json root = Json::object();
  root.set("enabled", Json::boolean(enabled()));
  root.set("interval_ms", Json::number(static_cast<double>(
                              interval_flag()->int64_value())));
  root.set("ticks_total",
           Json::number(static_cast<double>(ticks_total())));
  root.set("decisions_total",
           Json::number(static_cast<double>(decisions_total())));
  root.set("reverts_total",
           Json::number(static_cast<double>(reverts_total())));
  root.set("freezes_total",
           Json::number(static_cast<double>(freezes_total())));
  std::lock_guard<std::mutex> g(e.mu);
  install_builtins(e);  // idempotent: /tuner shows the table pre-tick
  Json rules = Json::array();
  for (const RuleState& s : e.rules) {
    Json j = Json::object();
    j.set("knob", Json::str(s.rule.knob));
    j.set("mode", Json::str(s.rule.mode == Mode::kHillClimb
                                ? "hill_climb"
                                : s.rule.mode == Mode::kAimd
                                      ? "aimd"
                                      : "qos_weights"));
    j.set("value", Json::str(s.flag->value_string()));
    j.set("min", Json::number(static_cast<double>(s.lo)));
    j.set("max", Json::number(static_cast<double>(s.hi)));
    j.set("pending", Json::boolean(s.pending));
    j.set("frozen_windows", Json::number(s.freeze));
    j.set("cooldown", Json::number(s.cooldown));
    j.set("dir", Json::number(s.dir));
    const std::string& sig = s.rule.mode == Mode::kHillClimb
                                 ? s.rule.target
                                 : s.rule.pressure;
    j.set("signal", Json::str(sig));
    auto it = e.series.find(sig);
    if (it != e.series.end() && it->second.have_ema) {
      j.set("metric", Json::number(it->second.ema));
    }
    rules.push_back(std::move(j));
  }
  root.set("rules", std::move(rules));
  // Live input snapshot (the observability surfaces the controller
  // samples — dynamic families skipped when unregistered).
  Json inputs = Json::object();
  for (const char* name : kTunerInputs) {
    std::string base(name);
    if (!base.empty() && base.back() == '_') {
      for (int i = 0; i < 8; ++i) {
        const std::string full = base + std::to_string(i);
        double v = 0.0;
        if (read_var_number(full, &v)) {
          inputs.set(full, Json::number(v));
        }
      }
      continue;
    }
    double v = 0.0;
    if (read_var_number(base, &v)) {
      inputs.set(base, Json::number(v));
    }
  }
  root.set("inputs", std::move(inputs));
  Json decisions = Json::array();
  const size_t n = e.journal.size();
  const size_t start = limit > 0 && n > limit ? n - limit : 0;
  for (size_t i = start; i < n; ++i) {
    const Decision& d = e.journal[i];
    Json j = Json::object();
    j.set("seq", Json::number(static_cast<double>(d.seq)));
    j.set("ts_mono_us",
          Json::number(static_cast<double>(d.ts_mono_us)));
    j.set("ts_wall_us",
          Json::number(static_cast<double>(d.ts_wall_us)));
    j.set("knob", Json::str(d.knob));
    j.set("old", Json::number(static_cast<double>(d.old_num)));
    j.set("new", Json::number(static_cast<double>(d.new_num)));
    if (!d.old_str.empty() || !d.new_str.empty()) {
      j.set("old_str", Json::str(d.old_str));
      j.set("new_str", Json::str(d.new_str));
    }
    j.set("action", Json::str(d.action));
    j.set("reason", Json::str(d.reason));
    j.set("metric_before", Json::number(d.metric_before));
    j.set("metric_after", Json::number(d.metric_after));
    decisions.push_back(std::move(j));
  }
  root.set("decisions", std::move(decisions));
  return root.dump();
}

uint64_t ticks_total() {
  // Relaxed: lifetime counter reads for /vars.
  return engine().ticks.load(std::memory_order_relaxed);
}
uint64_t decisions_total() {
  return engine().decisions.load(std::memory_order_relaxed);
}
uint64_t reverts_total() {
  return engine().reverts.load(std::memory_order_relaxed);
}
uint64_t freezes_total() {
  return engine().freezes.load(std::memory_order_relaxed);
}

int tick_once_for_test() {
  if (!enabled()) {
    return -1;
  }
  Engine& e = engine();
  std::lock_guard<std::mutex> g(e.mu);
  tick_locked(e);
  return 0;
}

void reset_for_test() {
  Engine& e = engine();
  std::lock_guard<std::mutex> g(e.mu);
  e.builtins_installed = false;
  e.rules.clear();
  e.extra_rules.clear();
  e.unresolved_rules.clear();
  e.rr = 0;
  e.last_tick_us = 0;
  e.ticks_in_window = 0;
  e.series.clear();
  e.journal.clear();
  e.seq = 0;
  e.ticks.store(0, std::memory_order_relaxed);
  e.decisions.store(0, std::memory_order_relaxed);
  e.reverts.store(0, std::memory_order_relaxed);
  e.freezes.store(0, std::memory_order_relaxed);
  e.rejected.store(0, std::memory_order_relaxed);
  e.frozen_now.store(0, std::memory_order_relaxed);
}

}  // namespace tuner
}  // namespace trpc
