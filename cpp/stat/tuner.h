// Self-tuning runtime (ROADMAP item 4): an in-process feedback
// controller that closes the observability loop back into the knobs.
//
// The runtime carries ~30 validated reloadable flags (stripe rails and
// chunk size, QoS lane weights, messenger cut budget, rma window,
// collective chunk/inflight ...) and the vars to see where time goes —
// but every number was hand-tuned per box, which no production fleet
// does.  This tier closes the loop:
// a control loop on its own background thread (never a dispatch fiber —
// tuning must not compete with the traffic it tunes, and it must run in
// fiber-less client processes too) samples the existing var surfaces on
// a `trpc_tuner_interval_ms` tick and drives per-knob feedback rules
// through the *validated* flag-reload path only:
//
//   - hill-climb rules (stripe chunk/rails, collective chunk/inflight)
//     probe a knob in its current direction and keep the move only when
//     the target metric (a counter rate, e.g. stripe_rx_bytes/s)
//     improves past a hysteresis band;
//   - AIMD rules (messenger cut budget, rma window) mirror the existing
//     concurrency limiter: a pressure signal (priority-lane depth,
//     window-full fallbacks) triggers a multiplicative corrective move,
//     a growth signal (cut-budget yields) an opposing step;
//   - the QoS-weights rule rewrites the lane-weight CSV (highest lane
//     doubled) while the priority lane stays backed up.
//
// Guardrails, all mandatory: per-knob hard bounds intersected with the
// flag's DECLARED bounds (base/flags.h set_int_range — clamping happens
// before the set, so out-of-range actuation is impossible by
// construction); a revert-on-regression guard (a change that worsens
// its own metric within one evaluation window is rolled back and the
// knob frozen for an exponentially-backed-off period); an activity gate
// (a rule whose target isn't flowing does nothing, so an idle or
// correctly-tuned box is never perturbed); and at most ONE knob change
// per evaluation window process-wide, so attribution stays clean.
//
// Every decision lands in a structured journal served by /tuner (and
// trpc_tuner_dump).
//
// Flag-off contract (same as trpc_analysis): default
// off; while off, no thread runs, nothing is sampled, every tuner var
// is provably frozen at 0, and no flag is ever touched.
#pragma once

#include <cstddef>
#include <cstdint>
#include <string>

namespace trpc {
namespace tuner {

enum class Mode : int {
  kHillClimb = 0,  // probe knob, keep moves that improve `target`
  kAimd = 1,       // pressure -> multiplicative relief; growth -> step back
  kQosWeights = 2, // CSV lane weights: double lane 0 under backlog
};

// One feedback rule.  `knob` must name a defined, *reloadable* trpc_*
// flag (add_rule rejects anything else); numeric actuation clamps into
// [min, max] intersected with the flag's declared bounds.
struct Rule {
  std::string knob;
  Mode mode = Mode::kHillClimb;

  // kHillClimb: maximize `target` — a counter whose per-second rate is
  // the metric, or the raw level when target_is_level (synthetic test
  // metrics).  The rule acts only while the metric >= min_activity.
  std::string target;
  bool target_is_level = false;
  double min_activity = 0.0;

  // kAimd / kQosWeights: `pressure` (level by default, rate when
  // pressure_is_level = false) above pressure_high triggers a
  // multiplicative move in relief_dir; `grow` (counter rate) above
  // grow_min while pressure is quiet steps the opposite way.
  std::string pressure;
  bool pressure_is_level = true;
  double pressure_high = 0.0;
  std::string grow;
  double grow_min = 0.0;
  int relief_dir = -1;
  // Optional guard for AIMD growth moves: when set, a growth move is
  // judged on THIS counter's rate (maximize) instead of on the grow
  // signal itself, and a move that buys nothing measurable is
  // retracted like a hill-climb probe.  Without it a growth move would
  // always "improve" its own trigger (doubling the cut budget always
  // lowers yields) while silently regressing the throughput the knob
  // exists to serve.
  std::string objective;

  // Step geometry and hard bounds (0/0 = flag-declared bounds only).
  double step_mul = 2.0;   // multiplicative step (> 1)
  int64_t step_add = 0;    // when > 0: additive step instead
  int64_t min = 0;
  int64_t max = 0;
  // Sentinel value meaning "this subsystem is deliberately disabled":
  // while the knob reads exactly this, the rule never actuates (the
  // tuner must not re-enable a plane behind the operator's back).
  // -1 = no sentinel.  The rma window rule sets 0.
  int64_t skip_at_value = -1;
};

// Registers the trpc_tuner* flags and tuner vars (idempotent).
void ensure_registered();
bool enabled();

// Installs an additional rule (tests, embedders).  Returns 0, or -1
// when the knob is not a defined reloadable flag.  Built-in rules are
// installed automatically on the first tick.
int add_rule(const Rule& r);

// The /tuner body: {"enabled", counters, "rules": [...], "inputs":
// {...}, "decisions": [newest `limit` entries, oldest first]}.  Served
// even while the flag is off (the journal may hold decisions from an
// earlier enabled window).
std::string dump_json(size_t limit);

// Lifetime counters (the tuner_* vars; provably frozen at 0 while
// trpc_tuner has never been on).
uint64_t ticks_total();
uint64_t decisions_total();
uint64_t reverts_total();
uint64_t freezes_total();

// -- test support ---------------------------------------------------------
// Runs one engine tick synchronously (same lock as the control loop).
// Returns 0, or -1 when the tuner is disabled.  Tests pin
// trpc_tuner_interval_ms high so the background loop stays parked and
// ticks are fully deterministic.
int tick_once_for_test();
// Drops dynamically-added rules, per-rule state, series history and the
// journal; lifetime counters reset too.  Call with the flag OFF.
void reset_for_test();

}  // namespace tuner
}  // namespace trpc
