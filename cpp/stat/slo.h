// Per-tenant SLO engine (ISSUE 19) — windowed attainment tracking with
// multi-window error-budget burn rates, fed from the server dispatch path
// and surfaced as slo_* vars, the /slo builtin, and the fleet
// publication blob the Announcer pushes over naming:// (see
// stat/digest.h digest-wire 2).
//
// Model (SRE multi-window multi-burn-rate alerting): a response is BAD
// when it errors or exceeds the tenant's p99 latency target; the error
// budget is 1 - avail_target.  Each tenant keeps two bucketed rings —
// a fast window (~5m scale) and a slow window (~1h scale), both
// test-compressible via flags — and
//   burn = (bad / total) / (1 - avail_target)
// per window.  A breach requires BOTH burns >= trpc_slo_burn_alert:
// the slow window proves sustained damage, the fast window proves it is
// still happening — and lets the alert clear within one fast window of
// recovery.  Breach edges (and only edges) bump slo_breach_total.
//
// Gating: everything is behind the default-off reloadable `trpc_slo`
// flag.  Flag off, the dispatch hook is ONE relaxed atomic load — no
// state is touched, so every slo_* var is provably frozen at 0 (the
// flag-off perf floor gates this).
#pragma once

#include <atomic>
#include <cstdint>
#include <memory>
#include <string>
#include <vector>

#include "stat/digest.h"

namespace trpc {
namespace slo {

// Backing switch for the reloadable trpc_slo flag (the flag's on_update
// hook writes it; the dispatch hook gates inline on one relaxed load).
extern std::atomic<bool> g_enabled;

inline bool enabled() {
  return g_enabled.load(std::memory_order_relaxed);
}

// Registers trpc_slo / trpc_slo_fast_window_ms / trpc_slo_slow_window_ms /
// trpc_slo_burn_alert + the global slo_* vars (idempotent).
void ensure_registered();

// Current knob values (read at SetSlo install time for the windows, live
// for the alert threshold).
int64_t fast_window_ms();
int64_t slow_window_ms();
double burn_alert();

// Lifetime count of breach EDGES (fires), across all engines.
uint64_t breach_total();

}  // namespace slo

// One tenant's decoded state from a fleet publication blob (digest-wire 2).
struct FleetTenantRecord {
  std::string tenant;
  int64_t p99_target_us = 0;  // INT64_MAX = latency-unbounded clause
  double avail_target = 0;
  int64_t fast_window_ms = 0, slow_window_ms = 0;
  int64_t fast_total = 0, fast_bad = 0, fast_err = 0;
  int64_t slow_total = 0, slow_bad = 0, slow_err = 0;
  double burn_fast = 0, burn_slow = 0;
  bool breached = false;
  LatencyDigest digest;
};

struct FleetNodeBlob {
  int64_t wall_us = 0;
  std::vector<FleetTenantRecord> tenants;
};

// Decodes one TRPCFL01 blob (the inverse of SloEngine::encode_blob).
// False on malformed input.
bool fleet_blob_decode(const void* data, size_t len, FleetNodeBlob* out);

class SloEngine {
 public:
  ~SloEngine();

  // Parses "tenantA:p99_us=2000,avail=99.9;*:p99_us=10000" — per-clause
  // keys: p99_us (target latency, us, >0) and avail (availability target
  // in percent, (0,100); default 99.0 when only p99_us is given).  "*" is
  // the default clause matching tenants with no clause of their own.
  // Returns nullptr (+ *err) on malformed specs.  Window widths are
  // captured from the trpc_slo_*_window_ms flags at parse time, so tests
  // compress them before Server::SetSlo.
  static std::shared_ptr<SloEngine> parse(const std::string& spec,
                                          std::string* err);

  // Dispatch feed (server.cc response closure).  Callers gate on
  // slo::enabled() — this re-checks, but the call itself must cost
  // nothing when the flag is off.
  void on_response(const std::string& tenant, int64_t latency_us,
                   bool error);

  // /slo builtin + trpc_slo_dump: {"enabled","burn_alert","tenants":[
  // {"tenant","p99_target_us","avail_target","fast":{...},"slow":{...},
  // "burn_fast","burn_slow","attainment","budget_remaining","breached",
  // "latency":{...}}]}.
  std::string dump_json() const;

  // Fleet publication blob (digest-wire 2, magic TRPCFL01): per-tenant
  // SLO state + a digest snapshot of the tenant's recorder.  Published by
  // the Announcer each renew round when trpc_fleet_publish is on.
  std::string encode_blob(int64_t wall_us) const;

  bool any_breached() const;
  // Per-tenant burn state for admission planes (net/infer.h): true while
  // the tenant's clause (or the "*" default) is burning past the alert
  // threshold on both windows.  Tenants with no clause never read as
  // breached.
  bool tenant_breached(const std::string& tenant) const;
  size_t tenant_count() const;

  struct Entry;  // opaque per-tenant state

 private:
  SloEngine() = default;
  std::vector<std::unique_ptr<Entry>> entries_;
  Entry* default_entry_ = nullptr;  // the "*" clause, if present

  Entry* find(const std::string& tenant) const;
};

}  // namespace trpc
