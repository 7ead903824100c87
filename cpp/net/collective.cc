#include "net/collective.h"

#include <string.h>

#include <algorithm>
#include <mutex>
#include <unordered_map>

#include "base/flags.h"
#include "base/logging.h"
#include "base/time.h"
#include "fiber/event.h"
#include "net/channel.h"
#include "net/controller.h"
#include "net/kvstore.h"
#include "net/naming.h"
#include "net/rma.h"
#include "net/server.h"
#include "stat/latency_recorder.h"
#include "stat/reducer.h"

namespace trpc {

namespace {

// ---- flags ---------------------------------------------------------------

Flag* int_flag(const char* name, int64_t dflt, const char* desc, int64_t lo,
               int64_t hi) {
  Flag* f = Flag::define_int64(name, dflt, desc);
  if (f != nullptr) {
    // Range validator + introspectable bounds in one declaration (the
    // tuner and /flags?format=json read them back).
    f->set_int_range(lo, hi);
  }
  return f;
}

Flag* chunk_flag() {
  static Flag* f = int_flag(
      "trpc_coll_chunk_bytes", 8 << 20,
      "chunk size collective transfers are cut into (bytes, [64KB, "
      "256MB]); each chunk is one Coll.Put riding the one-sided RMA "
      "plane, so smaller chunks pipeline deeper (T3 overlap) at more "
      "per-put cost",
      64 << 10, 256ll << 20);
  return f;
}

Flag* inflight_flag() {
  static Flag* f = int_flag(
      "trpc_coll_inflight", 4,
      "concurrent in-flight Coll.Put chunks per member per schedule "
      "step ([1, 64]); depth >1 overlaps chunk k+1's put with chunk "
      "k's verification",
      1, 64);
  return f;
}

Flag* rendezvous_flag() {
  static Flag* f = int_flag(
      "trpc_coll_rendezvous_ms", 15000,
      "how long a Coll.Put handler parks waiting for the local member "
      "to register its receive session (ms, [50, 600000]) — members "
      "enter a collective at slightly different times; past this the "
      "put fails and the sender aborts the step",
      50, 600000);
  return f;
}

Flag* ready_granularity_flag() {
  static Flag* f = int_flag(
      "trpc_coll_ready_granularity_bytes", 1 << 20,
      "chunk granularity of collective readiness maps (bytes, [4KB, "
      "256MB]) — producers stamp send-buffer ranges at this grain and "
      "readiness-triggered transfers fire per stamped chunk; finer "
      "grains overlap earlier at more stamp/scan cost",
      4 << 10, 256ll << 20);
  return f;
}

Flag* overlap_flag() {
  static Flag* f = [] {
    Flag* fl = Flag::define_bool(
        "trpc_coll_overlap", false,
        "fire collective transfers as their input chunks are stamped "
        "ready (T3-style compute/comm overlap) instead of waiting for "
        "the whole send buffer; off = barrier semantics, byte-identical "
        "with or without a readiness map attached");
    if (fl != nullptr) {
      fl->set_validator([](const std::string& v) {
        return v == "true" || v == "false" || v == "1" || v == "0" ||
               v == "on" || v == "off";
      });
    }
    return fl;
  }();
  return f;
}

int64_t flag_val(Flag* f, int64_t dflt) {
  return f != nullptr ? f->int64_value() : dflt;
}

bool overlap_enabled() {
  Flag* f = overlap_flag();
  return f != nullptr && f->bool_value();
}

// ---- vars ----------------------------------------------------------------

struct CollVars {
  Adder runs_total;
  Adder steps_total;
  Adder puts_total;
  Adder put_bytes;
  Adder aborts_total;
  Adder epoch_fails_total;
  Adder reshard_plans_total;
  Adder reshard_execs_total;
  Adder ready_triggers_total;
  Adder overlap_runs_total;
  std::unique_ptr<PassiveStatus<long>> sessions;
  // Per-op step latency, Prometheus-exposed with HELP so dashboards can
  // tell a slow reshard from a slow all-gather.
  LatencyRecorder step_all_gather;
  LatencyRecorder step_reduce_scatter;
  LatencyRecorder step_all_to_all;
  LatencyRecorder step_reshard;
  CollVars() {
    runs_total.expose("coll_runs_total",
                      "collective schedules executed by this member "
                      "(all_gather / reduce_scatter / all_to_all / "
                      "reshard runs, success or failure)");
    steps_total.expose("coll_steps_total",
                       "schedule steps this member completed (sends "
                       "acked AND expected receives landed)");
    puts_total.expose("coll_puts_total",
                      "Coll.Put chunk RPCs issued by this member");
    put_bytes.expose("coll_put_bytes",
                     "payload bytes this member moved over the fabric "
                     "via Coll.Put chunks");
    aborts_total.expose("coll_aborts_total",
                        "collective runs that failed whole-or-nothing "
                        "(local step failure or a peer's Coll.Abort)");
    epoch_fails_total.expose(
        "coll_epoch_fails_total",
        "schedule steps failed because the group's naming view changed "
        "mid-run (membership epoch moved under the schedule)");
    reshard_plans_total.expose(
        "coll_reshard_plans_total",
        "Reshard.Plan requests answered by this node");
    reshard_execs_total.expose(
        "coll_reshard_execs_total",
        "Reshard.Execute runs this node participated in");
    ready_triggers_total.expose(
        "coll_ready_triggers_total",
        "collective transfers fired by a readiness stamp before the "
        "whole-buffer barrier would have released them (frozen at 0 "
        "with trpc_coll_overlap off)");
    overlap_runs_total.expose(
        "coll_overlap_runs_total",
        "collective runs executed with readiness-triggered overlap "
        "(a ready map attached AND trpc_coll_overlap on; frozen at 0 "
        "otherwise)");
    sessions = std::make_unique<PassiveStatus<long>>(
        [] { return static_cast<long>(coll_sessions_live()); });
    sessions->expose("coll_sessions",
                     "collective receive sessions currently registered "
                     "(0 when no run is in flight)");
    step_all_gather.expose(
        "coll_step_all_gather",
        "wall time of one completed all_gather schedule step (sends "
        "acked + receives landed)");
    step_reduce_scatter.expose(
        "coll_step_reduce_scatter",
        "wall time of one completed reduce_scatter schedule step");
    step_all_to_all.expose(
        "coll_step_all_to_all",
        "wall time of one completed all_to_all schedule step");
    step_reshard.expose(
        "coll_step_reshard",
        "wall time of one completed reshard schedule step");
  }
  LatencyRecorder& step_lat(CollOp op) {
    switch (op) {
      case CollOp::kAllGather:
        return step_all_gather;
      case CollOp::kReduceScatter:
        return step_reduce_scatter;
      case CollOp::kAllToAll:
        return step_all_to_all;
      default:
        return step_reshard;
    }
  }
};

CollVars& coll_vars() {
  static CollVars* v = new CollVars();
  return *v;
}

uint64_t fnv1a(const void* data, size_t n, uint64_t h = 14695981039346656037ull) {
  const auto* p = static_cast<const unsigned char*>(data);
  for (size_t i = 0; i < n; ++i) {
    h = (h ^ p[i]) * 1099511628211ull;
  }
  return h;
}

void noop_deleter(void*, void*) {}

// ---- receive sessions ----------------------------------------------------

// One member's receive state for one (group, run).  Registered by the
// executor BEFORE it issues any put; Coll.Put handlers park (bounded by
// trpc_coll_rendezvous_ms) for it to appear, place chunks, and wake the
// executor's per-step countdown.  `busy` guards the destination buffer:
// unregistration drains in-flight handler copies before run() returns
// the buffer to the caller.
struct RecvSession {
  uint64_t group_id = 0;
  uint64_t run_seq = 0;
  uint32_t dst_rank = 0;
  char* dst = nullptr;  // recv buffer (accumulator for reduce ops)
  uint64_t dst_len = 0;
  // Serve source (Coll.Get pulls read the member's buffers directly):
  // the send buffer, and `dst` again for ring-forwarded bytes.
  const char* send_base = nullptr;
  uint64_t send_len = 0;
  // Readiness map over send_base (0 = none): Coll.Get serves of
  // NON-forwarded bytes additionally gate on the producer's stamp, so a
  // pull fires the moment its input chunks land (overlap mode).
  uint64_t ready_handle = 0;
  Event changed;  // bumped on every arrival / serve / abort / put-ack
  std::mutex mu;  // guards the fields below
  std::vector<uint64_t> expected_bytes;  // per step (my receives)
  std::vector<uint64_t> arrived_bytes;   // per step
  // Pull serves this member must complete per step: a member's step is
  // done only when its peers' pulls were served too — unregistering
  // earlier would fail a slow peer's get against a dead session.
  std::vector<uint64_t> expected_serve;  // per step (my pulled sends)
  std::vector<uint64_t> served_bytes;    // per step
  int abort_code = 0;
  std::string abort_why;
  uint32_t busy = 0;  // handlers copying into dst / serving out of it
};

struct SessionReg {
  std::mutex mu;
  std::unordered_map<uint64_t, std::shared_ptr<RecvSession>> map;
  // Aborts that arrived before the local member registered (a fast peer
  // failed step 0 while we were still compiling): poison the key so the
  // late registration fails fast instead of timing out.  Bounded FIFO.
  std::unordered_map<uint64_t, int> poisoned;
  std::vector<uint64_t> poison_order;
  Event registered;  // bumped on every registration
};

SessionReg& sessions() {
  static SessionReg* s = new SessionReg();
  return *s;
}

uint64_t session_key(uint64_t group_id, uint64_t run_seq,
                     uint32_t dst_rank) {
  return (group_id * 1099511628211ull ^ run_seq) * 1099511628211ull ^
         dst_rank;
}

void wake_session(RecvSession* s) {
  // Release pairs with the waiter's acquire load of `value`; the state
  // mutated under s->mu is published by the mutex itself.
  s->changed.value.fetch_add(1, std::memory_order_release);
  s->changed.wake_all();
}

std::shared_ptr<RecvSession> find_session(uint64_t key) {
  SessionReg& r = sessions();
  std::lock_guard<std::mutex> g(r.mu);
  auto it = r.map.find(key);
  return it != r.map.end() ? it->second : nullptr;
}

// Handler-side lookup: parks (bounded) until the session exists.
std::shared_ptr<RecvSession> wait_session(uint64_t key) {
  const int64_t deadline =
      monotonic_time_us() + flag_val(rendezvous_flag(), 15000) * 1000;
  SessionReg& r = sessions();
  while (true) {
    uint32_t v;
    {
      std::lock_guard<std::mutex> g(r.mu);
      auto it = r.map.find(key);
      if (it != r.map.end()) {
        return it->second;
      }
      // Acquire pairs with the registrar's release bump: the map insert
      // happens-before a woken waiter's re-check.
      v = r.registered.value.load(std::memory_order_acquire);
    }
    if (monotonic_time_us() >= deadline) {
      return nullptr;
    }
    r.registered.wait(v, deadline);
  }
}

std::shared_ptr<RecvSession> register_session(
    uint64_t group_id, uint64_t run_seq, uint32_t dst_rank, char* dst,
    uint64_t dst_len, const char* send_base, uint64_t send_len,
    std::vector<uint64_t> expected, std::vector<uint64_t> expected_serve,
    int* poison_code, uint64_t ready_handle = 0) {
  auto s = std::make_shared<RecvSession>();
  s->group_id = group_id;
  s->run_seq = run_seq;
  s->dst_rank = dst_rank;
  s->dst = dst;
  s->dst_len = dst_len;
  s->send_base = send_base;
  s->send_len = send_len;
  s->ready_handle = ready_handle;
  s->expected_bytes = std::move(expected);
  s->arrived_bytes.assign(s->expected_bytes.size(), 0);
  s->expected_serve = std::move(expected_serve);
  s->served_bytes.assign(s->expected_serve.size(), 0);
  const uint64_t key = session_key(group_id, run_seq, dst_rank);
  SessionReg& r = sessions();
  std::lock_guard<std::mutex> g(r.mu);
  auto pit = r.poisoned.find(key);
  if (pit != r.poisoned.end()) {
    *poison_code = pit->second;
    r.poisoned.erase(pit);
    for (auto it = r.poison_order.begin(); it != r.poison_order.end(); ++it) {
      if (*it == key) {
        r.poison_order.erase(it);
        break;
      }
    }
    return nullptr;
  }
  if (r.map.find(key) != r.map.end()) {
    // A LIVE session already holds this (group, run, rank): the caller
    // reused a run_seq that has not torn down — overwriting would land
    // run A's in-flight puts in run B's buffers.  Refuse whole.
    *poison_code = kECollMismatch;
    return nullptr;
  }
  r.map[key] = s;
  // Release pairs with wait_session's acquire re-check.
  r.registered.value.fetch_add(1, std::memory_order_release);
  r.registered.wake_all();
  return s;
}

void unregister_session(const std::shared_ptr<RecvSession>& s) {
  {
    SessionReg& r = sessions();
    std::lock_guard<std::mutex> g(r.mu);
    r.map.erase(session_key(s->group_id, s->run_seq, s->dst_rank));
  }
  // Drain in-flight handler copies: the caller reclaims the destination
  // buffer the moment run() returns, so no handler may still be writing.
  while (true) {
    uint32_t v;
    {
      std::lock_guard<std::mutex> g(s->mu);
      if (s->busy == 0) {
        return;
      }
      // Acquire pairs with wake_session's release bump (busy drop).
      v = s->changed.value.load(std::memory_order_acquire);
    }
    s->changed.wait(v, monotonic_time_us() + 100 * 1000);
  }
}

void poison_run(uint64_t key, int code) {
  constexpr size_t kPoisonCap = 128;
  SessionReg& r = sessions();
  std::lock_guard<std::mutex> g(r.mu);
  if (r.map.find(key) != r.map.end()) {
    return;  // session live: the abort path marked it directly
  }
  if (r.poisoned.emplace(key, code).second) {
    r.poison_order.push_back(key);
    if (r.poison_order.size() > kPoisonCap) {
      r.poisoned.erase(r.poison_order.front());
      r.poison_order.erase(r.poison_order.begin());
    }
  }
}

// ---- wire helpers --------------------------------------------------------

bool parse_put_wire(const IOBuf& req, CollPutWire* w) {
  if (req.size() < sizeof(CollPutWire)) {
    return false;
  }
  req.copy_to(w, sizeof(CollPutWire));
  return true;
}

}  // namespace

const char* coll_op_name(CollOp op) {
  switch (op) {
    case CollOp::kAllGather:
      return "all_gather";
    case CollOp::kReduceScatter:
      return "reduce_scatter";
    case CollOp::kAllToAll:
      return "all_to_all";
    case CollOp::kReshard:
      return "reshard";
  }
  return "?";
}

void coll_ensure_registered() {
  chunk_flag();
  inflight_flag();
  rendezvous_flag();
  ready_granularity_flag();
  overlap_flag();
  coll_vars();
}

uint64_t coll_ready_default_granularity() {
  return static_cast<uint64_t>(
      flag_val(ready_granularity_flag(), 1 << 20));
}

size_t coll_sessions_live() {
  SessionReg& r = sessions();
  std::lock_guard<std::mutex> g(r.mu);
  return r.map.size();
}

// ---- plans ---------------------------------------------------------------

uint64_t TransferSchedule::bytes_moved() const {
  uint64_t n = 0;
  for (const CollStep& s : steps) {
    for (const CollTransfer& t : s.puts) {
      n += t.len;
    }
  }
  return n;
}

uint64_t TransferSchedule::bytes_reused() const {
  uint64_t n = 0;
  for (const CollTransfer& t : local_copies) {
    n += t.len;
  }
  return n;
}

CollDep transfer_input_dep(const CollTransfer& t) {
  if (t.src_from_recv) {
    // Ring-forwarded bytes: produced by a PRIOR step's arrivals, which
    // the step barrier already orders — no send-buffer dependency.
    return CollDep{};
  }
  return CollDep{t.src_off, t.len};
}

uint64_t plan_producer_extent(const TransferSchedule& plan, uint32_t rank) {
  uint64_t extent = 0;
  auto fold = [&](const CollTransfer& t) {
    if (t.src != rank) {
      return;
    }
    const CollDep d = transfer_input_dep(t);
    if (d.len != 0) {
      extent = std::max(extent, d.off + d.len);
    }
  };
  for (const CollTransfer& t : plan.local_copies) {
    fold(t);
  }
  for (const CollStep& s : plan.steps) {
    for (const CollTransfer& t : s.puts) {
      fold(t);
    }
  }
  return extent;
}

TransferSchedule plan_all_gather(uint32_t n, uint64_t shard) {
  TransferSchedule p;
  p.op = CollOp::kAllGather;
  p.nmembers = n;
  p.shard_bytes = shard;
  for (uint32_t r = 0; r < n; ++r) {
    p.local_copies.push_back({r, r, 0, static_cast<uint64_t>(r) * shard,
                              shard, false, false});
  }
  for (uint32_t s = 0; n > 1 && s < n - 1; ++s) {
    CollStep step;
    for (uint32_t r = 0; r < n; ++r) {
      // Ring: at step s rank r forwards chunk (r - s) mod n to its right
      // neighbor; step 0 reads the member's own shard (sendbuf), later
      // steps forward what landed in recvbuf the step before.
      const uint32_t c = (r + n - s) % n;
      CollTransfer t;
      t.src = r;
      t.dst = (r + 1) % n;
      t.src_off = s == 0 ? 0 : static_cast<uint64_t>(c) * shard;
      t.src_from_recv = s != 0;
      t.dst_off = static_cast<uint64_t>(c) * shard;
      t.len = shard;
      step.puts.push_back(t);
    }
    p.steps.push_back(std::move(step));
  }
  return p;
}

TransferSchedule plan_reduce_scatter(uint32_t n, uint64_t shard) {
  TransferSchedule p;
  p.op = CollOp::kReduceScatter;
  p.nmembers = n;
  p.shard_bytes = shard;
  for (uint32_t s = 0; n > 1 && s < n - 1; ++s) {
    CollStep step;
    for (uint32_t r = 0; r < n; ++r) {
      // Ring reduce: at step s rank r ships its accumulated chunk
      // (r - 1 - s) mod n rightward; the receiver u32-adds it into ITS
      // accumulator (= sendbuf) copy of the same chunk.  After n-1
      // steps rank r's chunk r is fully reduced.
      const uint32_t c = (r + 2 * n - 1 - s) % n;
      CollTransfer t;
      t.src = r;
      t.dst = (r + 1) % n;
      t.src_off = static_cast<uint64_t>(c) * shard;
      t.dst_off = static_cast<uint64_t>(c) * shard;
      t.len = shard;
      t.reduce = true;
      step.puts.push_back(t);
    }
    p.steps.push_back(std::move(step));
  }
  for (uint32_t r = 0; r < n; ++r) {
    // Final local copy: the fully-reduced chunk r out of the
    // accumulator into recvbuf.
    p.final_copies.push_back({r, r, static_cast<uint64_t>(r) * shard, 0,
                              shard, false, false});
  }
  return p;
}

TransferSchedule plan_all_to_all(uint32_t n, uint64_t shard) {
  TransferSchedule p;
  p.op = CollOp::kAllToAll;
  p.nmembers = n;
  p.shard_bytes = shard;
  for (uint32_t r = 0; r < n; ++r) {
    p.local_copies.push_back({r, r, static_cast<uint64_t>(r) * shard,
                              static_cast<uint64_t>(r) * shard, shard,
                              false, false});
  }
  for (uint32_t s = 1; s < n; ++s) {
    // Pairwise rounds: at round s rank r exchanges with (r + s) mod n —
    // bounded fan-in per step, every pair exactly once.
    CollStep step;
    for (uint32_t r = 0; r < n; ++r) {
      const uint32_t d = (r + s) % n;
      CollTransfer t;
      t.src = r;
      t.dst = d;
      t.src_off = static_cast<uint64_t>(d) * shard;
      t.dst_off = static_cast<uint64_t>(r) * shard;
      t.len = shard;
      step.puts.push_back(t);
    }
    p.steps.push_back(std::move(step));
  }
  return p;
}

bool sharding_valid(const Sharding& s, uint32_t nmembers) {
  if (s.total == 0 || s.ranges.empty()) {
    return false;
  }
  uint64_t at = 0;
  for (const ShardRange& r : s.ranges) {
    if (r.rank >= nmembers || r.len == 0 || r.off != at) {
      return false;  // must tile [0, total) in order, no gaps/overlaps
    }
    at += r.len;
  }
  return at == s.total;
}

uint64_t sharding_local_bytes(const Sharding& s, uint32_t rank) {
  uint64_t n = 0;
  for (const ShardRange& r : s.ranges) {
    if (r.rank == rank) {
      n += r.len;
    }
  }
  return n;
}

namespace {

// Local-buffer offset of global byte `goff` under sharding `s` for the
// rank owning it (a rank's local buffer is its ranges concatenated in
// ascending global order).  Caller guarantees goff lies in a range owned
// by `rank`.
uint64_t local_off(const Sharding& s, uint32_t rank, uint64_t goff) {
  uint64_t acc = 0;
  for (const ShardRange& r : s.ranges) {
    if (r.rank != rank) {
      continue;
    }
    if (goff >= r.off && goff < r.off + r.len) {
      return acc + (goff - r.off);
    }
    acc += r.len;
  }
  return acc;  // unreachable under a valid plan
}

}  // namespace

TransferSchedule plan_reshard(const Sharding& src, const Sharding& dst,
                              uint32_t n) {
  TransferSchedule p;
  p.op = CollOp::kReshard;
  p.nmembers = n;
  // Bucket cross-owner moves into (dst - src) mod n rounds so per-step
  // fan-in is bounded; same-owner bytes are REUSED in place — the
  // 2112.01075 decomposition's whole point.
  std::vector<CollStep> rounds(n > 1 ? n - 1 : 0);
  for (const ShardRange& d : dst.ranges) {
    for (const ShardRange& srange : src.ranges) {
      const uint64_t lo = std::max(d.off, srange.off);
      const uint64_t hi = std::min(d.off + d.len, srange.off + srange.len);
      if (lo >= hi) {
        continue;
      }
      CollTransfer t;
      t.src = srange.rank;
      t.dst = d.rank;
      t.src_off = local_off(src, srange.rank, lo);
      t.dst_off = local_off(dst, d.rank, lo);
      t.len = hi - lo;
      if (srange.rank == d.rank) {
        p.local_copies.push_back(t);
      } else {
        rounds[(d.rank + n - srange.rank) % n - 1].puts.push_back(t);
      }
    }
  }
  for (CollStep& r : rounds) {
    if (!r.puts.empty()) {
      p.steps.push_back(std::move(r));
    }
  }
  return p;
}

uint64_t reshard_naive_bytes(const Sharding& src, uint32_t n) {
  uint64_t total = 0;
  for (uint32_t r = 0; r < n; ++r) {
    total += sharding_local_bytes(src, r) * (n > 0 ? n - 1 : 0);
  }
  return total;
}

// ---- handlers ------------------------------------------------------------

namespace {

void handle_put(Controller* cntl, const IOBuf& req, IOBuf* resp,
                Closure done) {
  CollPutWire w;
  if (!parse_put_wire(req, &w) || req.size() != sizeof(w) + w.len) {
    cntl->SetFailed(EINVAL, "bad Coll.Put request");
    done();
    return;
  }
  const uint64_t key = session_key(w.group_id, w.run_seq, w.dst_rank);
  std::shared_ptr<RecvSession> s = wait_session(key);
  if (s == nullptr) {
    cntl->SetFailed(kECollAbort,
                    "coll-abort: no receive session (member never "
                    "entered, or the run already tore down)");
    done();
    return;
  }
  {
    std::lock_guard<std::mutex> g(s->mu);
    if (s->abort_code != 0) {
      cntl->SetFailed(s->abort_code, "coll-abort: " + s->abort_why);
      done();
      return;
    }
    // Overflow-safe bounds (the frame is network input): subtract,
    // never add — dst_off + len could wrap past a 2^64 check.
    // The reduce fold is word-wise: an unaligned chunk would silently
    // drop its tail bytes while crediting the full length — reject it
    // like any other out-of-plan put (mirrors the sender-side check).
    const bool bad_reduce =
        (w.flags & kCollFlagReduce) != 0 &&
        (w.len % 4 != 0 || w.dst_off % 4 != 0);
    if (bad_reduce || w.step >= s->expected_bytes.size() ||
        w.dst_off > s->dst_len || w.len > s->dst_len - w.dst_off ||
        w.len > s->expected_bytes[w.step] ||
        s->arrived_bytes[w.step] >
            s->expected_bytes[w.step] - w.len) {
      LOG(Warning) << "coll put mismatch: step=" << w.step << "/"
                   << s->expected_bytes.size() << " dst_off=" << w.dst_off
                   << " len=" << w.len << " dst_len=" << s->dst_len
                   << " arrived="
                   << (w.step < s->arrived_bytes.size()
                           ? s->arrived_bytes[w.step]
                           : 0)
                   << " expected="
                   << (w.step < s->expected_bytes.size()
                           ? s->expected_bytes[w.step]
                           : 0)
                   << " src_rank=" << w.src_rank;
      cntl->SetFailed(kECollMismatch,
                      "coll-mismatch: put outside the compiled plan");
      done();
      return;
    }
    s->busy += 1;  // pin dst against unregistration while copying
  }
  if ((w.flags & kCollFlagReduce) != 0 && s->ready_handle != 0 &&
      s->dst == s->send_base) {
    // In-place reduce with a readiness map: the accumulator IS the
    // producer-stamped send buffer, so folding into an unstamped range
    // would be overwritten by the still-running producer (a lost
    // update).  Park until the local producer stamped the target range,
    // bounded by the rendezvous budget — a producer that never stamps
    // fails the put (and with it the step, whole-or-nothing) instead of
    // wedging.
    const int64_t rdl =
        monotonic_time_us() + flag_val(rendezvous_flag(), 15000) * 1000;
    while (rma_ready_test(s->ready_handle, w.dst_off, w.len) != 1) {
      int abort_code = 0;
      {
        std::lock_guard<std::mutex> g(s->mu);
        abort_code = s->abort_code;
      }
      const int64_t now = monotonic_time_us();
      if (abort_code != 0 || now >= rdl) {
        {
          std::lock_guard<std::mutex> g(s->mu);
          s->busy -= 1;
        }
        wake_session(s.get());
        if (abort_code != 0) {
          cntl->SetFailed(abort_code, "coll-abort: aborted while "
                                      "waiting for accumulator stamp");
        } else {
          cntl->SetFailed(kECollAbort,
                          "coll-abort: accumulator range never stamped "
                          "ready (producer stalled)");
        }
        done();
        return;
      }
      // Sliced park: woken the instant the range is stamped, re-checks
      // abort/teardown every slice.
      rma_ready_wait(s->ready_handle, w.dst_off, w.len,
                     std::min(rdl, now + 10 * 1000));
    }
  }
  if ((w.flags & kCollFlagReduce) != 0) {
    // Element-wise u32 add.  One bounded staging copy: the payload may
    // arrive as a chained IOBuf whose block boundaries are not
    // 4-aligned.
    std::vector<char> tmp(w.len);
    req.copy_to(tmp.data(), w.len, sizeof(w));
    auto* acc = reinterpret_cast<uint32_t*>(s->dst + w.dst_off);
    const auto* add = reinterpret_cast<const uint32_t*>(tmp.data());
    const size_t words = w.len / 4;
    for (size_t i = 0; i < words; ++i) {
      acc[i] += add[i];
    }
  } else {
    req.copy_to(s->dst + w.dst_off, w.len, sizeof(w));
  }
  {
    std::lock_guard<std::mutex> g(s->mu);
    s->busy -= 1;
    s->arrived_bytes[w.step] += w.len;
  }
  wake_session(s.get());
  (void)resp;  // ack is the empty response — the tiny control frame
  done();
}

// Serve-side pin for pulled bytes: the response IOBuf wraps the
// member's own buffer zero-copy; `busy` holds the session (and with it
// the caller's buffer lifetime guarantee) until the transport's last
// reference drops — after the rails memcpy'd the bytes into the
// getter's region.
struct ServeCtx {
  std::shared_ptr<RecvSession> sess;
};

void serve_deleter(void*, void* vctx) {
  auto* ctx = static_cast<ServeCtx*>(vctx);
  {
    std::lock_guard<std::mutex> g(ctx->sess->mu);
    ctx->sess->busy -= 1;
  }
  wake_session(ctx->sess.get());
  delete ctx;
}

void handle_get(Controller* cntl, const IOBuf& req, IOBuf* resp,
                Closure done) {
  CollPutWire w;
  if (!parse_put_wire(req, &w) || w.len == 0) {
    cntl->SetFailed(EINVAL, "bad Coll.Get request");
    done();
    return;
  }
  // A get reads the SOURCE member's buffers: its session is the key.
  const uint64_t key = session_key(w.group_id, w.run_seq, w.src_rank);
  std::shared_ptr<RecvSession> s = wait_session(key);
  if (s == nullptr) {
    cntl->SetFailed(kECollAbort,
                    "coll-abort: no serve session (member never "
                    "entered, or the run already tore down)");
    done();
    return;
  }
  const bool from_recv = (w.flags & kCollFlagFromRecv) != 0;
  const int64_t deadline =
      monotonic_time_us() + flag_val(rendezvous_flag(), 15000) * 1000;
  while (true) {
    uint32_t v;
    bool ready_blocked = false;
    uint64_t ready_handle = 0;
    {
      std::lock_guard<std::mutex> g(s->mu);
      if (s->abort_code != 0) {
        cntl->SetFailed(s->abort_code, "coll-abort: " + s->abort_why);
        done();
        return;
      }
      // Overflow-safe bounds (the frame is network input): subtract,
      // never add — shard_off + len could wrap past a 2^64 check.
      const uint64_t src_lim = from_recv ? s->dst_len : s->send_len;
      if (w.step >= s->expected_serve.size() ||
          (from_recv && w.step == 0) ||
          w.shard_off > src_lim || w.len > src_lim - w.shard_off ||
          w.len > s->expected_serve[w.step] ||
          s->served_bytes[w.step] >
              s->expected_serve[w.step] - w.len) {
        cntl->SetFailed(kECollMismatch,
                        "coll-mismatch: get outside the compiled plan");
        done();
        return;
      }
      // Ring-forwarded bytes exist only once the PREVIOUS step's
      // arrivals landed here — the data dependency the schedule
      // encodes; sendbuf reads are ready from registration — UNLESS a
      // readiness map is attached, in which case the producer's stamp
      // over the requested range is the send-buffer dependency (a get
      // never ships unstamped bytes, overlap flag on or off).
      const bool dep_ok =
          from_recv
              ? s->arrived_bytes[w.step - 1] >= s->expected_bytes[w.step - 1]
              : (s->ready_handle == 0 ||
                 rma_ready_test(s->ready_handle, w.shard_off, w.len) == 1);
      if (dep_ok) {
        if (!from_recv && s->ready_handle != 0 && overlap_enabled()) {
          // A transfer fired off a readiness stamp, not the barrier.
          coll_vars().ready_triggers_total << 1;
        }
        s->busy += 1;  // released by the response payload's deleter
        s->served_bytes[w.step] += w.len;
        break;
      }
      ready_blocked = !from_recv;
      ready_handle = s->ready_handle;
      // Acquire pairs with wake_session's release bump.
      v = s->changed.value.load(std::memory_order_acquire);
    }
    if (monotonic_time_us() >= deadline) {
      cntl->SetFailed(kECollAbort,
                      "coll-abort: serve readiness timed out (peer "
                      "stalled a step behind)");
      done();
      return;
    }
    if (ready_blocked) {
      // Blocked on the producer's stamp: park on the ready map (woken
      // the instant the range is stamped), sliced so abort/teardown is
      // still observed promptly.
      rma_ready_wait(ready_handle, w.shard_off, w.len,
                     std::min(deadline, monotonic_time_us() + 10 * 1000));
    } else {
      s->changed.wait(v, deadline);
    }
  }
  const char* base = from_recv ? s->dst : s->send_base;
  auto* ctx = new ServeCtx{s};
  resp->append_user_data(const_cast<char*>(base) + w.shard_off, w.len,
                         &serve_deleter, ctx);
  wake_session(s.get());
  done();
}

void handle_abort(Controller* cntl, const IOBuf& req, IOBuf* resp,
                  Closure done) {
  CollPutWire w;
  if (!parse_put_wire(req, &w)) {
    cntl->SetFailed(EINVAL, "bad Coll.Abort request");
    done();
    return;
  }
  const int code = w.flags != 0 ? static_cast<int>(w.flags) : kECollAbort;
  const uint64_t key = session_key(w.group_id, w.run_seq, w.dst_rank);
  std::shared_ptr<RecvSession> s = find_session(key);
  if (s != nullptr) {
    {
      std::lock_guard<std::mutex> g(s->mu);
      if (s->abort_code == 0) {
        s->abort_code = code;
        s->abort_why = "peer rank " + std::to_string(w.src_rank) +
                       " failed step " + std::to_string(w.step);
      }
    }
    wake_session(s.get());
  } else {
    poison_run(key, code);
  }
  (void)resp;
  done();
}

bool parse_shardings(const IOBuf& req, size_t off, const ReshardReqWire& h,
                     Sharding* src, Sharding* dst) {
  constexpr uint32_t kMaxRanges = 4096;
  if (h.nsrc == 0 || h.ndst == 0 || h.nsrc > kMaxRanges ||
      h.ndst > kMaxRanges ||
      req.size() < off + (static_cast<size_t>(h.nsrc) + h.ndst) *
                             sizeof(ShardRangeWire)) {
    return false;
  }
  src->total = h.total;
  dst->total = h.total;
  for (uint32_t i = 0; i < h.nsrc + h.ndst; ++i) {
    ShardRangeWire rw;
    req.copy_to(&rw, sizeof(rw), off + i * sizeof(rw));
    ShardRange r;
    r.rank = rw.rank;
    r.off = rw.off;
    r.len = rw.len;
    (i < h.nsrc ? src : dst)->ranges.push_back(r);
  }
  return sharding_valid(*src, h.nmembers) &&
         sharding_valid(*dst, h.nmembers);
}

void handle_reshard_plan(Controller* cntl, const IOBuf& req, IOBuf* resp,
                         Closure done) {
  ReshardReqWire h;
  if (req.size() < sizeof(h)) {
    cntl->SetFailed(EINVAL, "bad Reshard.Plan request");
    done();
    return;
  }
  req.copy_to(&h, sizeof(h));
  Sharding src, dst;
  if (h.nmembers == 0 || h.nmembers > 4096 ||
      !parse_shardings(req, sizeof(h), h, &src, &dst)) {
    cntl->SetFailed(kECollMismatch, "coll-mismatch: bad shardings");
    done();
    return;
  }
  const TransferSchedule plan = plan_reshard(src, dst, h.nmembers);
  ReshardPlanWire out;
  memset(&out, 0, sizeof(out));
  out.bytes_moved = plan.bytes_moved();
  out.bytes_reused = plan.bytes_reused();
  out.naive_bytes = reshard_naive_bytes(src, h.nmembers);
  out.steps = static_cast<uint32_t>(plan.steps.size());
  for (const CollStep& s : plan.steps) {
    out.transfers += static_cast<uint32_t>(s.puts.size());
  }
  resp->append(&out, sizeof(out));
  coll_vars().reshard_plans_total << 1;
  done();
}

// Reshard.Execute state: cached GroupChannels (keyed by member-list
// hash) and the dst-shard regions this node allocated per block id, so a
// re-execute replaces (withdraw + free) instead of leaking.
struct ReshardHost {
  std::mutex mu;
  std::unordered_map<uint64_t, std::shared_ptr<GroupChannel>> groups;
  std::unordered_map<uint64_t, void*> owned_regions;  // block id → base
};

ReshardHost& reshard_host() {
  static ReshardHost* h = new ReshardHost();
  return *h;
}

void handle_reshard_execute(Controller* cntl, const IOBuf& req, IOBuf* resp,
                            Closure done) {
  ReshardReqWire h;
  if (req.size() < sizeof(h)) {
    cntl->SetFailed(EINVAL, "bad Reshard.Execute request");
    done();
    return;
  }
  req.copy_to(&h, sizeof(h));
  if (h.run_id == 0 || h.nmembers == 0 || h.nmembers > 256 ||
      h.my_rank >= h.nmembers ||
      req.size() < sizeof(h) + static_cast<uint64_t>(h.nmembers) * 64) {
    cntl->SetFailed(kECollMismatch,
                    "coll-mismatch: bad member list (run_id must be "
                    "nonzero — the cached group is shared)");
    done();
    return;
  }
  std::vector<std::string> members(h.nmembers);
  for (uint32_t i = 0; i < h.nmembers; ++i) {
    char row[64];
    req.copy_to(row, sizeof(row), sizeof(h) + i * 64);
    row[63] = '\0';
    members[i] = row;
  }
  Sharding src, dst;
  if (!parse_shardings(req, sizeof(h) + h.nmembers * 64, h, &src, &dst)) {
    cntl->SetFailed(kECollMismatch, "coll-mismatch: bad shardings");
    done();
    return;
  }
  // Source bytes: the published KV block src_block_base + my_rank — the
  // PR 11 registry IS the group's addressing layer.
  const uint64_t src_block = h.src_block_base + h.my_rank;
  const char* src_ptr = nullptr;
  uint64_t src_len = 0;
  std::shared_ptr<RmaMapping> src_map;
  if (kv_store().pin(src_block, 0, &src_ptr, &src_len, &src_map, nullptr) !=
      0) {
    cntl->SetFailed(kEKvMiss, "kv-miss: source shard block " +
                                  std::to_string(src_block) +
                                  " not published on this node");
    done();
    return;
  }
  if (src_len != sharding_local_bytes(src, h.my_rank)) {
    cntl->SetFailed(kECollMismatch,
                    "coll-mismatch: source block bytes != sharding's "
                    "local bytes for this rank");
    done();
    return;
  }
  // Group channel (cached by member list + transport).
  std::shared_ptr<GroupChannel> group;
  {
    std::string ident;
    for (const std::string& m : members) {
      ident += m;
      ident += '\n';
    }
    const uint64_t gkey =
        fnv1a(ident.data(), ident.size()) ^ (h.use_shm ? 1 : 0) ^
        (static_cast<uint64_t>(h.my_rank) << 32);
    ReshardHost& host = reshard_host();
    std::lock_guard<std::mutex> g(host.mu);
    auto it = host.groups.find(gkey);
    if (it != host.groups.end()) {
      group = it->second;
    } else {
      group = std::make_shared<GroupChannel>();
      GroupChannel::Options gopts;
      gopts.timeout_ms = h.timeout_ms > 0 ? h.timeout_ms : 30000;
      gopts.use_shm = h.use_shm != 0;
      if (group->Init(members, h.my_rank, &gopts) != 0) {
        cntl->SetFailed(EINVAL, "coll: group init failed");
        done();
        return;
      }
      host.groups[gkey] = group;
    }
  }
  const uint64_t dst_len = sharding_local_bytes(dst, h.my_rank);
  uint64_t dst_rkey = 0;
  char* dst_ptr = static_cast<char*>(rma_alloc(dst_len, &dst_rkey));
  if (dst_ptr == nullptr) {
    cntl->SetFailed(ENOMEM, "coll: cannot allocate the target shard");
    done();
    return;
  }
  const TransferSchedule plan = plan_reshard(src, dst, h.nmembers);
  const int rc = group->run(plan, src_ptr, src_len, dst_ptr, dst_len,
                            h.run_id);
  if (rc != 0) {
    rma_free(dst_ptr);
    cntl->SetFailed(rc, std::string("coll: reshard run failed (") +
                            coll_op_name(CollOp::kReshard) + ")");
    done();
    return;
  }
  // Publish the resharded shard as dst_block_base + rank: the fleet's
  // new layout is immediately block-addressable.
  const uint64_t dst_block = h.dst_block_base + h.my_rank;
  kv_store().withdraw(dst_block);  // replace semantics (kEKvMiss is fine)
  KvBlockMeta meta;
  const int prc = kv_store().publish(dst_block, dst_ptr, dst_len,
                                     /*lease_ms=*/0, &meta);
  if (prc != 0) {
    rma_free(dst_ptr);
    cntl->SetFailed(prc, "coll: publishing the resharded block failed");
    done();
    return;
  }
  {
    ReshardHost& host = reshard_host();
    std::lock_guard<std::mutex> g(host.mu);
    auto it = host.owned_regions.find(dst_block);
    if (it != host.owned_regions.end()) {
      rma_free(it->second);  // previous layout's region: munmap deferred
    }
    host.owned_regions[dst_block] = dst_ptr;
  }
  coll_vars().reshard_execs_total << 1;
  uint64_t out[2] = {dst_len, meta.generation};
  resp->append(out, sizeof(out));
  done();
}

}  // namespace

int coll_attach(Server* s) {
  coll_ensure_registered();
  kv_ensure_registered();
  int rcs[5];
  rcs[0] = s->RegisterMethod(kCollPutMethod, handle_put);
  rcs[1] = s->RegisterMethod(kCollGetMethod, handle_get);
  rcs[2] = s->RegisterMethod(kCollAbortMethod, handle_abort);
  rcs[3] = s->RegisterMethod(kReshardPlanMethod, handle_reshard_plan);
  rcs[4] = s->RegisterMethod(kReshardExecuteMethod, handle_reshard_execute);
  return rcs[0] == 0 && rcs[1] == 0 && rcs[2] == 0 && rcs[3] == 0 &&
                 rcs[4] == 0
             ? 0
             : -1;
}

// ---- GroupChannel --------------------------------------------------------

GroupChannel::~GroupChannel() = default;

int GroupChannel::init_channels(const Options* opts) {
  if (opts != nullptr) {
    opts_ = *opts;
  }
  group_id_ = 0;
  std::string ident;
  for (const std::string& m : members_) {
    ident += m;
    ident += '\n';
  }
  group_id_ = fnv1a(ident.data(), ident.size());
  chans_.clear();
  chans_.resize(members_.size());
  for (size_t r = 0; r < members_.size(); ++r) {
    if (r == my_rank_) {
      continue;  // local moves never ride a channel
    }
    auto ch = std::make_unique<Channel>();
    Channel::Options copts;
    copts.timeout_ms = opts_.timeout_ms;
    copts.use_shm = opts_.use_shm;
    copts.connection_type = "single";
    if (ch->Init(members_[r], &copts) != 0) {
      return -1;
    }
    chans_[r] = std::move(ch);
  }
  return 0;
}

int GroupChannel::Init(const std::vector<std::string>& members,
                       uint32_t my_rank, const Options* opts) {
  if (members.empty() || my_rank >= members.size()) {
    return -1;
  }
  members_ = members;
  my_rank_ = my_rank;
  naming_registry_.clear();
  coll_ensure_registered();
  return init_channels(opts);
}

int GroupChannel::InitNaming(const std::string& naming_url,
                             const std::string& self_addr,
                             const Options* opts) {
  constexpr const char* kScheme = "naming://";
  if (naming_url.rfind(kScheme, 0) != 0) {
    return -1;
  }
  const std::string rest = naming_url.substr(strlen(kScheme));
  const size_t slash = rest.find('/');
  if (slash == std::string::npos || slash + 1 >= rest.size()) {
    return -1;
  }
  naming_registry_ = rest.substr(0, slash);
  naming_service_ = rest.substr(slash + 1);
  naming_ch_ = std::make_unique<Channel>();
  Channel::Options copts;
  copts.timeout_ms = opts != nullptr ? opts->timeout_ms : 30000;
  if (naming_ch_->Init(naming_registry_, &copts) != 0) {
    return -1;
  }
  std::vector<NamingMember> view;
  uint64_t version = 0;
  if (naming_resolve(naming_ch_.get(), naming_service_, &view, &version) !=
      0) {
    return -1;
  }
  // Deterministic rank order: every member resolves the same view and
  // sorts by address.  Draining members have withdrawn (Server::Drain
  // runs the naming hook FIRST) and are absent by construction.
  std::vector<std::string> members;
  for (const NamingMember& m : view) {
    members.push_back(m.addr);
  }
  std::sort(members.begin(), members.end());
  auto self = std::find(members.begin(), members.end(), self_addr);
  if (self == members.end()) {
    return -1;  // not a member of the snapshot
  }
  members_ = std::move(members);
  my_rank_ = static_cast<uint32_t>(self - members_.begin());
  naming_version_ = version;
  coll_ensure_registered();
  return init_channels(opts);
}

int GroupChannel::check_epoch() {
  if (naming_registry_.empty()) {
    return 0;  // explicit group: membership is the caller's contract
  }
  std::vector<NamingMember> view;
  uint64_t version = 0;
  if (naming_resolve(naming_ch_.get(), naming_service_, &view, &version) !=
      0) {
    return 0;  // registry unreachable: no verdict — do not kill the run
  }
  if (version != naming_version_) {
    coll_vars().epoch_fails_total << 1;
    return kECollEpoch;
  }
  return 0;
}

namespace {

// One in-flight Coll.Put chunk.  Owned by the run until every chunk
// completed (complete_locked_call may touch the controller after done
// runs, so contexts outlive their dones and are reaped at run end).
struct PutCtx {
  Controller cntl;
  IOBuf req;
  IOBuf resp;
};

struct RunState {
  std::shared_ptr<RecvSession> sess;
  // Relaxed would do for the counter alone, but the release/acquire
  // pair orders the done-closure's failure write before the waiter's
  // read (see on_done / wait below).
  std::atomic<uint32_t> outstanding{0};
  std::atomic<int> fail_code{0};
  std::mutex mu;  // guards fail_why + ctxs
  std::string fail_why;
  std::vector<std::unique_ptr<PutCtx>> ctxs;
};

}  // namespace

int GroupChannel::run(const TransferSchedule& plan, const void* sendbuf,
                      uint64_t send_len, void* recvbuf, uint64_t recv_len,
                      uint64_t run_seq, uint64_t ready) {
  coll_vars().runs_total << 1;
  if (plan.nmembers != nmembers() || my_rank_ >= plan.nmembers) {
    return kECollMismatch;
  }
  const bool reduce_op = plan.op == CollOp::kReduceScatter;
  // The arrival target: recvbuf, or the accumulator (= sendbuf, which
  // reduce ops MUTATE — the documented in-place ring contract).
  char* acc = reduce_op ? static_cast<char*>(const_cast<void*>(sendbuf))
                        : static_cast<char*>(recvbuf);
  const uint64_t acc_len = reduce_op ? send_len : recv_len;
  // Validate every extent the plan references against the caller's
  // buffers before a single byte moves.
  std::vector<uint64_t> expected(plan.steps.size(), 0);
  for (size_t s = 0; s < plan.steps.size(); ++s) {
    for (const CollTransfer& t : plan.steps[s].puts) {
      if (t.reduce && (t.len % 4 != 0 || t.dst_off % 4 != 0)) {
        return kECollMismatch;  // u32 reduction needs aligned words
      }
      if (t.src == my_rank_) {
        const uint64_t lim = t.src_from_recv ? recv_len : send_len;
        if (t.src_off + t.len > lim) {
          return kECollMismatch;
        }
      }
      if (t.dst == my_rank_) {
        if (t.dst_off + t.len > acc_len) {
          return kECollMismatch;
        }
        expected[s] += t.len;
      }
    }
  }
  for (const CollTransfer& t : plan.local_copies) {
    if (t.src == my_rank_ &&
        (t.src_off + t.len > send_len || t.dst_off + t.len > acc_len)) {
      return kECollMismatch;
    }
  }
  for (const CollTransfer& t : plan.final_copies) {
    if (t.src == my_rank_ &&
        (t.src_off + t.len > acc_len || t.dst_off + t.len > recv_len)) {
      return kECollMismatch;
    }
  }
  if (run_seq == 0) {
    run_seq = ++run_counter_;
  }
  // Pull serves this member owes per step (copy transfers are gets BY
  // the destination; my step is complete only once my peers pulled it).
  std::vector<uint64_t> expected_serve(plan.steps.size(), 0);
  for (size_t s = 0; s < plan.steps.size(); ++s) {
    for (const CollTransfer& t : plan.steps[s].puts) {
      if (t.src == my_rank_ && !t.reduce) {
        expected_serve[s] += t.len;
      }
    }
  }

  // Heap-owned run state, CO-OWNED by every done closure: a completion's
  // tail (the counter decrement + wake) can run a beat after the waiter
  // observed outstanding == 0 and run() tore down — stack state or a raw
  // session pointer there would be a use-after-free under exactly the
  // loaded schedules collectives create.
  auto rs_owner = std::make_shared<RunState>();
  RunState& rs = *rs_owner;
  int poison = 0;
  rs.sess = register_session(group_id_, run_seq, my_rank_, acc, acc_len,
                             static_cast<const char*>(sendbuf), send_len,
                             expected, expected_serve, &poison, ready);
  if (rs.sess == nullptr) {
    coll_vars().aborts_total << 1;
    return poison != 0 ? poison : kECollAbort;
  }

  const uint64_t chunk_bytes =
      static_cast<uint64_t>(flag_val(chunk_flag(), 8 << 20));
  const uint32_t inflight =
      static_cast<uint32_t>(flag_val(inflight_flag(), 4));
  auto fail = [&](int code, const std::string& why) {
    int want = 0;
    if (rs.fail_code.compare_exchange_strong(want, code,
                                             std::memory_order_acq_rel)) {
      std::lock_guard<std::mutex> g(rs.mu);
      rs.fail_why = why;
    }
  };
  auto failed = [&]() -> int {
    // Acquire pairs with fail()'s release store.
    int code = rs.fail_code.load(std::memory_order_acquire);
    if (code == 0) {
      std::lock_guard<std::mutex> g(rs.sess->mu);
      code = rs.sess->abort_code;
    }
    return code;
  };
  // Parks until [off, off+len) of the send buffer is stamped ready,
  // sliced so peer aborts/failures stay promptly observed.  0, or the
  // error code the step fails with.
  auto wait_ready = [&](uint64_t off, uint64_t len,
                        int64_t rdl) -> int {
    while (true) {
      const int r = rma_ready_test(ready, off, len);
      if (r == 1) {
        return 0;
      }
      if (r < 0) {
        return kECollMismatch;  // dep outside the map: plan/map mismatch
      }
      int code;
      {
        std::lock_guard<std::mutex> g(rs.sess->mu);
        code = rs.sess->abort_code;
      }
      if (code != 0) {
        return code;
      }
      const int64_t now = monotonic_time_us();
      if (now >= rdl) {
        return ETIMEDOUT;
      }
      rma_ready_wait(ready, off, len, std::min(rdl, now + 10 * 1000));
    }
  };

  const bool overlap = ready != 0 && overlap_enabled();
  if (overlap) {
    coll_vars().overlap_runs_total << 1;
  }
  int rc = 0;
  // Entry budget for producer stamps (the step budget, ambient-folded —
  // the PR 15 deadline plane reaches a stalled producer too).
  int64_t entry_deadline = monotonic_time_us() + opts_.timeout_ms * 1000;
  {
    const int64_t amb = ambient_deadline();
    if (amb != 0) {
      entry_deadline = std::min(entry_deadline, amb);
    }
  }
  if (ready != 0 && !overlap) {
    // Overlap off: wait ONCE for everything this rank will ever read
    // from its send buffer, then run the unchanged barrier path —
    // byte-identical semantics, single wait.
    const uint64_t extent = plan_producer_extent(plan, my_rank_);
    if (extent != 0) {
      const int wrc = wait_ready(0, extent, entry_deadline);
      if (wrc != 0) {
        rc = wrc == ETIMEDOUT ? kEDeadlineExpired : wrc;
        fail(rc, "send buffer never stamped ready (producer stalled)");
      }
    }
  }

  // Local moves first: the member's own bytes never ride the fabric.
  // Overlap mode gates each copy on its input stamp (the producer may
  // still be filling later ranges).
  for (const CollTransfer& t : plan.local_copies) {
    if (t.src != my_rank_ || rc != 0) {
      continue;
    }
    if (overlap) {
      const CollDep d = transfer_input_dep(t);
      if (d.len != 0) {
        const int wrc = wait_ready(d.off, d.len, entry_deadline);
        if (wrc != 0) {
          rc = wrc == ETIMEDOUT ? kEDeadlineExpired : wrc;
          fail(rc, "local copy input never stamped ready");
          break;
        }
        coll_vars().ready_triggers_total << 1;
      }
    }
    memcpy(acc + t.dst_off,
           static_cast<const char*>(sendbuf) + t.src_off, t.len);
  }
  uint32_t steps_done = 0;
  for (size_t s = 0; s < plan.steps.size() && rc == 0; ++s) {
    const int64_t step_start = monotonic_time_us();
    int64_t deadline = step_start + opts_.timeout_ms * 1000;
    // Deadline plane (net/deadline.h): the serving request's remaining
    // budget bounds every step — an expired budget aborts the schedule
    // whole-or-nothing through the same group-abort path a failed put
    // takes, instead of grinding out steps nobody is waiting for.
    const int64_t amb = ambient_deadline();
    if (amb != 0) {
      if (step_start >= amb) {
        rc = kEDeadlineExpired;
        fail(rc, "caller deadline expired before step " +
                     std::to_string(s));
        break;
      }
      deadline = std::min(deadline, amb);
    }
    if ((rc = check_epoch()) != 0) {
      fail(rc, "membership epoch moved under the schedule");
      break;
    }
    // Shared (not raw) handles for the done closures — see rs_owner.
    std::shared_ptr<RunState> rsp = rs_owner;
    std::shared_ptr<RecvSession> sess = rs.sess;
    // Bound the in-flight window (trpc_coll_inflight): transfer k+1
    // overlaps transfer k's verification, never more than the window.
    auto throttle = [&]() {
      while (rs.outstanding.load(std::memory_order_acquire) >= inflight) {
        if ((rc = failed()) != 0 || monotonic_time_us() > deadline) {
          rc = rc != 0 ? rc : ETIMEDOUT;
          return;
        }
        const uint32_t v =
            // Acquire pairs with wake_session's release bump.
            rs.sess->changed.value.load(std::memory_order_acquire);
        if (rs.outstanding.load(std::memory_order_acquire) >= inflight) {
          rs.sess->changed.wait(v, monotonic_time_us() + 20 * 1000);
        }
      }
    };
    auto fail_call = [rsp](size_t step, const char* what,
                           Controller* cntl) {  // rsp: shared, see above
      int want = 0;
      const int code =
          cntl->error_code() != 0 ? cntl->error_code() : kECollAbort;
      if (rsp->fail_code.compare_exchange_strong(
              want, code, std::memory_order_acq_rel)) {
        std::lock_guard<std::mutex> g(rsp->mu);
        rsp->fail_why = std::string(what) + " failed at step " +
                        std::to_string(step) + ": " + cntl->error_text();
      }
    };
    for (const CollTransfer& t : plan.steps[s].puts) {
      if (rc != 0) {
        break;
      }
      if (t.dst == my_rank_ && !t.reduce) {
        // PULL: one Coll.Get per transfer, landing DIRECT in my
        // registered buffer slice — the serving member's rails write
        // the bytes straight into place, one memcpy end to end.  (The
        // transfer is chunked INSIDE the one-sided put by the rma
        // plane; trpc_coll_chunk_bytes governs the push path below.)
        throttle();
        if (rc != 0) {
          break;
        }
        CollPutWire w;
        memset(&w, 0, sizeof(w));
        w.group_id = group_id_;
        w.run_seq = run_seq;
        w.op = static_cast<uint32_t>(plan.op);
        w.src_rank = t.src;
        w.step = static_cast<uint32_t>(s);
        w.nchunks = 1;
        w.flags = t.src_from_recv ? kCollFlagFromRecv : 0;
        w.dst_off = t.dst_off;
        w.len = t.len;
        w.shard_off = t.src_off;  // source-buffer offset to serve
        w.shard_len = t.len;
        w.dst_rank = my_rank_;
        auto ctx = std::make_unique<PutCtx>();
        ctx->req.append(&w, sizeof(w));
        ctx->cntl.set_timeout_ms(opts_.timeout_ms);
        char* target = acc + t.dst_off;
        ctx->cntl.call().land_buf = target;
        ctx->cntl.call().land_cap = t.len;
        PutCtx* raw = ctx.get();
        {
          std::lock_guard<std::mutex> g(rs.mu);
          rs.ctxs.push_back(std::move(ctx));
        }
        // Release on the increment: the context set up above is
        // published before the done closure can observe the counter.
        rs.outstanding.fetch_add(1, std::memory_order_release);
        coll_vars().puts_total << 1;
        coll_vars().put_bytes << static_cast<int64_t>(t.len);
        const uint64_t want_len = t.len;
        chans_[t.src]->CallMethod(
            kCollGetMethod, raw->req, &raw->resp, &raw->cntl,
            [rsp, sess, raw, s, target, want_len]() {
              if (raw->cntl.Failed()) {
                int want = 0;
                const int code = raw->cntl.error_code() != 0
                                     ? raw->cntl.error_code()
                                     : kECollAbort;
                if (rsp->fail_code.compare_exchange_strong(
                        want, code, std::memory_order_acq_rel)) {
                  std::lock_guard<std::mutex> g(rsp->mu);
                  rsp->fail_why = "get failed at step " +
                                  std::to_string(s) + ": " +
                                  raw->cntl.error_text();
                }
              } else if (raw->resp.size() != want_len) {
                int want = 0;
                if (rsp->fail_code.compare_exchange_strong(
                        want, kECollMismatch, std::memory_order_acq_rel)) {
                  std::lock_guard<std::mutex> g(rsp->mu);
                  rsp->fail_why = "get answered the wrong length";
                }
              } else {
                // Landed in place (direct put / striped landing)?  If
                // the response is a bounce view instead, place it now.
                const bool in_place =
                    raw->resp.block_count() == 1 &&
                    raw->resp.ref_at(0).block->data +
                            raw->resp.ref_at(0).offset ==
                        target;
                if (!in_place) {
                  raw->resp.copy_to(target, want_len);
                }
                {
                  std::lock_guard<std::mutex> g(sess->mu);
                  sess->arrived_bytes[s] += want_len;
                }
              }
              // Release orders the placement (and any failure write)
              // before the waiter's acquire observation.  rsp/sess are
              // shared_ptr copies: this tail may outlive run().
              rsp->outstanding.fetch_sub(1, std::memory_order_release);
              wake_session(sess.get());
            });
        continue;
      }
      if (t.src != my_rank_ || !t.reduce) {
        continue;  // not mine to initiate (pulled by its destination)
      }
      // PUSH (reduce transfers): chunked Coll.Put — the receiver folds
      // each chunk into its accumulator.
      const char* base = acc;  // reduce reads the accumulator (sendbuf)
      const uint32_t nchunks = static_cast<uint32_t>(
          (t.len + chunk_bytes - 1) / chunk_bytes);
      for (uint32_t c = 0; c < nchunks && rc == 0; ++c) {
        const uint64_t off = static_cast<uint64_t>(c) * chunk_bytes;
        const uint64_t len = std::min(chunk_bytes, t.len - off);
        if (overlap && !t.src_from_recv) {
          // Readiness-triggered push: this chunk fires the moment the
          // producer stamped its input range — the T3 per-chunk
          // overlap seam.  A producer that never stamps trips the step
          // deadline (whole-or-nothing), never a wedge.
          const int wrc = wait_ready(t.src_off + off, len, deadline);
          if (wrc != 0) {
            rc = wrc;
            fail(rc, "push input never stamped ready at step " +
                         std::to_string(s));
            break;
          }
          coll_vars().ready_triggers_total << 1;
        }
        throttle();
        if (rc != 0) {
          break;
        }
        CollPutWire w;
        memset(&w, 0, sizeof(w));
        w.group_id = group_id_;
        w.run_seq = run_seq;
        w.op = static_cast<uint32_t>(plan.op);
        w.src_rank = my_rank_;
        w.step = static_cast<uint32_t>(s);
        w.nchunks = nchunks;
        w.chunk = c;
        w.flags = kCollFlagReduce;
        w.dst_off = t.dst_off + off;
        w.len = len;
        w.shard_off = t.dst_off;
        w.shard_len = t.len;
        w.dst_rank = t.dst;
        auto ctx = std::make_unique<PutCtx>();
        ctx->req.append(&w, sizeof(w));
        // Zero-copy payload ref: the caller's buffer outlives the run
        // (run() only returns after every chunk completed or was
        // cancelled), so no deleter is needed.
        ctx->req.append_user_data(
            const_cast<char*>(base) + t.src_off + off, len, &noop_deleter);
        ctx->cntl.set_timeout_ms(opts_.timeout_ms);
        PutCtx* raw = ctx.get();
        {
          std::lock_guard<std::mutex> g(rs.mu);
          rs.ctxs.push_back(std::move(ctx));
        }
        // Release on the increment: the context set up above is
        // published before the done closure can observe the counter.
        rs.outstanding.fetch_add(1, std::memory_order_release);
        coll_vars().puts_total << 1;
        coll_vars().put_bytes << static_cast<int64_t>(len);
        chans_[t.dst]->CallMethod(
            kCollPutMethod, raw->req, &raw->resp, &raw->cntl,
            [rsp, sess, raw, s, fail_call]() {
              if (raw->cntl.Failed()) {
                fail_call(s, "put", &raw->cntl);
              }
              // Release orders this chunk's completion (and any failure
              // write) before the waiter's acquire observation.  rsp/
              // sess are shared_ptr copies: this tail may outlive run().
              rsp->outstanding.fetch_sub(1, std::memory_order_release);
              wake_session(sess.get());
            });
      }
    }
    // Step barrier: my transfers acked (each ack IS the tiny per-put
    // control frame), my expected receives landed, and my peers' pulls
    // of this step's data served.
    while (rc == 0) {
      if ((rc = failed()) != 0) {
        break;
      }
      bool sends_done =
          rs.outstanding.load(std::memory_order_acquire) == 0;
      bool recvs_done;
      bool serves_done;
      uint32_t v;
      {
        std::lock_guard<std::mutex> g(rs.sess->mu);
        recvs_done = rs.sess->arrived_bytes[s] >= expected[s];
        serves_done = rs.sess->served_bytes[s] >= expected_serve[s];
        // Acquire pairs with wake_session's release bump.
        v = rs.sess->changed.value.load(std::memory_order_acquire);
      }
      if (sends_done && recvs_done && serves_done) {
        break;
      }
      if (monotonic_time_us() > deadline) {
        rc = ETIMEDOUT;
        fail(ETIMEDOUT, "step " + std::to_string(s) + " timed out");
        break;
      }
      rs.sess->changed.wait(v, monotonic_time_us() + 20 * 1000);
    }
    if (rc == 0) {
      steps_done += 1;
      coll_vars().steps_total << 1;
      coll_vars().step_lat(plan.op)
          << (monotonic_time_us() - step_start);
      {
        // Contexts of a completed step are dead weight; reap them so a
        // many-step schedule's memory stays bounded by one step.
        std::lock_guard<std::mutex> g(rs.mu);
        rs.ctxs.clear();
      }
    }
  }

  if (rc != 0) {
    coll_vars().aborts_total << 1;
    {
      std::lock_guard<std::mutex> g(rs.mu);
      LOG(Warning) << "coll run failed rank=" << my_rank_ << " op="
                   << coll_op_name(plan.op) << " rc=" << rc << " why="
                   << rs.fail_why << " abort_why=" << rs.sess->abort_why;
    }
    // Cancel the still-in-flight chunks, then drain them: the contexts
    // (and the caller's buffers) must not be touched by a late
    // completion after run() returns.  The cancels go out with rs.mu
    // released: a cancel can complete its call inline, and the done
    // closure takes rs.mu to record the first failure.
    std::vector<fid_t> in_flight;
    {
      std::lock_guard<std::mutex> g(rs.mu);
      for (auto& c : rs.ctxs) {
        in_flight.push_back(c->cntl.call_id());
      }
    }
    for (const fid_t cid : in_flight) {
      StartCancel(cid);
    }
    while (rs.outstanding.load(std::memory_order_acquire) != 0) {
      const uint32_t v =
          // Acquire pairs with the done closures' release decrement.
          rs.sess->changed.value.load(std::memory_order_acquire);
      if (rs.outstanding.load(std::memory_order_acquire) != 0) {
        rs.sess->changed.wait(v, monotonic_time_us() + 50 * 1000);
      }
    }
    // Tell the group: the step failed for everyone (whole-or-nothing).
    CollPutWire w;
    memset(&w, 0, sizeof(w));
    w.group_id = group_id_;
    w.run_seq = run_seq;
    w.op = static_cast<uint32_t>(plan.op);
    w.src_rank = my_rank_;
    w.step = steps_done;
    w.flags = static_cast<uint32_t>(rc);
    for (size_t r = 0; r < chans_.size(); ++r) {
      if (chans_[r] == nullptr) {
        continue;
      }
      w.dst_rank = static_cast<uint32_t>(r);  // per-peer session key
      IOBuf abort_req;
      abort_req.append(&w, sizeof(w));
      Controller cntl;
      cntl.set_timeout_ms(std::min<int64_t>(2000, opts_.timeout_ms));
      IOBuf resp;
      chans_[r]->CallMethod(kCollAbortMethod, abort_req, &resp, &cntl);
      // Best effort: an unreachable peer fails its own step anyway.
    }
  } else {
    for (const CollTransfer& t : plan.final_copies) {
      if (t.src == my_rank_) {
        memcpy(static_cast<char*>(recvbuf) + t.dst_off, acc + t.src_off,
               t.len);
      }
    }
  }
  unregister_session(rs.sess);
  return rc;
}

int GroupChannel::all_gather(const void* sendbuf, uint64_t shard_bytes,
                             void* recvbuf, uint64_t recv_len) {
  return run(plan_all_gather(nmembers(), shard_bytes), sendbuf, shard_bytes,
             recvbuf, recv_len);
}

int GroupChannel::reduce_scatter(void* sendbuf, uint64_t send_len,
                                 void* recvbuf, uint64_t shard_bytes) {
  return run(plan_reduce_scatter(nmembers(), shard_bytes), sendbuf,
             send_len, recvbuf, shard_bytes);
}

int GroupChannel::all_to_all(const void* sendbuf, uint64_t send_len,
                             void* recvbuf, uint64_t recv_len) {
  // A remainder would silently drop the tail bytes (shard floors).
  if (nmembers() == 0 || send_len % nmembers() != 0) {
    return kECollMismatch;
  }
  return run(plan_all_to_all(nmembers(), send_len / nmembers()), sendbuf,
             send_len, recvbuf, recv_len);
}

int GroupChannel::reshard(const Sharding& src, const Sharding& dst,
                          const void* sendbuf, uint64_t send_len,
                          void* recvbuf, uint64_t recv_len,
                          uint64_t run_seq) {
  if (!sharding_valid(src, nmembers()) || !sharding_valid(dst, nmembers()) ||
      src.total != dst.total) {
    return kECollMismatch;
  }
  return run(plan_reshard(src, dst, nmembers()), sendbuf, send_len,
             recvbuf, recv_len, run_seq);
}

}  // namespace trpc
