#include "net/infer.h"

#include <atomic>
#include <deque>
#include <memory>
#include <mutex>
#include <unordered_map>
#include <vector>

#include "base/flags.h"
#include "base/logging.h"
#include "base/time.h"
#include "fiber/event.h"
#include "fiber/fiber.h"
#include "net/channel.h"
#include "net/concurrency_limiter.h"
#include "net/controller.h"
#include "net/deadline.h"
#include "net/kvstore.h"
#include "net/qos.h"
#include "net/server.h"
#include "net/stream.h"
#include "stat/slo.h"

namespace trpc {

namespace {

// ---- flags ----------------------------------------------------------------

Flag* batch_max_flag() {
  static Flag* f = [] {
    Flag* flag = Flag::define_int64(
        "trpc_infer_batch_max", 256,
        "continuous-batching decode slots: requests concurrently in the "
        "running batch, one token each per step ([1, 65536]); freed "
        "slots re-admit from the waiting queue the same step");
    if (flag != nullptr) {
      flag->set_int_range(1, 65536);
    }
    return flag;
  }();
  return f;
}

Flag* queue_max_flag() {
  static Flag* f = [] {
    Flag* flag = Flag::define_int64(
        "trpc_infer_queue_max", 200000,
        "admitted-but-not-yet-decoding requests the scheduler will hold "
        "([0, 1000000]); past batch+queue, Infer.Submit sheds with "
        "kEOverloaded (2005) — each waiting request holds its accepted "
        "token stream open, so this bounds logical streams too");
    if (flag != nullptr) {
      flag->set_int_range(0, 1000000);
    }
    return flag;
  }();
  return f;
}

Flag* step_us_flag() {
  static Flag* f = [] {
    Flag* flag = Flag::define_int64(
        "trpc_infer_step_us", 1000,
        "simulated batched forward-pass time per decode step, spent once "
        "per step for the WHOLE batch ([0, 10000000] µs, 0 = no model "
        "cost — drain mode); the knob bench sweeps to model TPOT");
    if (flag != nullptr) {
      flag->set_int_range(0, 10000000);
    }
    return flag;
  }();
  return f;
}

Flag* prefill_us_flag() {
  static Flag* f = [] {
    Flag* flag = Flag::define_int64(
        "trpc_infer_prefill_us_per_token", 5,
        "simulated prefill compute per UNCACHED prompt token ([0, "
        "1000000] µs); prefix-cache-matched tokens skip this entirely — "
        "the measurable recompute the cache saves");
    if (flag != nullptr) {
      flag->set_int_range(0, 1000000);
    }
    return flag;
  }();
  return f;
}

Flag* max_new_flag() {
  static Flag* f = [] {
    Flag* flag = Flag::define_int64(
        "trpc_infer_max_new_tokens", 256,
        "cap on generated tokens per request ([1, 65536]); a submit "
        "asking for more is clamped, and the effective cap is further "
        "clamped to the client's advertised stream window so one slow "
        "reader can never park the shared decode loop");
    if (flag != nullptr) {
      flag->set_int_range(1, 65536);
    }
    return flag;
  }();
  return f;
}

Flag* bytes_per_token_flag() {
  static Flag* f = [] {
    Flag* flag = Flag::define_int64(
        "trpc_infer_bytes_per_token", 64,
        "simulated KV-cache bytes per prompt token ([1, 65536]); sizes "
        "the prefix blocks published after prefill and the "
        "bytes-recomputed/bytes-cached accounting");
    if (flag != nullptr) {
      flag->set_int_range(1, 65536);
    }
    return flag;
  }();
  return f;
}

uint64_t splitmix64(uint64_t x) {
  x += 0x9e3779b97f4a7c15ULL;
  x = (x ^ (x >> 30)) * 0xbf58476d1ce4e5b9ULL;
  x = (x ^ (x >> 27)) * 0x94d049bb133111ebULL;
  return x ^ (x >> 31);
}

constexpr size_t kMaxChainBlocks = 64;

// ---- request --------------------------------------------------------------

enum FetchState { kFetchNone = 0, kFetchRunning = 1, kFetchDone = 2 };

struct InferReq {
  uint64_t id = 0;
  std::string tenant;
  StreamId sid = 0;
  int64_t arrival_us = 0;
  uint32_t max_new = 0;
  uint32_t emitted = 0;
  uint32_t nprompt = 0;
  uint32_t cached_tokens = 0;
  uint64_t prompt_hash = 0;
  std::vector<uint64_t> prompt;  // dropped once prefill publishes
  bool publish = true;
  bool decoding = false;  // prefill finished, counters transitioned
  int64_t ready_at_us = 0;
  int64_t first_token_us = 0;
  int64_t last_token_us = 0;
  std::shared_ptr<CancelScope> scope;
  std::atomic<bool> peer_closed{false};
  std::atomic<int> fetch_state{kFetchNone};
  // Matched blocks whose fetch failed for a non-cancel reason fall back
  // to recompute: the fetch fiber counts the tokens, the loop converts
  // them to prefill time once (fetch_state == kFetchDone).
  std::atomic<uint32_t> fallback_tokens{0};
  // Total bytes the prefix fetch plans to pull / has pulled — the delta
  // is what a mid-flight cancel credits to deadline_cancel_saved_bytes.
  uint64_t fetch_total_bytes = 0;
  std::atomic<uint64_t> fetch_done_bytes{0};
  std::vector<KvPrefixMeta> matched;  // one replica meta per matched depth
};

using ReqPtr = std::shared_ptr<InferReq>;

// Tracks detached prefix-fetch fibers so stop() can wait for them to
// retire.  Shared (not scheduler-owned): a retiring fiber touches ONLY
// this block after its decrement, so the scheduler may be freed the
// moment inflight hits zero even if the fiber hasn't returned yet.
struct FetchDrain {
  std::atomic<int64_t> inflight{0};
  Event ev;  // bumped on every retirement
};

}  // namespace

// ---- scheduler ------------------------------------------------------------

class InferScheduler {
 public:
  InferScheduler(Server* s, const InferOptions& opts)
      : srv_(s), opts_(opts) {}

  int start() {
    const int rc = srv_->RegisterMethod(
        "Infer.Submit",
        [this](Controller* cntl, const IOBuf& req, IOBuf* resp,
               Closure done) { submit(cntl, req, resp, std::move(done)); });
    if (rc != 0) {
      return rc;
    }
    if (fiber_start(&loop_fid_, &InferScheduler::loop_entry, this) != 0) {
      return -1;
    }
    loop_started_ = true;
    return 0;
  }

  void stop() {
    stop_.store(true, std::memory_order_release);
    wake();
    if (loop_started_) {
      fiber_join(loop_fid_);
    }
    // The loop's teardown cancelled every request scope, so in-flight
    // fetch fibers abort promptly — but they hold a raw scheduler
    // pointer and may still be inside CallMethod on fetch_ch_ or waking
    // work_ev_.  Wait for every one to retire before anything is freed.
    while (true) {
      const uint32_t snap =
          fetch_drain_->ev.value.load(std::memory_order_acquire);
      if (fetch_drain_->inflight.load(std::memory_order_acquire) == 0) {
        break;
      }
      fetch_drain_->ev.wait(snap, monotonic_time_us() + 50 * 1000);
    }
    std::lock_guard<std::mutex> g(fetch_ch_mu_);
    if (fetch_ch_ != nullptr) {
      delete fetch_ch_;
      fetch_ch_ = nullptr;
    }
  }

  size_t active() const { return active_n_.load(std::memory_order_acquire); }
  size_t waiting() const {
    return waiting_n_.load(std::memory_order_acquire);
  }
  int64_t streams_live() const {
    return streams_live_.load(std::memory_order_acquire);
  }
  int64_t streams_peak() const {
    return streams_peak_.load(std::memory_order_acquire);
  }
  std::string dump_json() const;

 private:
  static void loop_entry(void* arg) {
    static_cast<InferScheduler*>(arg)->loop();
  }

  void wake() {
    work_ev_.value.fetch_add(1, std::memory_order_release);
    work_ev_.wake_all();
  }

  void shed(Controller* cntl, const std::string& tenant) {
    infer_vars().shed_total << 1;
    auto gov = srv_->qos_governor();
    if (gov != nullptr) {
      for (const auto& e : gov->entries()) {
        if (e->name == tenant && e->shed != nullptr) {
          *e->shed << 1;
          break;
        }
      }
    }
    cntl->SetFailed(kEOverloaded, "inference batch + queue saturated");
  }

  // Weighted-fair admission under pressure.  Caller holds mu_.
  bool over_share_locked(const std::string& tenant, int64_t cap) {
    int w = qos_tenant_weight(tenant);
    int total_w = 0;
    auto gov = srv_->qos_governor();
    if (gov != nullptr) {
      for (const auto& e : gov->entries()) {
        if (e->name == tenant) {
          w = e->weight;
        }
      }
    }
    bool self_seen = false;
    for (const auto& [name, live] : tenant_live_) {
      if (live <= 0) {
        continue;
      }
      int tw = qos_tenant_weight(name);
      if (gov != nullptr) {
        for (const auto& e : gov->entries()) {
          if (e->name == name) {
            tw = e->weight;
          }
        }
      }
      total_w += tw;
      if (name == tenant) {
        self_seen = true;
      }
    }
    if (!self_seen) {
      total_w += w;
    }
    int64_t share = total_w > 0 ? cap * w / total_w : cap;
    auto slo = srv_->slo_engine();
    if (slo != nullptr && slo->tenant_breached(tenant)) {
      // A tenant burning its error budget is already failing its SLO —
      // halving its share sheds its excess first so it stops dragging
      // the batch for tenants still inside theirs.
      share /= 2;
    }
    if (share < 1) {
      share = 1;
    }
    auto it = tenant_live_.find(tenant);
    const int64_t mine = it != tenant_live_.end() ? it->second : 0;
    return mine >= share;
  }

  void submit(Controller* cntl, const IOBuf& req, IOBuf* resp, Closure done);
  void loop();
  void admit_locked(std::vector<ReqPtr>* admitted);
  void begin_prefill(const ReqPtr& r, int64_t now);
  void fetch_blocks(const ReqPtr& r);
  void publish_blocks(const ReqPtr& r);
  bool step_request(const ReqPtr& r, int64_t now);
  void finish(const ReqPtr& r, bool cancelled);
  void release_slot(const std::string& tenant);

  Server* srv_;
  InferOptions opts_;

  mutable std::mutex mu_;
  std::deque<ReqPtr> waiting_;
  std::unordered_map<std::string, int64_t> tenant_live_;
  std::vector<ReqPtr> active_;  // loop-owned

  Event work_ev_;
  std::atomic<bool> stop_{false};
  fiber_t loop_fid_{};
  bool loop_started_ = false;

  std::atomic<uint64_t> next_id_{1};
  std::atomic<size_t> active_n_{0};
  std::atomic<size_t> waiting_n_{0};
  std::atomic<int64_t> streams_live_{0};
  std::atomic<int64_t> streams_peak_{0};

  std::mutex fetch_ch_mu_;
  Channel* fetch_ch_ = nullptr;
  std::shared_ptr<FetchDrain> fetch_drain_ = std::make_shared<FetchDrain>();
};

void InferScheduler::submit(Controller* cntl, const IOBuf& req, IOBuf* resp,
                            Closure done) {
  infer_vars().submitted_total << 1;
  InferSubmitWire w;
  if (req.size() < sizeof(w)) {
    cntl->SetFailed(EINVAL, "short Infer.Submit request");
    done();
    return;
  }
  req.copy_to(&w, sizeof(w));
  if (w.magic != kInferMagic ||
      req.size() < sizeof(w) + w.n_prompt_tokens * sizeof(uint64_t) ||
      w.n_prompt_tokens > 65536) {
    cntl->SetFailed(EINVAL, "bad Infer.Submit request");
    done();
    return;
  }
  if (cntl->call().peer_stream == 0) {
    cntl->SetFailed(EINVAL, "Infer.Submit must offer a token stream");
    done();
    return;
  }

  const std::string tenant = cntl->qos_tenant();
  const int64_t batch_max = batch_max_flag()->int64_value();
  const int64_t queue_max = queue_max_flag()->int64_value();
  const int64_t cap = batch_max + queue_max;
  {
    std::lock_guard<std::mutex> g(mu_);
    const int64_t live = streams_live_.load(std::memory_order_relaxed);
    if (live >= cap ||
        (live >= (cap + 1) / 2 && over_share_locked(tenant, cap))) {
      shed(cntl, tenant);
      done();
      return;
    }
    // Reserve the slot in the SAME critical section as the cap/share
    // check: N concurrent submits would otherwise all pass the check
    // before any increment lands, overshooting batch+queue and the
    // per-tenant shares.  Failure paths below release the reservation.
    tenant_live_[tenant] += 1;
    const int64_t now_live =
        streams_live_.fetch_add(1, std::memory_order_acq_rel) + 1;
    int64_t peak = streams_peak_.load(std::memory_order_relaxed);
    while (now_live > peak &&
           !streams_peak_.compare_exchange_weak(peak, now_live,
                                                std::memory_order_acq_rel)) {
    }
  }

  auto r = std::make_shared<InferReq>();
  r->id = next_id_.fetch_add(1, std::memory_order_relaxed);
  r->tenant = tenant;
  r->arrival_us = monotonic_time_us();
  r->nprompt = w.n_prompt_tokens;
  r->publish = (w.flags & kSubmitNoPublish) == 0;
  r->prompt.resize(w.n_prompt_tokens);
  if (w.n_prompt_tokens > 0) {
    req.copy_to(r->prompt.data(), w.n_prompt_tokens * sizeof(uint64_t),
                sizeof(w));
  }
  uint64_t h = 0x811c9dc5;
  for (uint64_t t : r->prompt) {
    h = splitmix64(h ^ t);
  }
  r->prompt_hash = h;

  // Prefix-cache match: longest cached chain of the prompt.
  const int64_t block_tokens =
      Flag::find("trpc_kv_prefix_block_tokens") != nullptr
          ? Flag::find("trpc_kv_prefix_block_tokens")->int64_value()
          : 128;
  if (opts_.registry != nullptr && r->nprompt > 0) {
    Key128 keys[kMaxChainBlocks];
    const size_t nkeys =
        kv_prefix_chain(r->prompt.data(), r->nprompt, block_tokens, keys,
                        kMaxChainBlocks);
    std::vector<KvPrefixMeta> replicas;
    const size_t nblocks = opts_.registry->match(keys, nkeys, &replicas);
    // Keep ONE replica per depth (the first listed), in chain order.
    r->matched.reserve(nblocks);
    uint32_t next_depth = 0;
    for (const auto& m : replicas) {
      if (m.depth == next_depth) {
        r->matched.push_back(m);
        r->fetch_total_bytes += m.len;
        ++next_depth;
      }
    }
    r->cached_tokens = static_cast<uint32_t>(
        std::min<uint64_t>(r->matched.size() * block_tokens, r->nprompt));
  }

  uint32_t max_new = w.max_new_tokens != 0
                         ? w.max_new_tokens
                         : static_cast<uint32_t>(
                               max_new_flag()->int64_value());
  max_new = std::min<uint32_t>(
      max_new, static_cast<uint32_t>(max_new_flag()->int64_value()));

  // Accept the offered stream: the per-request token channel.
  StreamOptions sopts;
  std::weak_ptr<InferReq> weak = r;
  sopts.on_closed = [weak](StreamId) {
    if (auto req = weak.lock()) {
      req->peer_closed.store(true, std::memory_order_release);
    }
  };
  StreamId sid = 0;
  if (StreamAccept(&sid, cntl, sopts) != 0) {
    release_slot(tenant);
    cntl->SetFailed(EINVAL, "stream accept failed");
    done();
    return;
  }
  r->sid = sid;
  // Never let one request's token output exceed the client's advertised
  // credit: the decode loop writes without parking.  A window that can't
  // even fit ONE TokenRecord is rejected outright — leaving max_new
  // unclamped would park the shared decode fiber on the first write,
  // stalling every tenant's requests (and the deadline reaper with them).
  const uint64_t credit = stream_send_window(sid);
  if (credit < sizeof(TokenRecord)) {
    StreamClose(sid);
    // Don't advertise the destroyed stream in the failed response — the
    // client's not-accepted path closes its offered end cleanly.
    cntl->call().accepted_stream = 0;
    release_slot(tenant);
    cntl->SetFailed(EINVAL,
                    "stream window smaller than one TokenRecord");
    done();
    return;
  }
  const uint64_t fit = credit / sizeof(TokenRecord);
  if (fit < max_new) {
    max_new = static_cast<uint32_t>(fit);
  }
  r->max_new = max_new > 0 ? max_new : 1;

  // Cancel plane: connection death or budget expiry triggers the scope;
  // the loop polls triggered() and Cancel() fans to in-flight fetches.
  r->scope = std::make_shared<CancelScope>();
  r->scope->socket = cntl->call().socket_id;
  r->scope->deadline_us = cntl->deadline_abs_us();

  {
    std::lock_guard<std::mutex> g(mu_);
    waiting_.push_back(r);
    waiting_n_.store(waiting_.size(), std::memory_order_release);
  }
  wake();

  InferSubmitReply reply;
  reply.request_id = r->id;
  reply.cached_tokens = r->cached_tokens;
  reply.block_tokens = static_cast<uint32_t>(block_tokens);
  resp->append(&reply, sizeof(reply));
  done();
}

// Pops admissible requests while slots remain.  Expired/cancelled waiters
// are finished (not admitted) — their slot never counts.  Caller holds NO
// lock; admitted requests are appended to active_ by the loop.
void InferScheduler::admit_locked(std::vector<ReqPtr>* admitted) {
  const size_t batch_max =
      static_cast<size_t>(batch_max_flag()->int64_value());
  std::lock_guard<std::mutex> g(mu_);
  while (active_.size() + admitted->size() < batch_max &&
         !waiting_.empty()) {
    ReqPtr r = waiting_.front();
    waiting_.pop_front();
    admitted->push_back(std::move(r));
  }
  waiting_n_.store(waiting_.size(), std::memory_order_release);
}

void InferScheduler::begin_prefill(const ReqPtr& r, int64_t now) {
  infer_vars().admitted_total << 1;
  infer_vars().prefill_tokens_total << r->nprompt;
  infer_vars().prefill_cached_tokens_total << r->cached_tokens;
  const int64_t bpt = bytes_per_token_flag()->int64_value();
  const uint32_t recompute = r->nprompt - r->cached_tokens;
  infer_vars().prefill_bytes_recomputed << recompute * bpt;
  r->ready_at_us =
      now + static_cast<int64_t>(recompute) * prefill_us_flag()->int64_value();
  if (!r->matched.empty()) {
    r->fetch_state.store(kFetchRunning, std::memory_order_release);
    struct FetchArg {
      InferScheduler* self;
      ReqPtr req;
      std::shared_ptr<FetchDrain> drain;
    };
    fetch_drain_->inflight.fetch_add(1, std::memory_order_acq_rel);
    auto* arg = new FetchArg{this, r, fetch_drain_};
    fiber_t fid;
    if (fiber_start(
            &fid,
            [](void* p) {
              std::unique_ptr<FetchArg> a(static_cast<FetchArg*>(p));
              a->self->fetch_blocks(a->req);
              // Retire AFTER the last scheduler touch: once inflight
              // hits zero stop() may free the scheduler, so only the
              // shared drain block is safe past this point.
              std::shared_ptr<FetchDrain> drain = std::move(a->drain);
              a.reset();
              drain->inflight.fetch_sub(1, std::memory_order_acq_rel);
              drain->ev.value.fetch_add(1, std::memory_order_release);
              drain->ev.wake_all();
            },
            arg) != 0) {
      fetch_drain_->inflight.fetch_sub(1, std::memory_order_acq_rel);
      delete arg;
      // No fiber: fall back to recompute for every matched block.
      r->fallback_tokens.store(r->cached_tokens, std::memory_order_release);
      r->fetch_state.store(kFetchDone, std::memory_order_release);
    }
  }
}

// Pulls every matched prefix block (local store or Kv.FetchPrefix RPC),
// whole-or-nothing per block, under the request's cancel scope — a
// mid-flight cancel aborts the in-flight RPC via StartCancel fan-out and
// credits every unpulled byte to deadline_cancel_saved_bytes.
void InferScheduler::fetch_blocks(const ReqPtr& r) {
  set_ambient_cancel(r->scope.get());
  set_ambient_deadline(r->scope->deadline_us);
  const int64_t block_tokens =
      Flag::find("trpc_kv_prefix_block_tokens") != nullptr
          ? Flag::find("trpc_kv_prefix_block_tokens")->int64_value()
          : 128;
  size_t fetched = 0;
  bool aborted = false;
  for (const auto& m : r->matched) {
    if (r->scope->triggered() ||
        r->peer_closed.load(std::memory_order_acquire)) {
      aborted = true;
      break;
    }
    int rc = 0;
    IOBuf out;
    if (!opts_.kv_fetch_addr.empty()) {
      std::lock_guard<std::mutex> g(fetch_ch_mu_);
      if (fetch_ch_ == nullptr) {
        fetch_ch_ = new Channel();
        if (fetch_ch_->Init(opts_.kv_fetch_addr) != 0) {
          delete fetch_ch_;
          fetch_ch_ = nullptr;
          rc = -1;
        }
      }
      if (fetch_ch_ != nullptr) {
        KvPrefixWire w;
        memset(&w, 0, sizeof(w));
        w.hash_hi = m.hash.hi;
        w.hash_lo = m.hash.lo;
        w.generation = m.generation;
        IOBuf req;
        req.append(&w, sizeof(w));
        Controller cntl;
        fetch_ch_->CallMethod(kKvPrefixFetchMethod, req, &out, &cntl);
        rc = cntl.Failed() ? cntl.error_code() : 0;
        if (rc == ECANCELED || cntl.error_code() == kEDeadlineExpired) {
          aborted = true;
          break;
        }
      }
    } else if (opts_.store != nullptr) {
      rc = opts_.store->fetch_prefix(m.hash, m.generation, &out);
    } else {
      rc = -1;
    }
    if (rc != 0) {
      // Non-cancel failure (stale replica, miss): recompute the rest of
      // the chain instead — blocks after a hole are unusable anyway.
      break;
    }
    ++fetched;
    r->fetch_done_bytes.fetch_add(out.size(), std::memory_order_acq_rel);
    infer_vars().prefill_bytes_cached << out.size();
  }
  set_ambient_cancel(nullptr);
  set_ambient_deadline(0);
  if (aborted) {
    const uint64_t saved =
        r->fetch_total_bytes -
        r->fetch_done_bytes.load(std::memory_order_acquire);
    if (saved > 0) {
      deadline_vars().cancel_saved_bytes << static_cast<int64_t>(saved);
    }
    infer_vars().prefix_fetch_aborted << 1;
  }
  const uint32_t unfetched = static_cast<uint32_t>(
      std::min<uint64_t>((r->matched.size() - fetched) * block_tokens,
                         r->cached_tokens));
  if (!aborted && unfetched > 0) {
    r->fallback_tokens.store(unfetched, std::memory_order_release);
  }
  r->fetch_state.store(kFetchDone, std::memory_order_release);
  wake();
}

// Publishes the prompt's UNCACHED blocks into the local store + registry
// so the next identical prompt hits (content-addressed: duplicate bytes
// dedup at kEKvExists).  Bytes derive deterministically from the chain
// key so equal prompts hash equal.
void InferScheduler::publish_blocks(const ReqPtr& r) {
  if (opts_.store == nullptr || !r->publish || r->nprompt == 0) {
    return;
  }
  const int64_t block_tokens =
      Flag::find("trpc_kv_prefix_block_tokens") != nullptr
          ? Flag::find("trpc_kv_prefix_block_tokens")->int64_value()
          : 128;
  Key128 keys[kMaxChainBlocks];
  const size_t nkeys = kv_prefix_chain(r->prompt.data(), r->nprompt,
                                       block_tokens, keys, kMaxChainBlocks);
  const int64_t bpt = bytes_per_token_flag()->int64_value();
  const size_t block_bytes =
      static_cast<size_t>(block_tokens) * static_cast<size_t>(bpt);
  std::vector<uint8_t> bytes(block_bytes);
  const size_t first_uncached = r->matched.size();
  for (size_t d = first_uncached; d < nkeys; ++d) {
    uint64_t seed = keys[d].hi ^ keys[d].lo;
    for (size_t i = 0; i < block_bytes; i += 8) {
      const uint64_t v = splitmix64(seed + i);
      const size_t n = std::min<size_t>(8, block_bytes - i);
      memcpy(bytes.data() + i, &v, n);
    }
    KvPrefixMeta meta;
    const int rc = opts_.store->publish_prefix(
        keys[d], static_cast<uint32_t>(d), bytes.data(), block_bytes,
        r->prompt.data() + d * block_tokens, block_tokens, 0, &meta);
    if (rc == kEKvExists) {
      infer_vars().publish_dedup_total << 1;
      continue;
    }
    if (rc != 0) {
      continue;
    }
    if (opts_.registry != nullptr) {
      snprintf(meta.node, sizeof(meta.node), "%s", opts_.node.c_str());
      uint64_t gen = 0;
      opts_.registry->put_prefix(meta, 0, &gen);
    }
  }
}

// One decode step for one active request.  Returns false when the
// request left the batch (done or cancelled).
bool InferScheduler::step_request(const ReqPtr& r, int64_t now) {
  if (!r->decoding) {
    if (r->fetch_state.load(std::memory_order_acquire) == kFetchRunning) {
      return true;  // prefix pull still in flight
    }
    const uint32_t fallback =
        r->fallback_tokens.exchange(0, std::memory_order_acq_rel);
    if (fallback > 0) {
      // Fetch fell back: pay recompute for the unfetched tokens.
      r->ready_at_us += static_cast<int64_t>(fallback) *
                        prefill_us_flag()->int64_value();
      r->cached_tokens -= std::min(fallback, r->cached_tokens);
      infer_vars().prefill_bytes_recomputed
          << static_cast<int64_t>(fallback) *
                 bytes_per_token_flag()->int64_value();
    }
    if (now < r->ready_at_us) {
      return true;  // still prefilling
    }
    publish_blocks(r);
    r->prompt.clear();
    r->prompt.shrink_to_fit();
    r->decoding = true;
  }
  TokenRecord rec;
  rec.token = splitmix64(r->prompt_hash ^ (r->emitted + 1));
  rec.index = r->emitted;
  rec.flags = (r->emitted + 1 >= r->max_new) ? kTokenEos : 0;
  IOBuf chunk;
  chunk.append(&rec, sizeof(rec));
  if (StreamWrite(r->sid, std::move(chunk)) != 0) {
    finish(r, true);
    return false;
  }
  const int64_t t = monotonic_time_us();
  if (r->emitted == 0) {
    r->first_token_us = t;
    infer_vars().ttft << (t - r->arrival_us);
  } else {
    infer_vars().tpot << (t - r->last_token_us);
  }
  r->last_token_us = t;
  r->emitted += 1;
  infer_vars().tokens_total << 1;
  if (r->emitted >= r->max_new) {
    finish(r, false);
    return false;
  }
  return true;
}

void InferScheduler::release_slot(const std::string& tenant) {
  std::lock_guard<std::mutex> g(mu_);
  auto it = tenant_live_.find(tenant);
  if (it != tenant_live_.end() && --it->second <= 0) {
    tenant_live_.erase(it);
  }
  streams_live_.fetch_sub(1, std::memory_order_acq_rel);
}

void InferScheduler::finish(const ReqPtr& r, bool cancelled) {
  if (cancelled) {
    // Fan out: aborts in-flight prefix pulls (registered under the scope
    // as ambient cancel) and marks the scope for any late registration.
    r->scope->Cancel();
    if (!r->peer_closed.load(std::memory_order_acquire)) {
      TokenRecord rec;
      rec.index = r->emitted;
      rec.flags = kTokenCancelled;
      IOBuf chunk;
      chunk.append(&rec, sizeof(rec));
      StreamWrite(r->sid, std::move(chunk));  // best effort
    }
    infer_vars().cancelled_total << 1;
  } else {
    infer_vars().done_total << 1;
  }
  StreamClose(r->sid);
  release_slot(r->tenant);
}

void InferScheduler::loop() {
  infer_ensure_registered();
  std::vector<ReqPtr> admitted;
  while (!stop_.load(std::memory_order_acquire)) {
    int64_t now = monotonic_time_us();

    // 1) Leave: reap finished/cancelled requests FIRST so their slots are
    //    free for this same step's admission scan.
    for (size_t i = 0; i < active_.size();) {
      const ReqPtr& r = active_[i];
      if (r->peer_closed.load(std::memory_order_acquire) ||
          r->scope->triggered(now)) {
        finish(r, true);
        active_[i] = active_.back();
        active_.pop_back();
        continue;
      }
      ++i;
    }

    // 2) Join: admit from the waiting queue into freed slots.  Waiters
    //    whose budget died or whose client left are finished, not
    //    admitted.
    admitted.clear();
    admit_locked(&admitted);
    for (const ReqPtr& r : admitted) {
      if (r->peer_closed.load(std::memory_order_acquire) ||
          r->scope->triggered(now)) {
        finish(r, true);
        continue;
      }
      begin_prefill(r, now);
      active_.push_back(r);
    }
    active_n_.store(active_.size(), std::memory_order_release);

    if (active_.empty()) {
      const uint32_t snap =
          work_ev_.value.load(std::memory_order_acquire);
      bool empty;
      {
        std::lock_guard<std::mutex> g(mu_);
        empty = waiting_.empty();
      }
      if (empty && !stop_.load(std::memory_order_acquire)) {
        work_ev_.wait(snap, monotonic_time_us() + 50 * 1000);
      }
      continue;
    }

    // 3) One simulated batched forward pass for the whole step.
    const int64_t step_us = step_us_flag()->int64_value();
    if (step_us > 0) {
      fiber_sleep_us(step_us);
    } else {
      fiber_yield();
    }
    now = monotonic_time_us();

    // 4) Emit one token per decode-eligible request.
    for (size_t i = 0; i < active_.size();) {
      if (!step_request(active_[i], now)) {
        active_[i] = active_.back();
        active_.pop_back();
        continue;
      }
      ++i;
    }
    active_n_.store(active_.size(), std::memory_order_release);
    infer_vars().steps_total << 1;
  }

  // Stop: cancel everything still in flight.
  for (const ReqPtr& r : active_) {
    finish(r, true);
  }
  active_.clear();
  active_n_.store(0, std::memory_order_release);
  std::deque<ReqPtr> leftovers;
  {
    std::lock_guard<std::mutex> g(mu_);
    leftovers.swap(waiting_);
    waiting_n_.store(0, std::memory_order_release);
  }
  for (const ReqPtr& r : leftovers) {
    finish(r, true);
  }
}

std::string InferScheduler::dump_json() const {
  InferVars& v = infer_vars();
  double ttft[8] = {0};
  double tpot[8] = {0};
  v.ttft.read_stats(ttft);
  v.tpot.read_stats(tpot);
  std::string out = "{";
  auto num = [&out](const char* k, int64_t val, bool comma = true) {
    out += "\"";
    out += k;
    out += "\":";
    out += std::to_string(val);
    if (comma) {
      out += ",";
    }
  };
  num("active", static_cast<int64_t>(active()));
  num("waiting", static_cast<int64_t>(waiting()));
  num("streams_live", streams_live());
  num("streams_peak", streams_peak());
  num("submitted", v.submitted_total.get_value());
  num("admitted", v.admitted_total.get_value());
  num("done", v.done_total.get_value());
  num("cancelled", v.cancelled_total.get_value());
  num("shed", v.shed_total.get_value());
  num("tokens", v.tokens_total.get_value());
  num("steps", v.steps_total.get_value());
  num("prefill_tokens", v.prefill_tokens_total.get_value());
  num("cached_tokens", v.prefill_cached_tokens_total.get_value());
  num("bytes_recomputed", v.prefill_bytes_recomputed.get_value());
  num("bytes_cached", v.prefill_bytes_cached.get_value());
  num("fetch_aborted", v.prefix_fetch_aborted.get_value());
  num("publish_dedup", v.publish_dedup_total.get_value());
  out += "\"ttft\":{";
  num("count", static_cast<int64_t>(ttft[0]));
  num("p50_us", static_cast<int64_t>(ttft[3]));
  num("p99_us", static_cast<int64_t>(ttft[5]), false);
  out += "},\"tpot\":{";
  num("count", static_cast<int64_t>(tpot[0]));
  num("p50_us", static_cast<int64_t>(tpot[3]));
  num("p99_us", static_cast<int64_t>(tpot[5]), false);
  out += "}}";
  return out;
}

// ---- public surface -------------------------------------------------------

InferScheduler* infer_attach(Server* s, const InferOptions& opts) {
  infer_ensure_registered();
  auto* sched = new InferScheduler(s, opts);
  if (sched->start() != 0) {
    delete sched;
    return nullptr;
  }
  return sched;
}

void infer_stop(InferScheduler* sched) {
  if (sched == nullptr) {
    return;
  }
  sched->stop();
  delete sched;
}

size_t infer_active(InferScheduler* sched) { return sched->active(); }
size_t infer_waiting(InferScheduler* sched) { return sched->waiting(); }
int64_t infer_streams_live(InferScheduler* sched) {
  return sched->streams_live();
}
int64_t infer_streams_peak(InferScheduler* sched) {
  return sched->streams_peak();
}
std::string infer_dump_json(InferScheduler* sched) {
  return sched->dump_json();
}

// ---- flags / vars ---------------------------------------------------------

InferVars::InferVars() {
  submitted_total.expose(
      "infer_submitted_total",
      "Infer.Submit requests received (before admission)");
  admitted_total.expose(
      "infer_admitted_total",
      "requests admitted into the continuous batch (began prefill)");
  shed_total.expose(
      "infer_shed_total",
      "Infer.Submit requests shed with kEOverloaded: batch+queue "
      "saturated, or the tenant was over its weighted share under "
      "pressure (halved while burning its SLO error budget)");
  done_total.expose(
      "infer_done_total",
      "requests that completed generation (final token flagged EOS)");
  cancelled_total.expose(
      "infer_cancelled_total",
      "requests cancelled mid-flight: client disconnect, explicit "
      "stream close, or deadline expiry — slot freed the same step");
  tokens_total.expose(
      "infer_tokens_total",
      "tokens emitted across all requests (one per active request per "
      "decode step)");
  steps_total.expose(
      "infer_steps_total",
      "decode steps executed (each = one simulated batched forward "
      "pass + one token per active request)");
  prefill_tokens_total.expose(
      "infer_prefill_tokens_total",
      "prompt tokens across admitted requests (cached + recomputed)");
  prefill_cached_tokens_total.expose(
      "infer_prefill_cached_tokens_total",
      "prompt tokens whose prefill was skipped via a prefix-cache "
      "chain match (net/kvstore.h) instead of recomputed");
  prefill_bytes_recomputed.expose(
      "infer_prefill_bytes_recomputed_total",
      "simulated KV bytes recomputed during prefill (uncached prompt "
      "tokens x trpc_infer_bytes_per_token); the numerator of the "
      "bytes-recomputed ratio the serving bench reports");
  prefill_bytes_cached.expose(
      "infer_prefill_bytes_cached_total",
      "prefix-cache bytes pulled instead of recomputed (local store "
      "hits and Kv.FetchPrefix pulls that completed)");
  prefix_fetch_aborted.expose(
      "infer_prefix_fetch_aborted_total",
      "prefix-block fetch sequences aborted whole-or-nothing by "
      "cancellation mid-flight (unpulled bytes credited to "
      "deadline_cancel_saved_bytes)");
  publish_dedup_total.expose(
      "infer_prefix_publish_dedup_total",
      "post-prefill prefix publishes folded into an existing live "
      "block by content hash (kEKvExists — another request already "
      "published identical bytes)");
  ttft.expose(
      "infer_ttft",
      "time-to-first-token per request: Infer.Submit arrival to the "
      "first TokenRecord write (µs) — queue wait + prefill");
  tpot.expose(
      "infer_tpot",
      "time-per-output-token: gap between consecutive TokenRecord "
      "writes of one request (µs) — decode-step cadence under load");
}

InferVars& infer_vars() {
  static InferVars* v = new InferVars();
  return *v;
}

void infer_ensure_registered() {
  batch_max_flag();
  queue_max_flag();
  step_us_flag();
  prefill_us_flag();
  max_new_flag();
  bytes_per_token_flag();
  infer_vars();
}

}  // namespace trpc
