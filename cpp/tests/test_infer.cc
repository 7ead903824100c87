// Streamed-inference front door tests (net/infer.h, ISSUE 20):
// end-to-end token streaming with EOS, continuous batching (requests
// join the running batch mid-flight and leave without idling a slot),
// prefix-cache prefill skipping recompute on a repeated prompt, deadline
// expiry cancelling a live stream, client close freeing the slot the
// same step, the chaos case (mid-stream disconnect under svr_delay
// aborts remote prefix fetches whole-or-nothing, credits
// deadline_cancel_saved_bytes, wedges nothing), per-tenant typed
// shedding under overload, flag-bound validation, and the lifecycle
// counters of one request.  Runs under TSan + ASan via tests/test_cpp.py.
#include "net/infer.h"

#include <unistd.h>

#include <atomic>
#include <memory>
#include <mutex>
#include <string>
#include <vector>

#include "base/flags.h"
#include "base/time.h"
#include "fiber/fiber.h"
#include "net/channel.h"
#include "net/controller.h"
#include "net/deadline.h"
#include "net/kvstore.h"
#include "net/server.h"
#include "net/stream.h"
#include "tests/test_util.h"

using namespace trpc;

namespace {

struct Serving {
  Server* srv = nullptr;
  InferScheduler* sched = nullptr;
  int port = 0;

  ~Serving() {
    if (sched != nullptr) {
      infer_stop(sched);
    }
    delete srv;
  }
};

void make_serving(Serving* s, const InferOptions& opts = InferOptions{}) {
  s->srv = new Server();
  s->sched = infer_attach(s->srv, opts);
  EXPECT(s->sched != nullptr);
  EXPECT_EQ(s->srv->Start(0), 0);
  s->port = s->srv->port();
}

std::string addr_of(const Serving& s) {
  return "127.0.0.1:" + std::to_string(s.port);
}

// Client side of one completion: offers the token stream, submits, and
// collects TokenRecords as the scheduler pushes them.
struct TokenClient {
  struct State {
    std::mutex mu;
    std::vector<TokenRecord> recs;
    std::atomic<int> nrecs{0};
    std::atomic<bool> closed{false};
  };
  std::shared_ptr<State> st = std::make_shared<State>();
  StreamId sid = 0;
  InferSubmitReply reply;
  int error_code = 0;
  bool ok = false;

  std::vector<TokenRecord> records() {
    std::lock_guard<std::mutex> g(st->mu);
    return st->recs;
  }
  bool wait_closed(int64_t timeout_ms = 5000) {
    const int64_t deadline = monotonic_time_us() + timeout_ms * 1000;
    while (!st->closed.load() && monotonic_time_us() < deadline) {
      usleep(5000);
    }
    return st->closed.load();
  }
  bool wait_records(int n, int64_t timeout_ms = 5000) {
    const int64_t deadline = monotonic_time_us() + timeout_ms * 1000;
    while (st->nrecs.load() < n && monotonic_time_us() < deadline) {
      usleep(5000);
    }
    return st->nrecs.load() >= n;
  }
};

TokenClient submit(Channel* ch, const std::vector<uint64_t>& prompt,
                   uint32_t max_new, int64_t timeout_ms = 30000,
                   const std::string& tenant = "", uint32_t flags = 0,
                   int64_t window_bytes = 0) {
  TokenClient c;
  auto st = c.st;
  Controller cntl;
  if (timeout_ms > 0) {
    cntl.set_timeout_ms(timeout_ms);
  }
  if (!tenant.empty()) {
    cntl.set_qos(tenant, 0);
  }
  StreamOptions opts;
  if (window_bytes > 0) {
    opts.window_bytes = window_bytes;
  }
  opts.on_message = [st](StreamId, IOBuf&& chunk) {
    TokenRecord rec;
    if (chunk.size() >= sizeof(rec)) {
      chunk.copy_to(&rec, sizeof(rec));
      std::lock_guard<std::mutex> g(st->mu);
      st->recs.push_back(rec);
    }
    st->nrecs.fetch_add(1);
  };
  opts.on_closed = [st](StreamId) { st->closed.store(true); };
  EXPECT_EQ(StreamCreate(&c.sid, &cntl, opts), 0);
  InferSubmitWire w;
  w.magic = kInferMagic;
  w.flags = flags;
  w.max_new_tokens = max_new;
  w.n_prompt_tokens = static_cast<uint32_t>(prompt.size());
  IOBuf req, resp;
  req.append(&w, sizeof(w));
  if (!prompt.empty()) {
    req.append(prompt.data(), prompt.size() * sizeof(uint64_t));
  }
  ch->CallMethod("Infer.Submit", req, &resp, &cntl);
  if (cntl.Failed()) {
    c.error_code = cntl.error_code();
    return c;
  }
  EXPECT_EQ(resp.size(), sizeof(InferSubmitReply));
  resp.copy_to(&c.reply, sizeof(c.reply));
  c.ok = true;
  return c;
}

std::vector<uint64_t> make_prompt(uint64_t seed, size_t n) {
  std::vector<uint64_t> p(n);
  for (size_t i = 0; i < n; ++i) {
    p[i] = seed * 100003 + i + 1;
  }
  return p;
}

void set_flag(const char* name, const std::string& value) {
  EXPECT_EQ(Flag::set(name, value), 0);
}

// Every test pins the flags it depends on (flags are process-global and
// earlier cases change them).
void reset_infer_flags() {
  infer_ensure_registered();
  kv_ensure_registered();  // trpc_kv_prefix_block_tokens lives there
  set_flag("trpc_infer_batch_max", "256");
  set_flag("trpc_infer_queue_max", "200000");
  set_flag("trpc_infer_step_us", "1000");
  set_flag("trpc_infer_prefill_us_per_token", "0");
  set_flag("trpc_infer_max_new_tokens", "256");
  set_flag("trpc_infer_bytes_per_token", "64");
  set_flag("trpc_kv_prefix_block_tokens", "8");
}

int64_t wait_live_zero(InferScheduler* sched, int64_t timeout_ms = 5000) {
  const int64_t deadline = monotonic_time_us() + timeout_ms * 1000;
  while (infer_streams_live(sched) > 0 && monotonic_time_us() < deadline) {
    usleep(5000);
  }
  return infer_streams_live(sched);
}

}  // namespace

TEST_CASE(infer_end_to_end_tokens_and_eos) {
  reset_infer_flags();
  Serving s;
  make_serving(&s);
  Channel ch;
  EXPECT_EQ(ch.Init(addr_of(s)), 0);

  TokenClient c = submit(&ch, make_prompt(1, 4), 8);
  EXPECT(c.ok);
  EXPECT(c.reply.request_id != 0);
  EXPECT_EQ(c.reply.cached_tokens, 0u);  // no prefix cache attached
  EXPECT(c.wait_records(8));
  EXPECT(c.wait_closed());
  auto recs = c.records();
  EXPECT_EQ(recs.size(), 8u);
  for (size_t i = 0; i < recs.size(); ++i) {
    EXPECT_EQ(recs[i].index, i);  // strictly ordered, no gaps
  }
  EXPECT_EQ(recs.back().flags, kTokenEos);
  // Same prompt generates the same tokens (deterministic decode sim).
  TokenClient c2 = submit(&ch, make_prompt(1, 4), 8);
  EXPECT(c2.ok);
  EXPECT(c2.wait_closed());
  auto recs2 = c2.records();
  EXPECT_EQ(recs2.size(), 8u);
  EXPECT_EQ(recs2[0].token, recs[0].token);
  EXPECT_EQ(wait_live_zero(s.sched), 0);
}

TEST_CASE(infer_continuous_batching_join_and_leave) {
  reset_infer_flags();
  set_flag("trpc_infer_batch_max", "2");
  set_flag("trpc_infer_step_us", "5000");
  Serving s;
  make_serving(&s);
  Channel ch;
  EXPECT_EQ(ch.Init(addr_of(s)), 0);

  // A occupies a slot for ~1s; B finishes in ~25ms and frees its slot;
  // C (queued behind the full batch) must JOIN the running batch the
  // step B leaves and finish while A is still streaming.
  TokenClient a = submit(&ch, make_prompt(2, 4), 200);
  TokenClient b = submit(&ch, make_prompt(3, 4), 5);
  TokenClient c = submit(&ch, make_prompt(4, 4), 5);
  EXPECT(a.ok);
  EXPECT(b.ok);
  EXPECT(c.ok);
  EXPECT(b.wait_closed());
  EXPECT(c.wait_closed());
  EXPECT_EQ(c.records().back().flags, kTokenEos);
  // A is mid-generation: its stream is open and far from done — C's
  // completion happened inside A's window, proving mid-flight join.
  EXPECT(!a.st->closed.load());
  EXPECT(a.st->nrecs.load() < 200);
  StreamClose(a.sid);  // client walks away; slot must free
  EXPECT(a.wait_closed());
  EXPECT_EQ(wait_live_zero(s.sched), 0);
}

TEST_CASE(infer_prefix_cache_skips_recompute) {
  reset_infer_flags();
  set_flag("trpc_infer_prefill_us_per_token", "200");
  static KvStore store;
  static KvRegistry registry;
  InferOptions opts;
  opts.store = &store;
  opts.registry = &registry;
  opts.node = "serve0";
  Serving s;
  make_serving(&s, opts);
  Channel ch;
  EXPECT_EQ(ch.Init(addr_of(s)), 0);

  // 32 tokens = 4 full blocks at block_tokens=8.
  const auto prompt = make_prompt(5, 32);
  const int64_t recomputed0 =
      infer_vars().prefill_bytes_recomputed.get_value();
  const int64_t cached_bytes0 =
      infer_vars().prefill_bytes_cached.get_value();

  // Cold: nothing cached, every byte recomputed, blocks published.
  TokenClient c1 = submit(&ch, prompt, 4);
  EXPECT(c1.ok);
  EXPECT_EQ(c1.reply.cached_tokens, 0u);
  EXPECT(c1.wait_closed());
  EXPECT_EQ(registry.prefix_count(), 4u);
  const int64_t recomputed1 =
      infer_vars().prefill_bytes_recomputed.get_value();
  EXPECT_EQ(recomputed1 - recomputed0, 32 * 64);

  // Warm: the whole prompt chain matches; prefill pulls bytes from the
  // store instead of recomputing ANY of them.
  TokenClient c2 = submit(&ch, prompt, 4);
  EXPECT(c2.ok);
  EXPECT_EQ(c2.reply.cached_tokens, 32u);
  EXPECT_EQ(c2.reply.block_tokens, 8u);
  EXPECT(c2.wait_closed());
  EXPECT_EQ(infer_vars().prefill_bytes_recomputed.get_value(), recomputed1);
  EXPECT_EQ(infer_vars().prefill_bytes_cached.get_value() - cached_bytes0,
            4 * 8 * 64);  // 4 blocks x block_tokens x bytes_per_token
  // Deterministic decode: the cached path emits the same tokens.
  EXPECT_EQ(c1.records()[0].token, c2.records()[0].token);
  EXPECT_EQ(wait_live_zero(s.sched), 0);
  store.clear();
  registry.clear();
}

TEST_CASE(infer_deadline_expiry_cancels_midstream) {
  reset_infer_flags();
  set_flag("trpc_infer_step_us", "20000");  // 20ms/token: 256 tokens ≈ 5s
  Serving s;
  make_serving(&s);
  Channel ch;
  EXPECT_EQ(ch.Init(addr_of(s)), 0);

  const int64_t cancelled0 = infer_vars().cancelled_total.get_value();
  // The submit call's 400ms budget becomes the request's end-to-end
  // deadline; generation needs ~5s, so the scheduler must reap it.
  TokenClient c = submit(&ch, make_prompt(6, 4), 256, /*timeout_ms=*/400);
  EXPECT(c.ok);
  EXPECT(c.wait_closed(10000));
  auto recs = c.records();
  EXPECT(!recs.empty());
  EXPECT(recs.size() < 256u);
  EXPECT_EQ(recs.back().flags, kTokenCancelled);
  EXPECT(infer_vars().cancelled_total.get_value() > cancelled0);
  EXPECT_EQ(wait_live_zero(s.sched), 0);
}

TEST_CASE(infer_client_close_frees_slot_for_waiter) {
  reset_infer_flags();
  set_flag("trpc_infer_batch_max", "1");
  set_flag("trpc_infer_step_us", "5000");
  Serving s;
  make_serving(&s);
  Channel ch;
  EXPECT_EQ(ch.Init(addr_of(s)), 0);

  TokenClient hog = submit(&ch, make_prompt(7, 4), 200);
  TokenClient waiter = submit(&ch, make_prompt(8, 4), 3);
  EXPECT(hog.ok);
  EXPECT(waiter.ok);
  EXPECT(hog.wait_records(1));
  EXPECT(!waiter.st->closed.load());
  // The only slot is held; closing the hog's stream client-side must
  // free it and admit the waiter the same step.
  StreamClose(hog.sid);
  EXPECT(waiter.wait_closed());
  EXPECT_EQ(waiter.records().back().flags, kTokenEos);
  EXPECT_EQ(wait_live_zero(s.sched), 0);
}

// The ISSUE 20 chaos case: a client disconnect mid-prefill, while the
// scheduler is pulling this request's matched prefix blocks from a
// DELAYED remote kv node, must abort the fetch sequence whole-or-nothing
// — unpulled bytes credited to deadline_cancel_saved_bytes, the aborted
// counter bumped, no stream or slot wedged, and the slot reusable.
TEST_CASE(infer_chaos_disconnect_aborts_prefix_fetch) {
  reset_infer_flags();

  // kv node: serves Kv.FetchPrefix out of the process store, with every
  // request delayed 100ms (fault plane svr_delay).
  Server* kvsrv = new Server();
  EXPECT_EQ(kv_attach_store(kvsrv), 0);
  EXPECT_EQ(kvsrv->Start(0), 0);
  EXPECT_EQ(kvsrv->SetFaults("svr_delay=1:100"), 0);
  const std::string kv_addr =
      "127.0.0.1:" + std::to_string(kvsrv->port());

  // Pre-populate: the prompt's 4 chain blocks live on the kv node.
  const auto prompt = make_prompt(9, 32);
  static KvRegistry registry;
  Key128 keys[8];
  const size_t nkeys = kv_prefix_chain(prompt.data(), prompt.size(), 8,
                                       keys, 8);
  EXPECT_EQ(nkeys, 4u);
  std::vector<uint8_t> block(8 * 64, 0xab);
  for (size_t d = 0; d < nkeys; ++d) {
    KvPrefixMeta meta;
    EXPECT_EQ(kv_store().publish_prefix(keys[d], static_cast<uint32_t>(d),
                                        block.data(), block.size(),
                                        prompt.data() + d * 8, 8, 60000,
                                        &meta),
              0);
    snprintf(meta.node, sizeof(meta.node), "kvnode");
    uint64_t gen = 0;
    EXPECT_EQ(registry.put_prefix(meta, 60000, &gen), 0);
  }

  // Serving node: matches against the registry, pulls over the wire from
  // the delayed kv node (no local store — every block is a remote RPC).
  InferOptions opts;
  opts.registry = &registry;
  opts.kv_fetch_addr = kv_addr;
  Serving s;
  make_serving(&s, opts);
  Channel ch;
  EXPECT_EQ(ch.Init(addr_of(s)), 0);

  const int64_t saved0 = deadline_vars().cancel_saved_bytes.get_value();
  const int64_t aborted0 = infer_vars().prefix_fetch_aborted.get_value();
  const int64_t cached0 = infer_vars().prefill_bytes_cached.get_value();

  TokenClient c = submit(&ch, prompt, 4);
  EXPECT(c.ok);
  EXPECT_EQ(c.reply.cached_tokens, 32u);
  // 4 blocks x 100ms delay each: disconnect ~150ms in, mid-chain.
  usleep(150 * 1000);
  StreamClose(c.sid);

  // The scheduler must reap the request and abort the in-flight pull.
  EXPECT_EQ(wait_live_zero(s.sched, 10000), 0);
  const int64_t deadline = monotonic_time_us() + 5 * 1000 * 1000;
  while (infer_vars().prefix_fetch_aborted.get_value() == aborted0 &&
         monotonic_time_us() < deadline) {
    usleep(5000);
  }
  EXPECT(infer_vars().prefix_fetch_aborted.get_value() > aborted0);
  EXPECT(deadline_vars().cancel_saved_bytes.get_value() > saved0);
  // Whole-or-nothing: whatever DID land is an integral number of
  // blocks, and at least one block was still unpulled when cancelled.
  const int64_t pulled =
      infer_vars().prefill_bytes_cached.get_value() - cached0;
  EXPECT_EQ(pulled % (8 * 64), 0);
  EXPECT(pulled < static_cast<int64_t>(nkeys) * 8 * 64);

  // Nothing wedged: the freed slot serves a fresh (uncached) request.
  TokenClient c2 = submit(&ch, make_prompt(10, 4), 3);
  EXPECT(c2.ok);
  EXPECT(c2.wait_closed());
  EXPECT_EQ(c2.records().back().flags, kTokenEos);
  EXPECT_EQ(wait_live_zero(s.sched), 0);

  registry.clear();
  kv_store().clear();
  delete kvsrv;
}

TEST_CASE(infer_overload_sheds_typed_per_tenant) {
  reset_infer_flags();
  set_flag("trpc_infer_batch_max", "2");
  set_flag("trpc_infer_queue_max", "6");  // cap = 8, pressure at live >= 4
  set_flag("trpc_infer_step_us", "5000");
  Serving s;
  make_serving(&s);
  Channel ch;
  EXPECT_EQ(ch.Init(addr_of(s)), 0);

  const int64_t shed0 = infer_vars().shed_total.get_value();
  std::vector<TokenClient> held;
  for (int i = 0; i < 4; ++i) {
    held.push_back(submit(&ch, make_prompt(20 + i, 4), 200, 30000, "hog"));
    EXPECT(held.back().ok);
  }
  held.push_back(submit(&ch, make_prompt(30, 4), 200, 30000, "victim"));
  EXPECT(held.back().ok);

  // Under pressure (live=5 of cap 8), "hog" holds 4 of a fair share of
  // 4 — its next submit sheds TYPED (kEOverloaded), not a timeout...
  TokenClient hog_extra =
      submit(&ch, make_prompt(31, 4), 200, 30000, "hog");
  EXPECT(!hog_extra.ok);
  EXPECT_EQ(hog_extra.error_code, kEOverloaded);
  EXPECT(infer_vars().shed_total.get_value() > shed0);
  // ...while the in-share tenant still admits at the same instant.
  TokenClient victim2 =
      submit(&ch, make_prompt(32, 4), 200, 30000, "victim");
  EXPECT(victim2.ok);
  held.push_back(victim2);

  for (auto& c : held) {
    StreamClose(c.sid);
  }
  EXPECT_EQ(wait_live_zero(s.sched, 10000), 0);
}

// A client advertising a stream window that cannot fit even ONE
// TokenRecord must be rejected at submit: admitting it unclamped would
// park the shared decode fiber on the first StreamWrite, stalling every
// tenant's requests (and the deadline reaper that runs in the same
// fiber).
TEST_CASE(infer_tiny_window_rejected_not_parked) {
  reset_infer_flags();
  Serving s;
  make_serving(&s);
  Channel ch;
  EXPECT_EQ(ch.Init(addr_of(s)), 0);

  TokenClient tiny = submit(&ch, make_prompt(50, 4), 8, 30000, "", 0,
                            /*window_bytes=*/8);
  EXPECT(!tiny.ok);
  EXPECT_EQ(tiny.error_code, EINVAL);
  // The admission slot reserved for it was released, not leaked.
  EXPECT_EQ(wait_live_zero(s.sched), 0);

  // The decode loop never parked: a sane request still completes.
  TokenClient c = submit(&ch, make_prompt(51, 4), 4);
  EXPECT(c.ok);
  EXPECT(c.wait_closed());
  EXPECT_EQ(c.records().back().flags, kTokenEos);
  EXPECT_EQ(wait_live_zero(s.sched), 0);
}

// Shutdown with a prefix fetch mid-RPC: infer_stop must cancel the
// request, WAIT for the detached fetch fiber to retire, and only then
// free the fetch channel and scheduler — the fiber holds a raw
// scheduler pointer, so ASan/TSan catch any early free here.
TEST_CASE(infer_stop_drains_inflight_prefix_fetch) {
  reset_infer_flags();
  Server* kvsrv = new Server();
  EXPECT_EQ(kv_attach_store(kvsrv), 0);
  EXPECT_EQ(kvsrv->Start(0), 0);
  EXPECT_EQ(kvsrv->SetFaults("svr_delay=1:100"), 0);
  const std::string kv_addr =
      "127.0.0.1:" + std::to_string(kvsrv->port());

  const auto prompt = make_prompt(52, 32);
  static KvRegistry registry;
  Key128 keys[8];
  const size_t nkeys = kv_prefix_chain(prompt.data(), prompt.size(), 8,
                                       keys, 8);
  EXPECT_EQ(nkeys, 4u);
  std::vector<uint8_t> block(8 * 64, 0xcd);
  for (size_t d = 0; d < nkeys; ++d) {
    KvPrefixMeta meta;
    EXPECT_EQ(kv_store().publish_prefix(keys[d], static_cast<uint32_t>(d),
                                        block.data(), block.size(),
                                        prompt.data() + d * 8, 8, 60000,
                                        &meta),
              0);
    snprintf(meta.node, sizeof(meta.node), "kvnode");
    uint64_t gen = 0;
    EXPECT_EQ(registry.put_prefix(meta, 60000, &gen), 0);
  }

  InferOptions opts;
  opts.registry = &registry;
  opts.kv_fetch_addr = kv_addr;
  auto* srv = new Server();
  InferScheduler* sched = infer_attach(srv, opts);
  EXPECT(sched != nullptr);
  EXPECT_EQ(srv->Start(0), 0);
  Channel ch;
  EXPECT_EQ(ch.Init("127.0.0.1:" + std::to_string(srv->port())), 0);

  TokenClient c = submit(&ch, prompt, 4);
  EXPECT(c.ok);
  EXPECT_EQ(c.reply.cached_tokens, 32u);
  // 4 blocks x 100ms delay each: stop ~120ms in, fetch mid-chain.
  usleep(120 * 1000);
  infer_stop(sched);
  delete srv;
  EXPECT(c.wait_closed());

  registry.clear();
  kv_store().clear();
  delete kvsrv;
}

TEST_CASE(infer_flag_bounds_validated) {
  infer_ensure_registered();
  EXPECT(Flag::set("trpc_infer_batch_max", "0") != 0);
  EXPECT(Flag::set("trpc_infer_batch_max", "70000") != 0);
  EXPECT_EQ(Flag::set("trpc_infer_batch_max", "16"), 0);
  EXPECT(Flag::set("trpc_infer_step_us", "-1") != 0);
  EXPECT(Flag::set("trpc_infer_queue_max", "2000000") != 0);
  EXPECT(Flag::set("trpc_infer_max_new_tokens", "0") != 0);
  EXPECT(Flag::set("trpc_infer_bytes_per_token", "0") != 0);
  EXPECT(Flag::set("trpc_infer_prefill_us_per_token", "1000001") != 0);
  reset_infer_flags();
}

TEST_CASE(infer_eos_request_moves_lifecycle_counters) {
  reset_infer_flags();
  Serving s;
  make_serving(&s);
  Channel ch;
  EXPECT_EQ(ch.Init(addr_of(s)), 0);
  const int64_t admitted0 = infer_vars().admitted_total.get_value();
  const int64_t tokens0 = infer_vars().tokens_total.get_value();
  const int64_t done0 = infer_vars().done_total.get_value();
  const int64_t cancelled0 = infer_vars().cancelled_total.get_value();

  TokenClient c = submit(&ch, make_prompt(40, 4), 4);
  EXPECT(c.ok);
  EXPECT(c.wait_closed());
  EXPECT_EQ(wait_live_zero(s.sched), 0);

  // One admission, four tokens, one EOS finish, no cancel.
  EXPECT_EQ(infer_vars().admitted_total.get_value() - admitted0, 1);
  EXPECT_EQ(infer_vars().tokens_total.get_value() - tokens0, 4);
  EXPECT_EQ(infer_vars().done_total.get_value() - done0, 1);
  EXPECT_EQ(infer_vars().cancelled_total.get_value(), cancelled0);
}

TEST_MAIN
