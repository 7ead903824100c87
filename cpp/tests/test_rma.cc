// One-sided RMA plane tests (net/rma.h): region registration lifecycle,
// use-after-unregister rejection, shm multi-rail 64MB integrity, ici
// parallel-rail integrity, direct-to-caller-region response landing,
// cancel-mid-put buffer quiescence, sub-threshold bypass byte-identity,
// window-full fallback to the striped copy path, and chunk-level fault
// injection (drop / trunc / corrupt) asserting whole-or-nothing failure —
// a registered buffer is never observable as complete with partial bytes.
#include <unistd.h>

#include <cstring>
#include <string>

#include "base/flags.h"
#include "base/proc.h"
#include "base/time.h"
#include "fiber/fiber.h"
#include "net/channel.h"
#include "net/fault.h"
#include "net/hotpath_stats.h"
#include "net/protocol.h"
#include "net/rma.h"
#include "net/server.h"
#include "net/stripe.h"
#include "tests/test_util.h"

using namespace trpc;

namespace {

Server* g_server = nullptr;
int g_port = 0;

void start_once() {
  if (g_server != nullptr) {
    return;
  }
  g_server = new Server();
  g_server->RegisterMethod("Echo.Echo", [](Controller*, const IOBuf& req,
                                           IOBuf* resp, Closure done) {
    resp->append(req);  // zero-copy ref share
    done();
  });
  EXPECT_EQ(g_server->Start(0), 0);
  g_port = g_server->port();
}

std::string addr() { return "127.0.0.1:" + std::to_string(g_port); }

// Patterned payload: a mis-offset one-sided write changes bytes, unlike
// a constant fill.
std::string pattern(size_t n, uint32_t salt = 0) {
  std::string s(n, 0);
  for (size_t i = 0; i < n; ++i) {
    s[i] = static_cast<char>(((i + salt) * 2654435761u) >> 13);
  }
  return s;
}

struct FaultGuard {
  ~FaultGuard() { FaultActor::global().set(""); }
};

struct FlagGuard {
  std::string name, old_value;
  FlagGuard(const std::string& n, const std::string& v) : name(n) {
    old_value = Flag::find(n)->value_string();
    EXPECT_EQ(Flag::set(n, v), 0);
  }
  ~FlagGuard() { Flag::set(name, old_value); }
};

struct RmaDelta {
  int64_t tx_msgs, rx_msgs, tx_bytes, rejected, window_full;
  RmaDelta() { reset(); }
  void reset() {
    HotPathVars& v = hotpath_vars();
    tx_msgs = v.rma_tx_msgs.get_value();
    rx_msgs = v.rma_rx_msgs.get_value();
    tx_bytes = v.rma_tx_bytes.get_value();
    rejected = v.rma_rejected.get_value();
    window_full = v.rma_window_full.get_value();
  }
  int64_t d_tx_msgs() const {
    return hotpath_vars().rma_tx_msgs.get_value() - tx_msgs;
  }
  int64_t d_rx_msgs() const {
    return hotpath_vars().rma_rx_msgs.get_value() - rx_msgs;
  }
  int64_t d_tx_bytes() const {
    return hotpath_vars().rma_tx_bytes.get_value() - tx_bytes;
  }
  int64_t d_rejected() const {
    return hotpath_vars().rma_rejected.get_value() - rejected;
  }
  int64_t d_window_full() const {
    return hotpath_vars().rma_window_full.get_value() - window_full;
  }
};

}  // namespace

TEST_CASE(rma_registration_lifecycle) {
  const size_t n0 = rma_region_count();
  uint64_t rkey = 0;
  void* buf = rma_alloc(1 << 20, &rkey);
  EXPECT(buf != nullptr);
  EXPECT(rkey != 0);
  EXPECT_EQ(rma_region_count(), n0 + 1);
  // The data area is usable memory.
  memset(buf, 0x5a, 1 << 20);
  uint64_t found_rkey = 0, off = 0;
  EXPECT(rma_exportable(buf, 1 << 20, &found_rkey, &off));
  EXPECT_EQ(found_rkey, rkey);
  EXPECT_EQ(off, 0u);
  // Interior ranges resolve with their offset.
  EXPECT(rma_exportable(static_cast<char*>(buf) + 4096, 1024, &found_rkey,
                        &off));
  EXPECT_EQ(off, 4096u);
  rma_free(buf);
  EXPECT_EQ(rma_region_count(), n0);
  EXPECT(!rma_exportable(buf, 1, &found_rkey, &off));

  // Local pins: registered, never exportable, unregister exactly once.
  char local[256];
  const uint64_t pin = rma_reg(local, sizeof(local));
  EXPECT(pin != 0);
  EXPECT(!rma_exportable(local, sizeof(local), &found_rkey, &off));
  EXPECT_EQ(rma_unreg(pin), 0);
  EXPECT_EQ(rma_unreg(pin), -1);
}

TEST_CASE(rma_shm_multi_rail_64mb_integrity) {
  start_once();
  FlagGuard rails("trpc_shm_rails", "8");
  Channel ch;
  Channel::Options opts;
  opts.use_shm = true;
  opts.timeout_ms = 60000;
  EXPECT_EQ(ch.Init(addr(), &opts), 0);
  const std::string big = pattern(64 << 20);
  RmaDelta d;
  Controller cntl;
  cntl.set_enable_checksum(true);  // per-chunk CRCs in the transfer hdr
  IOBuf req, resp;
  req.append(big);
  ch.CallMethod("Echo.Echo", req, &resp, &cntl);
  EXPECT(!cntl.Failed());
  EXPECT_EQ(resp.size(), big.size());
  EXPECT(resp.equals(big.data(), big.size()));
  // Request + response both rode the one-sided path, not frames.
  EXPECT(d.d_tx_msgs() >= 2);
  EXPECT(d.d_rx_msgs() >= 2);
  EXPECT(d.d_tx_bytes() >= 2ll * (64 << 20));
  EXPECT_EQ(d.d_rejected(), 0);
  EXPECT_EQ(stripe_pending_reassemblies(), 0u);
}

TEST_CASE(rma_ici_parallel_rail_integrity) {
  start_once();
  FlagGuard rails("trpc_ici_rails", "4");
  Channel ch;
  Channel::Options opts;
  opts.use_ici = true;
  opts.timeout_ms = 60000;
  EXPECT_EQ(ch.Init(addr(), &opts), 0);
  // Ordinary (non-staging) payload: descriptors would copy it through
  // the ring DMA serially; the rma path writes it with parallel rails.
  const std::string big = pattern(24 << 20, 7);
  RmaDelta d;
  for (int i = 0; i < 2; ++i) {
    Controller cntl;
    IOBuf req, resp;
    req.append(big);
    ch.CallMethod("Echo.Echo", req, &resp, &cntl);
    EXPECT(!cntl.Failed());
    EXPECT_EQ(resp.size(), big.size());
    EXPECT(resp.equals(big.data(), big.size()));
  }
  EXPECT(d.d_tx_msgs() >= 4);  // 2 calls x (request + response)
  EXPECT_EQ(d.d_rejected(), 0);
}

TEST_CASE(rma_direct_response_lands_in_caller_region) {
  start_once();
  Channel ch;
  Channel::Options opts;
  opts.use_shm = true;
  opts.timeout_ms = 60000;
  EXPECT_EQ(ch.Init(addr(), &opts), 0);
  const size_t cap = 8 << 20;
  uint64_t rkey = 0;
  void* land = rma_alloc(cap, &rkey);
  EXPECT(land != nullptr);
  const std::string big = pattern(6 << 20, 3);
  RmaDelta d;
  Controller cntl;
  cntl.call().land_buf = land;  // the batch plane's registration path
  cntl.call().land_cap = cap;
  IOBuf req, resp;
  req.append(big);
  ch.CallMethod("Echo.Echo", req, &resp, &cntl);
  EXPECT(!cntl.Failed());
  EXPECT_EQ(resp.size(), big.size());
  EXPECT(resp.equals(big.data(), big.size()));
  // The response payload IS the caller's registered buffer (in-place
  // view, zero receiver-side copies), and its bytes match.
  EXPECT(resp.block_count() >= 1);
  EXPECT(resp.ref_at(0).block->data + resp.ref_at(0).offset ==
         static_cast<char*>(land));
  EXPECT_EQ(memcmp(land, big.data(), big.size()), 0);
  EXPECT(d.d_tx_msgs() >= 2);
  resp.clear();  // drop the view before the region goes away
  rma_free(land);
}

TEST_CASE(rma_use_after_unregister_rejected) {
  start_once();
  // A control frame naming a landing that is no longer bound (the
  // caller unregistered / the region was freed) must drop whole.
  const size_t cap = 4 << 20;
  uint64_t rkey = 0;
  void* land = rma_alloc(cap, &rkey);
  EXPECT(land != nullptr);
  const uint64_t cid = 0x5eed5eed12345678ull;
  stripe_register_landing(cid, land, cap);
  stripe_unregister_landing(cid);  // caller cancelled: bind must be gone
  RmaDelta d;
  InputMessage msg;
  msg.meta.type = RpcMeta::kResponse;
  msg.meta.correlation_id = cid;
  msg.meta.rma_rkey = rkey;
  msg.meta.rma_off = kRmaDirectOff;
  msg.meta.rma_len = 1 << 20;
  msg.meta.rma_chunk = 1 << 20;
  EXPECT(!rma_resolve(&msg, nullptr));
  EXPECT_EQ(d.d_rejected(), 1);
  // Freed region + still-bound cid is equally rejected (use after free).
  stripe_register_landing(cid, land, cap);
  rma_free(land);
  InputMessage msg2;
  msg2.meta.type = RpcMeta::kResponse;
  msg2.meta.correlation_id = cid;
  msg2.meta.rma_rkey = rkey;
  msg2.meta.rma_off = kRmaDirectOff;
  msg2.meta.rma_len = 1 << 20;
  msg2.meta.rma_chunk = 1 << 20;
  EXPECT(!rma_resolve(&msg2, nullptr));
  EXPECT_EQ(d.d_rejected(), 2);
  stripe_unregister_landing(cid);
  // A window-path control frame with no socket/session context is
  // rejected too (never resolves arbitrary local regions).
  InputMessage msg3;
  msg3.meta.type = RpcMeta::kRequest;
  msg3.meta.correlation_id = 1;
  msg3.meta.rma_rkey = rkey;
  msg3.meta.rma_off = 0;
  msg3.meta.rma_len = 4096;
  msg3.meta.rma_chunk = 4096;
  EXPECT(!rma_resolve(&msg3, nullptr));
  EXPECT_EQ(d.d_rejected(), 3);
}

TEST_CASE(rma_cancel_mid_put_buffer_quiescent) {
  start_once();
  Channel ch;
  Channel::Options opts;
  opts.use_shm = true;
  opts.timeout_ms = 60000;
  EXPECT_EQ(ch.Init(addr(), &opts), 0);
  {
    // Warm the ring + window so the failing call below is established.
    Controller cntl;
    IOBuf req, resp;
    req.append("warm");
    ch.CallMethod("Echo.Echo", req, &resp, &cntl);
    EXPECT(!cntl.Failed());
  }
  const size_t cap = 8 << 20;
  uint64_t rkey = 0;
  void* land = rma_alloc(cap, &rkey);
  EXPECT(land != nullptr);
  memset(land, 0x77, cap);
  // Server answers late; the call times out first — the client-side
  // completion unregisters the landing BEFORE the response's one-sided
  // put could be resolved against it.  Deadline stamping OFF for this
  // scenario: with the deadline plane (ISSUE 15) a stamped budget makes
  // the server SHED the delayed request instead of producing the late
  // response — this test models the peer that never learned of the
  // abandonment (old client / wire stamping disabled), where the
  // landing-unbind defense is the only line left.
  FlagGuard wire("trpc_deadline_wire", "false");
  EXPECT_EQ(g_server->SetFaults("svr_delay=1:800"), 0);
  RmaDelta d;
  {
    Controller cntl;
    cntl.set_timeout_ms(150);
    cntl.call().land_buf = land;
    cntl.call().land_cap = cap;
    IOBuf req, resp;
    req.append(pattern(4 << 20, 9));
    ch.CallMethod("Echo.Echo", req, &resp, &cntl);
    EXPECT(cntl.Failed());  // timed out; landing unregistered on return
  }
  g_server->SetFaults("");
  // The late response's control frame must be REJECTED (unbound cid),
  // not land in a buffer the caller already considers recycled.
  const int64_t deadline = monotonic_time_us() + 5 * 1000 * 1000;
  while (d.d_rejected() == 0 && monotonic_time_us() < deadline) {
    fiber_sleep_us(20 * 1000);
  }
  EXPECT(d.d_rejected() >= 1);
  rma_free(land);
  // The channel still works after the rejected transfer.
  Controller cntl;
  IOBuf req, resp;
  req.append("after");
  ch.CallMethod("Echo.Echo", req, &resp, &cntl);
  EXPECT(!cntl.Failed());
}

TEST_CASE(rma_sub_threshold_bypass_byte_identity) {
  start_once();
  Channel ch;
  Channel::Options opts;
  opts.use_shm = true;
  opts.timeout_ms = 15000;
  EXPECT_EQ(ch.Init(addr(), &opts), 0);
  RmaDelta d;
  for (int i = 0; i < 32; ++i) {
    Controller cntl;
    IOBuf req, resp;
    req.append(pattern(1024, i));
    ch.CallMethod("Echo.Echo", req, &resp, &cntl);
    EXPECT(!cntl.Failed());
    EXPECT_EQ(resp.size(), 1024u);
  }
  // Sub-threshold traffic leaves the entire rma plane untouched — the
  // proof small RPCs pay nothing for it.
  EXPECT_EQ(d.d_tx_msgs(), 0);
  EXPECT_EQ(d.d_rx_msgs(), 0);
  EXPECT_EQ(d.d_tx_bytes(), 0);
  EXPECT_EQ(d.d_rejected(), 0);
  EXPECT_EQ(d.d_window_full(), 0);
}

TEST_CASE(rma_window_full_falls_back_to_copy_path) {
  start_once();
  // A 16MB window (64 slots of 256KB) cannot hold a 20MB transfer: the
  // send must fall back to the striped copy path and stay correct.
  FlagGuard window("trpc_rma_window_bytes", "16777216");
  Channel ch;
  Channel::Options opts;
  opts.use_shm = true;
  opts.timeout_ms = 60000;
  EXPECT_EQ(ch.Init(addr(), &opts), 0);
  const std::string big = pattern(20 << 20, 11);
  RmaDelta d;
  const int64_t stripe0 = hotpath_vars().stripe_tx_chunks.get_value();
  Controller cntl;
  cntl.set_enable_checksum(true);
  IOBuf req, resp;
  req.append(big);
  ch.CallMethod("Echo.Echo", req, &resp, &cntl);
  EXPECT(!cntl.Failed());
  EXPECT_EQ(resp.size(), big.size());
  EXPECT(resp.equals(big.data(), big.size()));
  EXPECT_EQ(d.d_tx_msgs(), 0);  // nothing fit the one-sided window
  EXPECT(hotpath_vars().stripe_tx_chunks.get_value() - stripe0 > 0);
}

TEST_CASE(rma_chunk_drop_fails_call_whole) {
  start_once();
  Channel ch;
  Channel::Options opts;
  opts.use_shm = true;
  opts.timeout_ms = 60000;
  EXPECT_EQ(ch.Init(addr(), &opts), 0);
  {
    Controller cntl;  // establish the ring before arming faults
    IOBuf req, resp;
    req.append("warm");
    ch.CallMethod("Echo.Echo", req, &resp, &cntl);
    EXPECT(!cntl.Failed());
  }
  FaultGuard guard;
  EXPECT_EQ(FaultActor::global().set("seed=11;drop=0.7"), 0);
  Controller cntl;
  cntl.set_timeout_ms(1200);
  IOBuf req, resp;
  req.append(pattern(8 << 20, 13));
  ch.CallMethod("Echo.Echo", req, &resp, &cntl);
  // Dropped chunks leave completion bits clear (or the control frame
  // vanished): the CALL fails whole, never a partial payload.
  EXPECT(cntl.Failed());
  EXPECT_EQ(resp.size(), 0u);
  FaultActor::global().set("");
  // Clean again afterwards (reconnects if the fault killed the ring).
  Controller ok;
  ok.set_timeout_ms(20000);
  IOBuf req2, resp2;
  const std::string big = pattern(4 << 20, 17);
  req2.append(big);
  ch.CallMethod("Echo.Echo", req2, &resp2, &ok);
  EXPECT(!ok.Failed());
  EXPECT(resp2.equals(big.data(), big.size()));
}

TEST_CASE(rma_chunk_corrupt_rejected_by_chunk_crc) {
  start_once();
  Channel ch;
  Channel::Options opts;
  opts.use_shm = true;
  opts.timeout_ms = 60000;
  EXPECT_EQ(ch.Init(addr(), &opts), 0);
  {
    Controller cntl;
    IOBuf req, resp;
    req.append("warm");
    ch.CallMethod("Echo.Echo", req, &resp, &cntl);
    EXPECT(!cntl.Failed());
  }
  FaultGuard guard;
  EXPECT_EQ(FaultActor::global().set("seed=3;corrupt=0.8"), 0);
  RmaDelta d;
  Controller cntl;
  cntl.set_timeout_ms(1500);
  cntl.set_enable_checksum(true);  // arms the per-chunk CRCs
  IOBuf req, resp;
  req.append(pattern(8 << 20, 19));
  ch.CallMethod("Echo.Echo", req, &resp, &cntl);
  // A flipped byte in a landed chunk fails CRC verification at resolve:
  // the transfer is dropped whole and the call times out.
  EXPECT(cntl.Failed());
  EXPECT_EQ(resp.size(), 0u);
  EXPECT(d.d_rejected() >= 1);
}

TEST_CASE(rma_span_scavenger_reclaims_leaked_never_live) {
  // The documented span-leak-on-dropped-control degradation: a sender
  // allocates a window span, writes (or drops) its chunks, and the
  // CONTROL frame vanishes in transit — the slots stayed allocated
  // until connection teardown.  The scavenger must reclaim exactly
  // those spans, and never a live admitted one.
  start_once();
  Channel ch;
  Channel::Options opts;
  opts.use_shm = true;
  opts.timeout_ms = 60000;
  EXPECT_EQ(ch.Init(addr(), &opts), 0);
  {
    Controller cntl;  // establish the ring before arming faults
    IOBuf req, resp;
    req.append("warm");
    ch.CallMethod("Echo.Echo", req, &resp, &cntl);
    EXPECT(!cntl.Failed());
  }
  FlagGuard age("trpc_rma_span_scavenge_ms", "150");
  // Earlier suite tests (chunk-drop/corrupt, cancel/deadline races)
  // legitimately leak never-admitted spans — exactly the class this
  // scavenger exists for.  Purge that residue first so the live-span
  // exemption below is judged on this test's own span only.  Two passes
  // a full age apart: the scavenger is mark-then-sweep (first_seen
  // stamping), so one pass only STARTS aging a slot it never saw.
  rma_scavenge();
  usleep(200 * 1000);
  rma_scavenge();
  // A LIVE span first: hold the zero-copy response (it wraps a span in
  // OUR window) past the scavenge age — admitted spans are exempt.
  {
    Controller cntl;
    cntl.set_timeout_ms(20000);
    IOBuf req, resp;
    const std::string body = pattern(8 << 20, 23);
    req.append(body);
    ch.CallMethod("Echo.Echo", req, &resp, &cntl);
    EXPECT(!cntl.Failed());
    EXPECT(resp.equals(body.data(), body.size()));
    EXPECT(rma_spans_in_use() >= 1);
    usleep(300 * 1000);  // older than the scavenge age, but admitted
    EXPECT_EQ(rma_scavenge(), 0u);
    EXPECT(rma_spans_in_use() >= 1);  // still held by `resp`
  }
  // The response ref dropped: its span frees via the deleter, not the
  // scavenger.  (The request-side span frees when the echo's shared
  // payload refs drop — poll briefly for the async release.)
  for (int i = 0; i < 100 && rma_spans_in_use() != 0; ++i) {
    usleep(10 * 1000);
  }
  EXPECT_EQ(rma_spans_in_use(), 0u);

  // Now the leak: drop EVERYTHING (chunk writes and the control frame
  // itself) — the span allocated in the peer window is never resolved
  // and never freed.
  const int64_t scavenged_before = [] {
    // rma_span_scavenged is registry-read (no struct access needed).
    std::string out;
    return Variable::read_exposed("rma_span_scavenged", &out)
               ? strtoll(out.c_str(), nullptr, 10)
               : 0;
  }();
  {
    FaultGuard guard;
    EXPECT_EQ(FaultActor::global().set("seed=31;drop=1.0;max=64"), 0);
    Controller cntl;
    cntl.set_timeout_ms(800);
    IOBuf req, resp;
    req.append(pattern(8 << 20, 29));
    ch.CallMethod("Echo.Echo", req, &resp, &cntl);
    EXPECT(cntl.Failed());  // control frame dropped: the call dies whole
    EXPECT_EQ(resp.size(), 0u);
  }
  EXPECT(rma_spans_in_use() >= 1);  // the leaked span
  usleep(250 * 1000);  // first pass stamps first-seen...
  rma_scavenge();
  usleep(250 * 1000);  // ...second pass ages it past 150ms and reclaims
  rma_scavenge();
  EXPECT_EQ(rma_spans_in_use(), 0u);
  std::string out;
  EXPECT(Variable::read_exposed("rma_span_scavenged", &out));
  EXPECT(strtoll(out.c_str(), nullptr, 10) > scavenged_before);
  // The window is healthy again: a clean large echo reuses the slots.
  Controller ok;
  ok.set_timeout_ms(20000);
  IOBuf req2, resp2;
  const std::string big = pattern(4 << 20, 31);
  req2.append(big);
  ch.CallMethod("Echo.Echo", req2, &resp2, &ok);
  EXPECT(!ok.Failed());
  EXPECT(resp2.equals(big.data(), big.size()));
}

namespace {

// One landing of `span` by rma_land, checked against copy_to's bytes.
struct LandJob {
  const IOBuf* span = nullptr;
  const std::string* want = nullptr;  // copy_to's result
  bool on_fiber = false;
  uint32_t rails_used = 0;
  bool exact = false;
  bool where_expected = false;
};

void land_once(void* arg) {
  auto* j = static_cast<LandJob*>(arg);
  j->where_expected = in_fiber() == j->on_fiber;
  // A canary either side: a rail that wrote past its range shows.
  const size_t n = j->want->size();
  std::string got(n + 128, '\x5a');
  j->rails_used = rma_land(*j->span, &got[64], n);
  j->exact = got.compare(64, n, *j->want) == 0 &&
             got.find_first_not_of('\x5a') == 64 &&
             got.find_last_not_of('\x5a') == 64 + n - 1;
}

}  // namespace

TEST_CASE(rma_land_fans_a_window_span_out_like_copy_to) {
  start_once();
  Channel ch;
  Channel::Options opts;
  opts.use_shm = true;
  opts.timeout_ms = 60000;
  EXPECT_EQ(ch.Init(addr(), &opts), 0);
  // Five chunks of the default 2 MB, the last one 1 MB + 3 bytes: an
  // unaligned length and, at 2 and 4 rails, a last rail shorter than the
  // others.  The pattern's last byte is not the canary's.
  const std::string big = pattern((9 << 20) + 3, 11);
  const size_t spans0 = rma_spans_in_use();
  Controller cntl;
  IOBuf req, resp;
  req.append(big);
  ch.CallMethod("Echo.Echo", req, &resp, &cntl);
  EXPECT(!cntl.Failed());
  // The response IS a window span: one user-data block, its slots held.
  EXPECT_EQ(resp.block_count(), 1u);
  EXPECT(resp.ref_at(0).block->user_deleter != nullptr);
  EXPECT(rma_spans_in_use() > spans0);
  std::string want(big.size(), 0);
  EXPECT_EQ(resp.copy_to(&want[0], want.size()), want.size());
  EXPECT(want == big);
  const struct {
    const char* rails;
    uint32_t used;  // ceil(5 / ceil(5 / rails))
  } cases[] = {{"1", 1}, {"2", 2}, {"4", 3}, {"16", 5}};
  for (const auto& c : cases) {
    FlagGuard rails("trpc_shm_rails", c.rails);
    // The join's two branches: a plain pthread (usleep) and a fiber.
    LandJob on_thread{&resp, &want, false};
    land_once(&on_thread);
    LandJob on_fiber{&resp, &want, true};
    fiber_t f = 0;
    EXPECT_EQ(fiber_start(&f, land_once, &on_fiber, 0), 0);
    EXPECT_EQ(fiber_join(f), 0);
    for (const LandJob* j : {&on_thread, &on_fiber}) {
      EXPECT(j->where_expected);
      EXPECT(j->exact);
      EXPECT_EQ(j->rails_used, c.used);
    }
  }
  // A prefix shorter than the span and under one chunk is the plain copy.
  std::string head(4096, 0);
  EXPECT_EQ(rma_land(resp, &head[0], head.size()), 1u);
  EXPECT(big.compare(0, head.size(), head) == 0);
  // Not a window span (pool blocks, or a user block of someone else's):
  // the plain copy whatever its size.
  IOBuf plain;
  plain.append(big);
  std::string copy(big.size(), 0);
  EXPECT_EQ(rma_land(plain, &copy[0], copy.size()), 1u);
  EXPECT(copy == big);
  IOBuf wrapped;
  wrapped.append_user_data(const_cast<char*>(big.data()), big.size(),
                           [](void*, void*) {});
  copy.assign(big.size(), 0);
  EXPECT_EQ(rma_land(wrapped, &copy[0], copy.size()), 1u);
  EXPECT(copy == big);
  // Landing holds no reference of its own: the slots go with the payload.
  resp.clear();
  EXPECT_EQ(rma_spans_in_use(), spans0);
}

TEST_CASE(rma_kernel_capability_probe) {
  // The satellite gate: the probe answers deterministically, and on this
  // repo's dev boxes (kernel 4.4.0) io_uring is known-absent — but the
  // test only pins the CONTRACT (0/1, stable, unknown = -1).
  const int a = kernel_supports("io_uring");
  EXPECT(a == 0 || a == 1);
  EXPECT_EQ(kernel_supports("io_uring"), a);  // memoized, stable
  EXPECT_EQ(kernel_supports("no_such_feature"), -1);
  EXPECT_EQ(kernel_supports(nullptr), -1);
}

TEST_MAIN
