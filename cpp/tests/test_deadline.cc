// Deadline & cancellation plane tests (ISSUE 15): wire tail-group 7
// roundtrip + unset-traffic byte identity, server-side shed before
// dispatch (in-flight, injected-dispatch-delay, and QoS-lane queueing),
// handler-visible remaining budget, budget shrinking across proxy hops,
// cascading cancel fan-out to downstream calls and mid-transfer
// one-sided puts (composed with chunk-drop faults), the typed
// kEDeadlineExpired stopping the cluster retry chain, the retry-budget
// token bucket bounding storm amplification, hedge suppression when the
// remaining budget cannot cover the observed p50, and registry hygiene.
#include <unistd.h>

#include <atomic>
#include <string>
#include <vector>

#include "base/flags.h"
#include "base/time.h"
#include "fiber/event.h"
#include "fiber/fiber.h"
#include "net/channel.h"
#include "net/cluster.h"
#include "net/controller.h"
#include "net/deadline.h"
#include "net/fault.h"
#include "net/protocol.h"
#include "net/qos.h"
#include "net/rma.h"
#include "net/server.h"
#include "tests/test_util.h"

using namespace trpc;

namespace {

std::atomic<int> g_echo_execs{0};
std::atomic<int> g_med_execs{0};
std::atomic<int> g_fail_execs{0};
std::atomic<int64_t> g_seen_remaining{-1};

Server* g_server = nullptr;
int g_port = 0;

void register_common(Server* s) {
  s->RegisterMethod(
      "Echo.Echo", [](Controller* cntl, const IOBuf& req, IOBuf* resp,
                      Closure done) {
        g_echo_execs.fetch_add(1, std::memory_order_acq_rel);
        g_seen_remaining.store(cntl->remaining_us(),
                               std::memory_order_release);
        resp->append(req);
        done();
      });
  s->RegisterMethod(
      "Echo.Med", [](Controller*, const IOBuf& req, IOBuf* resp,
                     Closure done) {
        g_med_execs.fetch_add(1, std::memory_order_acq_rel);
        fiber_sleep_us(30 * 1000);
        resp->append(req);
        done();
      });
  s->RegisterMethod(
      "Echo.Med2", [](Controller*, const IOBuf& req, IOBuf* resp,
                      Closure done) {
        fiber_sleep_us(60 * 1000);
        resp->append(req);
        done();
      });
  s->RegisterMethod(
      "Echo.Fail", [](Controller* cntl, const IOBuf&, IOBuf*,
                      Closure done) {
        g_fail_execs.fetch_add(1, std::memory_order_acq_rel);
        cntl->SetFailed(42, "deliberate failure");
        done();
      });
}

void start_server_once() {
  if (g_server != nullptr) {
    return;
  }
  g_server = new Server();
  register_common(g_server);
  EXPECT_EQ(g_server->Start(0), 0);
  g_port = g_server->port();
}

std::string addr() { return "127.0.0.1:" + std::to_string(g_port); }

struct DeadlineDelta {
  int64_t shed, stamped, client_expired, fanout, saved, retry_sup,
      hedge_sup;
  DeadlineDelta() { reset(); }
  void reset() {
    DeadlineVars& v = deadline_vars();
    shed = v.shed_total.get_value();
    stamped = v.stamped_total.get_value();
    client_expired = v.client_expired_total.get_value();
    fanout = v.cancel_fanout_total.get_value();
    saved = v.cancel_saved_bytes.get_value();
    retry_sup = v.retry_suppressed.get_value();
    hedge_sup = v.hedge_suppressed.get_value();
  }
  int64_t d_shed() const {
    return deadline_vars().shed_total.get_value() - shed;
  }
  int64_t d_stamped() const {
    return deadline_vars().stamped_total.get_value() - stamped;
  }
  int64_t d_client_expired() const {
    return deadline_vars().client_expired_total.get_value() -
           client_expired;
  }
  int64_t d_fanout() const {
    return deadline_vars().cancel_fanout_total.get_value() - fanout;
  }
  int64_t d_saved() const {
    return deadline_vars().cancel_saved_bytes.get_value() - saved;
  }
  int64_t d_retry_sup() const {
    return deadline_vars().retry_suppressed.get_value() - retry_sup;
  }
  int64_t d_hedge_sup() const {
    return deadline_vars().hedge_suppressed.get_value() - hedge_sup;
  }
};

void wait_until(const std::function<bool()>& pred, int64_t budget_ms) {
  const int64_t deadline = monotonic_time_us() + budget_ms * 1000;
  while (!pred() && monotonic_time_us() < deadline) {
    usleep(2000);
  }
}

std::string pattern(size_t n, int seed) {
  std::string s(n, 0);
  for (size_t i = 0; i < n; ++i) {
    s[i] = static_cast<char>((i * 131 + seed * 7) & 0xff);
  }
  return s;
}

}  // namespace

// ---- wire ----------------------------------------------------------------

TEST_CASE(wire_roundtrip_and_unset_byte_identity) {
  const Protocol& p = tstd_protocol();
  // Unset traffic: the frame must contain NO optional tail at all —
  // byte-for-byte the pre-deadline-plane layout (fixed fields + method
  // + empty error_text = 38 + 1 + 4 bytes of meta).
  {
    RpcMeta meta;
    meta.type = RpcMeta::kRequest;
    meta.correlation_id = 7;
    meta.method = "M";
    IOBuf frame, payload;
    payload.append("x");
    tstd_pack(&frame, meta, payload);
    char hdr[16];
    EXPECT_EQ(frame.copy_to(hdr, 16), 16u);
    uint32_t meta_len = 0;
    memcpy(&meta_len, hdr + 4, 4);
    EXPECT_EQ(meta_len, 43u);  // no tail groups emitted
    const int64_t before = monotonic_time_us();
    InputMessage msg;
    EXPECT(p.parse(&frame, &msg, nullptr) == ParseError::kOk);
    EXPECT_EQ(msg.meta.deadline_us, 0u);
    // Every request's arrival is read at the cut, budget or none: the
    // server's phase stamps count from it (net/wire_split.h).
    EXPECT(msg.arrival_us >= before);
  }
  // Deadline-only meta: groups 1..7 ride (121B tail), the budget
  // roundtrips exactly, and arrival is stamped at cut.
  {
    RpcMeta meta;
    meta.type = RpcMeta::kRequest;
    meta.correlation_id = 8;
    meta.method = "M";
    meta.deadline_us = 123456;
    IOBuf frame, payload;
    payload.append("x");
    tstd_pack(&frame, meta, payload);
    char hdr[16];
    EXPECT_EQ(frame.copy_to(hdr, 16), 16u);
    uint32_t meta_len = 0;
    memcpy(&meta_len, hdr + 4, 4);
    EXPECT_EQ(meta_len, 43u + 121u);
    const int64_t before = monotonic_time_us();
    InputMessage msg;
    EXPECT(p.parse(&frame, &msg, nullptr) == ParseError::kOk);
    EXPECT_EQ(msg.meta.deadline_us, 123456u);
    EXPECT(msg.arrival_us >= before);
  }
}

TEST_CASE(wire_flag_off_restores_byte_identity) {
  start_server_once();
  EXPECT_EQ(Flag::set("trpc_deadline_wire", "false"), 0);
  DeadlineDelta d;
  Channel ch;
  EXPECT_EQ(ch.Init(addr()), 0);
  Controller cntl;
  cntl.set_timeout_ms(5000);
  IOBuf req, resp;
  req.append("plain");
  ch.CallMethod("Echo.Echo", req, &resp, &cntl);
  EXPECT(!cntl.Failed());
  EXPECT_EQ(d.d_stamped(), 0);  // vars provably frozen with the flag off
  // The handler saw NO deadline.
  EXPECT_EQ(g_seen_remaining.load(std::memory_order_acquire), INT64_MAX);
  EXPECT_EQ(Flag::set("trpc_deadline_wire", "true"), 0);
}

// ---- server enforcement --------------------------------------------------

TEST_CASE(handler_reads_propagated_remaining_budget) {
  start_server_once();
  DeadlineDelta d;
  Channel ch;
  EXPECT_EQ(ch.Init(addr()), 0);
  Controller cntl;
  cntl.set_timeout_ms(500);
  IOBuf req, resp;
  req.append("q");
  ch.CallMethod("Echo.Echo", req, &resp, &cntl);
  EXPECT(!cntl.Failed());
  EXPECT_EQ(d.d_stamped(), 1);
  const int64_t seen = g_seen_remaining.load(std::memory_order_acquire);
  EXPECT(seen > 0);
  EXPECT(seen <= 500 * 1000);
}

TEST_CASE(expired_in_dispatch_delay_shed_never_executed) {
  start_server_once();
  EXPECT_EQ(g_server->SetFaults("seed=1;svr_delay=1:120"), 0);
  DeadlineDelta d;
  const int execs_before = g_echo_execs.load(std::memory_order_acquire);
  Channel ch;
  EXPECT_EQ(ch.Init(addr()), 0);
  Controller cntl;
  cntl.set_timeout_ms(40);  // budget dies inside the injected 120ms delay
  IOBuf req, resp;
  req.append("doomed");
  ch.CallMethod("Echo.Echo", req, &resp, &cntl);
  EXPECT(cntl.Failed());  // locally: the 40ms timer
  // Server side: the request is SHED post-delay — never half-executed.
  wait_until([&] { return d.d_shed() >= 1; }, 3000);
  EXPECT(d.d_shed() >= 1);
  EXPECT_EQ(g_echo_execs.load(std::memory_order_acquire), execs_before);
  EXPECT_EQ(g_server->SetFaults(""), 0);
}

TEST_CASE(expired_in_qos_lane_shed_before_dispatch) {
  start_server_once();
  EXPECT_EQ(Flag::set("trpc_qos_lanes", "2"), 0);
  qos_test_pause(true);  // stage a backlog: requests queue, undrained
  DeadlineDelta d;
  const int execs_before = g_echo_execs.load(std::memory_order_acquire);
  Channel ch;
  EXPECT_EQ(ch.Init(addr()), 0);
  Controller cntl;
  cntl.set_timeout_ms(40);
  IOBuf req, resp;
  req.append("queued");
  ch.CallMethod("Echo.Echo", req, &resp, &cntl);
  EXPECT(cntl.Failed());  // timed out while parked in the lane
  usleep(30 * 1000);      // arrival + 40ms is now well past
  qos_test_pause(false);
  // Kick a drain with a fresh (healthy) request.
  Controller kick;
  kick.set_timeout_ms(5000);
  IOBuf req2, resp2;
  req2.append("kick");
  ch.CallMethod("Echo.Echo", req2, &resp2, &kick);
  EXPECT(!kick.Failed());
  wait_until([&] { return d.d_shed() >= 1; }, 3000);
  // The queued-expired request was shed at dispatch (arrival stamped at
  // parse: lane wait counted against the budget), and only the healthy
  // kick executed.
  EXPECT(d.d_shed() >= 1);
  EXPECT_EQ(g_echo_execs.load(std::memory_order_acquire),
            execs_before + 1);
  EXPECT_EQ(Flag::set("trpc_qos_lanes", "0"), 0);
}

TEST_CASE(client_fail_fast_when_ambient_budget_exhausted) {
  start_server_once();
  DeadlineDelta d;
  set_ambient_deadline(monotonic_time_us() - 1);  // already past
  Channel ch;
  EXPECT_EQ(ch.Init(addr()), 0);
  Controller cntl;
  cntl.set_timeout_ms(5000);
  IOBuf req, resp;
  req.append("dead on arrival");
  ch.CallMethod("Echo.Echo", req, &resp, &cntl);
  set_ambient_deadline(0);
  EXPECT(cntl.Failed());
  EXPECT_EQ(cntl.error_code(), kEDeadlineExpired);
  EXPECT_EQ(d.d_client_expired(), 1);
  EXPECT_EQ(d.d_stamped(), 0);  // never reached the wire
}

TEST_CASE(ambient_bound_expiry_surfaces_typed_error) {
  start_server_once();
  // The ambient budget (60ms) is strictly tighter than the call's own
  // 5s timeout: its expiry is budget exhaustion, surfaced as the TYPED
  // status so retry layers stop the chain.
  EXPECT_EQ(g_server->SetFaults("seed=1;svr_delay=1:250"), 0);
  set_ambient_deadline(monotonic_time_us() + 60 * 1000);
  Channel ch;
  EXPECT_EQ(ch.Init(addr()), 0);
  Controller cntl;
  cntl.set_timeout_ms(5000);
  IOBuf req, resp;
  req.append("x");
  const int64_t t0 = monotonic_time_us();
  ch.CallMethod("Echo.Echo", req, &resp, &cntl);
  const int64_t dt_ms = (monotonic_time_us() - t0) / 1000;
  set_ambient_deadline(0);
  EXPECT(cntl.Failed());
  EXPECT_EQ(cntl.error_code(), kEDeadlineExpired);
  EXPECT(dt_ms < 250);  // died at the budget, not the hop timeout
  EXPECT_EQ(g_server->SetFaults(""), 0);
}

// ---- propagation across hops ---------------------------------------------

TEST_CASE(proxied_call_restamps_budget_minus_elapsed) {
  start_server_once();
  // Proxy server A: burns ~30ms, then calls the backend (g_server) with
  // a huge own timeout — the WIRE stamp must carry the caller's
  // remaining budget, not the proxy's fresh 10s.
  static std::string backend_addr;
  backend_addr = addr();
  Server proxy;
  proxy.RegisterMethod(
      "Proxy.Echo", [](Controller* cntl, const IOBuf& req, IOBuf* resp,
                       Closure done) {
        fiber_sleep_us(30 * 1000);
        Channel down;
        if (down.Init(backend_addr) != 0) {
          cntl->SetFailed(EINVAL, "init");
          done();
          return;
        }
        Controller dc;
        dc.set_timeout_ms(10000);
        IOBuf dresp;
        down.CallMethod("Echo.Echo", req, &dresp, &dc);
        if (dc.Failed()) {
          cntl->SetFailed(dc.error_code(), dc.error_text());
        } else {
          resp->append(dresp);
        }
        done();
      });
  EXPECT_EQ(proxy.Start(0), 0);
  Channel ch;
  EXPECT_EQ(ch.Init("127.0.0.1:" + std::to_string(proxy.port())), 0);
  Controller cntl;
  cntl.set_timeout_ms(500);
  IOBuf req, resp;
  req.append("hop");
  ch.CallMethod("Proxy.Echo", req, &resp, &cntl);
  EXPECT(!cntl.Failed());
  const int64_t seen = g_seen_remaining.load(std::memory_order_acquire);
  // The backend saw the 500ms budget minus the proxy's ~30ms burn (and
  // NOT the proxy's own 10s): decremented-by-elapsed at every hop.
  EXPECT(seen > 0);
  EXPECT(seen < 480 * 1000);
  EXPECT(seen > 100 * 1000);
  proxy.Stop();
  proxy.Join();
}

// ---- cascading cancellation ----------------------------------------------

TEST_CASE(cancel_fans_out_to_downstream_call) {
  start_server_once();
  static std::string backend_addr;
  backend_addr = addr();
  static std::atomic<int> downstream_code{-1};
  static std::atomic<int> downstream_ok{0};
  static std::atomic<int64_t> downstream_ms{-1};
  downstream_code.store(-1, std::memory_order_release);
  downstream_ok.store(0, std::memory_order_release);
  downstream_ms.store(-1, std::memory_order_release);
  // Slow backend method for the downstream leg.
  Server proxy;
  proxy.RegisterMethod(
      "Proxy.Slow", [](Controller* cntl, const IOBuf& req, IOBuf* resp,
                       Closure done) {
        Channel down;
        if (down.Init(backend_addr) != 0) {
          cntl->SetFailed(EINVAL, "init");
          done();
          return;
        }
        Controller dc;
        dc.set_timeout_ms(10000);
        IOBuf dresp;
        IOBuf dreq;
        dreq.append("med");
        const int64_t t0 = monotonic_time_us();
        // Three sequential downstream calls ~90ms total: the cancel
        // lands mid-chain and must abort the in-flight one AND the
        // handler's loop (IsCanceled).
        for (int i = 0; i < 3 && !cntl->IsCanceled(); ++i) {
          dc.Reset();
          down.CallMethod("Echo.Med", dreq, &dresp, &dc);
          if (dc.Failed()) {
            break;
          }
          downstream_ok.fetch_add(1, std::memory_order_acq_rel);
        }
        downstream_code.store(dc.error_code(), std::memory_order_release);
        downstream_ms.store((monotonic_time_us() - t0) / 1000,
                            std::memory_order_release);
        if (dc.Failed()) {
          cntl->SetFailed(dc.error_code(), dc.error_text());
        } else {
          resp->append(dresp);
        }
        done();
      });
  EXPECT_EQ(proxy.Start(0), 0);
  DeadlineDelta d;
  Channel ch;
  EXPECT_EQ(ch.Init("127.0.0.1:" + std::to_string(proxy.port())), 0);
  Controller cntl;
  cntl.set_timeout_ms(10000);
  IOBuf req, resp;
  req.append("x");
  Event ev;
  ch.CallMethod("Proxy.Slow", req, &resp, &cntl, [&ev] {
    ev.value.fetch_add(1, std::memory_order_release);
    ev.wake_all();
  });
  usleep(40 * 1000);  // mid-chain (first ~30ms downstream in flight)
  cntl.StartCancel();
  wait_until(
      [&] {
        return downstream_ms.load(std::memory_order_acquire) >= 0;
      },
      3000);
  EXPECT(cntl.Failed());
  EXPECT_EQ(cntl.error_code(), ECANCELED);
  // The fan-out aborted the proxy's downstream CHAIN: either the
  // in-flight call died ECANCELED mid-flight, or (slower schedules —
  // TSan — where the cancel lands between calls) the IsCanceled guard
  // cut the loop.  Either way fewer than all 3 legs completed.
  const int code = downstream_code.load(std::memory_order_acquire);
  const int ok_legs = downstream_ok.load(std::memory_order_acquire);
  EXPECT(code == ECANCELED || ok_legs < 3);
  EXPECT(ok_legs < 3);
  EXPECT(d.d_fanout() >= 1);
  proxy.Stop();
  proxy.Join();
}

TEST_CASE(cancel_mid_rma_response_stops_transfer) {
  // A decode-side pull abandoned mid-transfer: the serving side's
  // one-sided put must stop within one chunk budget, not ship the rest.
  static Server* shm_srv = [] {
    auto* s = new Server();
    s->RegisterMethod(
        "Kv.SlowBig", [](Controller*, const IOBuf&, IOBuf* resp,
                         Closure done) {
          fiber_sleep_us(120 * 1000);  // cancel lands while we park
          resp->append(pattern(16 << 20, 5));
          done();
        });
    EXPECT_EQ(s->Start(0), 0);
    return s;
  }();
  Channel ch;
  Channel::Options opts;
  opts.use_shm = true;
  opts.timeout_ms = 60000;
  EXPECT_EQ(ch.Init("127.0.0.1:" + std::to_string(shm_srv->port()), &opts),
            0);
  {
    Controller warm;
    IOBuf req, resp;
    req.append("w");
    ch.CallMethod("Kv.SlowBig", req, &resp, &warm);
    EXPECT(!warm.Failed());
  }
  const size_t cap = 32 << 20;
  uint64_t rkey = 0;
  void* land = rma_alloc(cap, &rkey);
  EXPECT(land != nullptr);
  DeadlineDelta d;
  {
    Controller cntl;
    cntl.set_timeout_ms(60000);
    cntl.call().land_buf = land;
    cntl.call().land_cap = cap;
    IOBuf req, resp;
    req.append("pull");
    Event ev;
    ch.CallMethod("Kv.SlowBig", req, &resp, &cntl, [&ev] {
      ev.value.fetch_add(1, std::memory_order_release);
      ev.wake_all();
    });
    usleep(40 * 1000);   // handler parked server-side
    cntl.StartCancel();  // kCancel frame → scope fires before the put
    wait_until([&] { return d.d_saved() > 0; }, 5000);
    EXPECT(cntl.Failed());
  }
  // At least all-but-one-chunk of the 16MB body was never written.
  EXPECT(d.d_saved() >= (16 << 20) - (4 << 20));
  EXPECT(d.d_fanout() >= 1);
  // The channel still works after the aborted transfer.
  {
    Controller cntl;
    IOBuf req, resp;
    req.append("after");
    ch.CallMethod("Kv.SlowBig", req, &resp, &cntl);
    EXPECT(!cntl.Failed());
    EXPECT_EQ(resp.size(), static_cast<size_t>(16 << 20));
  }
  rma_free(land);
}

TEST_CASE(cancel_fanout_composes_with_chunk_drop_faults) {
  // Chaos composition (satellite): cancels racing transfers WHILE the
  // seeded fault actor drops/garbles chunks — whatever the interleaving,
  // nothing crashes, no partial payload is ever admitted, and the
  // channel stays healthy once faults clear.
  start_server_once();
  Channel ch;
  EXPECT_EQ(ch.Init(addr()), 0);
  EXPECT_EQ(g_server->SetFaults("seed=5;svr_delay=0.5:60"), 0);
  EXPECT_EQ(FaultActor::global().set("seed=5;drop=0.15;trunc=0.1"), 0);
  const std::string big = pattern(6 << 20, 11);
  for (int i = 0; i < 8; ++i) {
    Controller cntl;
    cntl.set_timeout_ms(5000);
    IOBuf req, resp;
    req.append(big);
    Event ev;
    ch.CallMethod("Echo.Echo", req, &resp, &cntl, [&ev] {
      ev.value.fetch_add(1, std::memory_order_release);
      ev.wake_all();
    });
    usleep((i % 3) * 15 * 1000);
    cntl.StartCancel();
    const uint32_t snap = ev.value.load(std::memory_order_acquire);
    if (snap == 0) {
      ev.wait(0, monotonic_time_us() + 8 * 1000 * 1000);
    }
    // Whole-or-nothing: success echoes every byte, failure delivers none.
    if (!cntl.Failed()) {
      EXPECT_EQ(resp.size(), big.size());
    } else {
      EXPECT_EQ(resp.size(), 0u);
    }
  }
  FaultActor::global().set("");
  EXPECT_EQ(g_server->SetFaults(""), 0);
  // The last faulted frame may have left truncated residue in a parse
  // buffer, and the old channel's connection may be half-dead in any
  // direction — the recovery contract is that a FRESH connection to the
  // same server works once faults clear.  Short per-attempt timeouts:
  // a poisoned attempt costs one bounded timeout, not the budget.
  bool healed = false;
  for (int i = 0; i < 8 && !healed; ++i) {
    Channel fresh;
    EXPECT_EQ(fresh.Init(addr()), 0);
    Controller cntl;
    cntl.set_timeout_ms(2000);
    IOBuf req, resp;
    req.append("healed");
    fresh.CallMethod("Echo.Echo", req, &resp, &cntl);
    healed = !cntl.Failed() && resp.to_string() == "healed";
  }
  EXPECT(healed);
}

// ---- cluster governance --------------------------------------------------

namespace {

struct TwoNodes {
  Server a, b;
  std::string url;
};

TwoNodes* start_two_nodes() {
  auto* n = new TwoNodes();
  register_common(&n->a);
  register_common(&n->b);
  EXPECT_EQ(n->a.Start(0), 0);
  EXPECT_EQ(n->b.Start(0), 0);
  n->url = "list://127.0.0.1:" + std::to_string(n->a.port()) +
           ",127.0.0.1:" + std::to_string(n->b.port());
  return n;
}

}  // namespace

TEST_CASE(deadline_expired_stops_retry_chain) {
  TwoNodes* n = start_two_nodes();
  ClusterChannel ch;
  ClusterChannel::Options opts;
  opts.timeout_ms = 10000;
  opts.max_retry = 3;
  opts.health_check_method = "";
  EXPECT_EQ(ch.Init(n->url, "rr", &opts), 0);
  // Ambient budget (25ms) < the 30ms handler: the attempt dies with the
  // TYPED code and the chain stops — a dead budget must not burn
  // retries on every node.
  const int before = g_med_execs.load(std::memory_order_acquire);
  set_ambient_deadline(monotonic_time_us() + 25 * 1000);
  Controller cntl;
  IOBuf req, resp;
  req.append("x");
  ch.CallMethod("Echo.Med", req, &resp, &cntl);
  set_ambient_deadline(0);
  EXPECT(cntl.Failed());
  EXPECT_EQ(cntl.error_code(), kEDeadlineExpired);
  usleep(80 * 1000);  // let any (wrong) extra attempts land
  EXPECT_EQ(g_med_execs.load(std::memory_order_acquire), before + 1);
  delete n;
}

TEST_CASE(retry_budget_bounds_storm_amplification) {
  TwoNodes* n = start_two_nodes();
  const auto run_calls = [&](int count) {
    ClusterChannel ch;
    ClusterChannel::Options opts;
    opts.timeout_ms = 2000;
    opts.max_retry = 3;
    opts.health_check_method = "";
    EXPECT_EQ(ch.Init(n->url, "rr", &opts), 0);
    for (int i = 0; i < count; ++i) {
      Controller cntl;
      IOBuf req, resp;
      req.append("x");
      ch.CallMethod("Echo.Fail", req, &resp, &cntl);
      EXPECT(cntl.Failed());
    }
  };
  // Budget OFF: every failed call retries onto the other node — 2.0x
  // attempt amplification (bounded only by the node count here).
  EXPECT_EQ(Flag::set("trpc_cluster_retry_budget_pct", "0"), 0);
  int before = g_fail_execs.load(std::memory_order_acquire);
  run_calls(30);
  const int attempts_off =
      g_fail_execs.load(std::memory_order_acquire) - before;
  EXPECT_EQ(attempts_off, 60);
  // Budget ON (10%): amplification bounded ≤ 1.2x under 100% failure.
  EXPECT_EQ(Flag::set("trpc_cluster_retry_budget_pct", "10"), 0);
  DeadlineDelta d;
  before = g_fail_execs.load(std::memory_order_acquire);
  run_calls(30);
  const int attempts_on =
      g_fail_execs.load(std::memory_order_acquire) - before;
  EXPECT(attempts_on >= 30);
  EXPECT(attempts_on <= 36);  // ≤ 1.2x of 30 primaries
  EXPECT(d.d_retry_sup() >= 24);
  EXPECT_EQ(Flag::set("trpc_cluster_retry_budget_pct", "0"), 0);
  delete n;
}

TEST_CASE(hedge_suppressed_when_budget_cannot_cover_p50) {
  TwoNodes* n = start_two_nodes();
  ClusterChannel ch;
  ClusterChannel::Options opts;
  opts.timeout_ms = 2000;
  opts.max_retry = 0;
  opts.backup_request_ms = 10;
  opts.health_check_method = "";
  EXPECT_EQ(ch.Init(n->url, "rr", &opts), 0);
  // Warm the cluster's p50 estimate with ~60ms calls (the 10ms hedge
  // trigger fires on each, which is fine — the remaining 2s covers
  // them, so they launch and feed the estimate).
  for (int i = 0; i < 6; ++i) {
    Controller cntl;
    IOBuf req, resp;
    req.append("warm");
    ch.CallMethod("Echo.Med2", req, &resp, &cntl);
    EXPECT(!cntl.Failed());
  }
  // Tight call on the FASTER (30ms) method: at hedge-arm time (~10ms
  // in) the remaining ~35ms budget cannot cover the observed ~60ms p50
  // — the hedge is suppressed; the primary still answers inside its
  // own budget.
  DeadlineDelta d;
  const int before = g_med_execs.load(std::memory_order_acquire);
  Controller cntl;
  cntl.set_timeout_ms(45);
  IOBuf req, resp;
  req.append("tight");
  ch.CallMethod("Echo.Med", req, &resp, &cntl);
  EXPECT(!cntl.Failed());
  EXPECT(d.d_hedge_sup() >= 1);
  usleep(60 * 1000);
  EXPECT_EQ(g_med_execs.load(std::memory_order_acquire),
            before + 1);  // no second attempt ever launched
  delete n;
}

// ---- hygiene -------------------------------------------------------------

TEST_CASE(cancel_registry_drains_to_zero) {
  // Every dispatched request above unregistered its scope; slow
  // handlers (Echo.Slow-style parks) get a bounded grace.
  wait_until([] { return cancel_registered() == 0; }, 5000);
  EXPECT_EQ(cancel_registered(), 0u);
}

TEST_MAIN
