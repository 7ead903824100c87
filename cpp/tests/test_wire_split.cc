// The wire phase cut at the server's stamps (net/wire_split.h): the fold
// as a function of a call's stamps and of whether its two ends share a
// clock (the branch no loopback test reaches: a peer on another clock),
// the stamps' form on the wire with and without the other tail groups and
// what a decoder that predates them sees of it, and the one way of being
// answered before the handler that Python cannot set up: the per-method
// concurrency limiter's rejection.
#include <unistd.h>

#include <cstring>
#include <string>

#include "base/time.h"
#include "fiber/event.h"
#include "fiber/fiber.h"
#include "net/channel.h"
#include "net/concurrency_limiter.h"
#include "net/protocol.h"
#include "net/server.h"
#include "net/wire_split.h"
#include "stat/variable.h"
#include "tests/test_util.h"

using namespace trpc;

namespace {

constexpr int64_t kIssue = 5'000'000;  // the caller's clock, us

}  // namespace

// ---- the fold --------------------------------------------------------------

TEST_CASE(one_clock_cuts_wire_into_four_parts_that_sum_to_it) {
  // issue +120 arrival +30 handler +400 done +70 reply.
  const WireSplit w = split_wire(kIssue, kIssue + 620, {kIssue + 120,
                                 kIssue + 150, kIssue + 550}, true);
  EXPECT(w.split);
  EXPECT(w.legs);
  EXPECT_EQ(w.req_leg_us, 120);
  EXPECT_EQ(w.srv_queue_us, 30);
  EXPECT_EQ(w.srv_handler_us, 400);
  EXPECT_EQ(w.net_us, 190);
  EXPECT_EQ(w.net_us - w.req_leg_us, 70);  // the response's leg
  EXPECT_EQ(w.req_leg_us + w.srv_queue_us + w.srv_handler_us +
                (w.net_us - w.req_leg_us),
            620);
}

TEST_CASE(a_peer_on_another_clock_folds_net_and_no_leg) {
  // The same call, the server's clock 7 hours ahead of the caller's.
  const int64_t skew = 7ll * 3600 * 1000 * 1000;
  const WireSplit said_apart =
      split_wire(kIssue, kIssue + 620, {kIssue + skew + 120,
                 kIssue + skew + 150, kIssue + skew + 550}, false);
  EXPECT(said_apart.split);
  EXPECT(!said_apart.legs);
  EXPECT_EQ(said_apart.req_leg_us, 0);
  EXPECT_EQ(said_apart.srv_queue_us, 30);
  EXPECT_EQ(said_apart.srv_handler_us, 400);
  EXPECT_EQ(said_apart.net_us, 190);
  // A connection that claims one clock and shows another (a time
  // namespace, a forwarded port) cuts no leg either: the arrival is not
  // inside the call, whichever way the clocks differ.
  for (const int64_t off : {skew, -skew, int64_t{-121}, int64_t{71}}) {
    const WireSplit w =
        split_wire(kIssue, kIssue + 620, {kIssue + off + 120,
                   kIssue + off + 150, kIssue + off + 550}, true);
    EXPECT(w.split);
    EXPECT(!w.legs);
    EXPECT_EQ(w.net_us, 190);
  }
  // One clock said, and one clock not said: the same three sums.
  const WireSplit quiet = split_wire(kIssue, kIssue + 620, {kIssue + 120,
                                     kIssue + 150, kIssue + 550}, false);
  EXPECT(quiet.split && !quiet.legs);
  EXPECT_EQ(quiet.net_us, 190);
}

TEST_CASE(stamps_that_cannot_be_the_calls_fold_nothing) {
  // None carried.
  EXPECT(!split_wire(kIssue, kIssue + 620, {0, 0, 0}, true).split);
  // Out of order.
  EXPECT(!split_wire(kIssue, kIssue + 620, {kIssue + 150, kIssue + 120,
                     kIssue + 550}, true).split);
  EXPECT(!split_wire(kIssue, kIssue + 620, {kIssue + 120, kIssue + 560,
                     kIssue + 550}, true).split);
  // A server share longer than the whole wire.
  EXPECT(!split_wire(kIssue, kIssue + 620, {kIssue + 10, kIssue + 20,
                     kIssue + 700}, false).split);
  // A call answered before any handler: handler == done, handler time 0.
  const WireSplit shed = split_wire(kIssue, kIssue + 100, {kIssue + 40,
                                    kIssue + 60, kIssue + 60}, true);
  EXPECT(shed.split && shed.legs);
  EXPECT_EQ(shed.srv_queue_us, 20);
  EXPECT_EQ(shed.srv_handler_us, 0);
}

// ---- the stamps on the wire -------------------------------------------------

namespace {

// Packs `meta` over a 1 KB payload and returns the frame's bytes.
std::string packed(const RpcMeta& meta) {
  IOBuf frame, payload;
  payload.append(std::string(1024, 'p'));
  tstd_pack(&frame, meta, payload);
  return frame.to_string();
}

uint32_t meta_len_of(const std::string& frame) {
  uint32_t n = 0;
  memcpy(&n, frame.data() + 4, 4);
  return n;
}

RpcMeta parsed(const std::string& frame) {
  IOBuf buf;
  buf.append(frame);
  InputMessage msg;
  EXPECT(tstd_protocol().parse(&buf, &msg, nullptr) == ParseError::kOk);
  EXPECT_EQ(msg.payload.size(), 1024u);
  EXPECT_EQ(msg.arrival_us, 0);  // only a request's arrival is read
  return msg.meta;
}

// What a decoder that predates the stamps makes of `frame`: it reads the
// tail group by group while enough bytes remain for the next one and
// never looks at what is left, so dropping the stamps' 16 bytes from the
// meta's end (and from meta_len) IS its view of the frame.
std::string as_an_old_decoder_reads(std::string frame) {
  const uint32_t len = meta_len_of(frame) - 16;
  frame.erase(16 + len, 16);
  memcpy(&frame[4], &len, 4);
  return frame;
}

RpcMeta response(uint64_t cid) {
  RpcMeta m;
  m.type = RpcMeta::kResponse;
  m.correlation_id = cid;
  return m;
}

}  // namespace

TEST_CASE(a_plain_response_carries_the_stamps_in_sixteen_bytes) {
  RpcMeta bare = response(9);
  RpcMeta stamped = bare;
  stamped.srv.arrival_us = 123'456'789'012;
  stamped.srv.handler_us = stamped.srv.arrival_us + 35;
  stamped.srv.done_us = stamped.srv.handler_us + 2'000;
  const std::string before = packed(bare);
  const std::string after = packed(stamped);
  // 16 header + 42 fixed meta + 1024: a 1 KB response weighs 1,082 bytes
  // without the stamps and 1,098 with them.
  EXPECT_EQ(meta_len_of(before), 42u);
  EXPECT_EQ(before.size(), 1082u);
  EXPECT_EQ(meta_len_of(after), 42u + 16u);
  EXPECT_EQ(after.size(), 1098u);
  const RpcMeta out = parsed(after);
  EXPECT_EQ(out.correlation_id, 9u);
  EXPECT_EQ(out.srv.arrival_us, stamped.srv.arrival_us);
  EXPECT_EQ(out.srv.handler_us, stamped.srv.handler_us);
  EXPECT_EQ(out.srv.done_us, stamped.srv.done_us);
  EXPECT_EQ(out.trace_id, 0u);  // no other group was read out of them
  EXPECT_EQ(parsed(before).srv.arrival_us, 0);
  // An old decoder's first gate is `24 bytes remain` (the trace group):
  // it skips a 16-byte tail whole and sees the frame it always saw.
  EXPECT(as_an_old_decoder_reads(after) == before);
}

TEST_CASE(beside_other_groups_the_stamps_ride_last_and_an_old_decoder_stops_before_them) {
  // A one-sided response: the rma group is active, so the tail is
  // there anyway; the stamps pay the deadline group's 8 bytes and their
  // own 16.
  RpcMeta bare = response(11);
  bare.rma_rkey = 0xabcdef;
  bare.rma_len = 1 << 22;
  bare.qos_tenant = "t";
  RpcMeta stamped = bare;
  stamped.srv.arrival_us = 99'000'000;
  stamped.srv.handler_us = 99'000'010;
  stamped.srv.done_us = 99'000'500;
  const std::string before = packed(bare);
  const std::string after = packed(stamped);
  EXPECT_EQ(meta_len_of(after), meta_len_of(before) + 8u + 16u);
  const RpcMeta out = parsed(after);
  EXPECT_EQ(out.rma_rkey, 0xabcdefu);
  EXPECT(out.qos_tenant == "t");
  EXPECT_EQ(out.deadline_us, 0u);
  EXPECT_EQ(out.srv.arrival_us, 99'000'000);
  EXPECT_EQ(out.srv.handler_us, 99'000'010);
  EXPECT_EQ(out.srv.done_us, 99'000'500);
  const RpcMeta old_view = parsed(as_an_old_decoder_reads(after));
  EXPECT_EQ(old_view.rma_rkey, 0xabcdefu);
  EXPECT_EQ(old_view.rma_len, 1u << 22);
  EXPECT(old_view.qos_tenant == "t");
  EXPECT_EQ(old_view.srv.arrival_us, 0);
  // A traced response (rpcz on): the trace group alone is 24 bytes and
  // cannot be mistaken for the stamps; with them the whole tail rides.
  RpcMeta traced = response(12);
  traced.trace_id = 7;
  traced.span_id = 8;
  EXPECT_EQ(meta_len_of(packed(traced)), 42u + 24u);
  EXPECT_EQ(parsed(packed(traced)).srv.arrival_us, 0);
  traced.srv.arrival_us = 5;
  traced.srv.handler_us = 6;
  traced.srv.done_us = 7;
  EXPECT_EQ(meta_len_of(packed(traced)), 42u + 121u + 16u);
  EXPECT_EQ(parsed(packed(traced)).trace_id, 7u);
  EXPECT_EQ(parsed(packed(traced)).srv.done_us, 7);
}

TEST_CASE(differences_saturate_and_a_hostile_arrival_reads_as_absent) {
  RpcMeta m = response(13);
  m.srv.arrival_us = 1'000;
  m.srv.handler_us = 1'000 + (5ll << 32);  // a 6-hour queue
  m.srv.done_us = m.srv.handler_us + 9;
  const RpcMeta out = parsed(packed(m));
  EXPECT_EQ(out.srv.arrival_us, 1'000);
  EXPECT_EQ(out.srv.handler_us, 1'000 + 0xffffffffll);
  EXPECT_EQ(out.srv.done_us, out.srv.handler_us + 9);
  // An arrival the differences could overflow from: nothing is kept.
  std::string frame = packed(m);
  const uint64_t huge = ~0ull - 5;
  memcpy(&frame[16 + 42], &huge, 8);
  const RpcMeta hostile = parsed(frame);
  EXPECT_EQ(hostile.srv.arrival_us, 0);
  EXPECT_EQ(hostile.srv.handler_us, 0);
  EXPECT_EQ(hostile.srv.done_us, 0);
}

// ---- rejected by the method's limiter ---------------------------------------

namespace {

Event g_entered;
Event g_release;

int64_t var(const std::string& name) {
  std::string out;
  EXPECT(Variable::read_exposed(name, &out));
  return atoll(out.c_str());
}

struct HeldCall {
  Channel* ch;
  int code = -1;
};

void call_held(void* arg) {
  auto* h = static_cast<HeldCall*>(arg);
  Controller cntl;
  cntl.set_timeout_ms(8000);
  IOBuf req, resp;
  h->ch->CallMethod("Hold.One", req, &resp, &cntl);
  h->code = cntl.error_code();
}

}  // namespace

TEST_CASE(a_call_the_limiter_rejects_counts_with_no_handler_time) {
  Server srv;
  EXPECT_EQ(srv.RegisterMethod(
                "Hold.One",
                [](Controller*, const IOBuf&, IOBuf*, Closure done) {
                  const uint32_t seq =
                      g_release.value.load(std::memory_order_acquire);
                  g_entered.value.fetch_add(1, std::memory_order_release);
                  g_entered.wake_all();
                  g_release.wait(seq, monotonic_time_us() + 5'000'000);
                  done();
                }),
            0);
  EXPECT_EQ(srv.SetMethodMaxConcurrency("Hold.One", "1"), 0);
  // From registration on, with no flag set and before any call.
  EXPECT_EQ(var("rpc_server_Hold.One_calls"), 0);
  EXPECT_EQ(var("rpc_server_Hold.One_queue_us"), 0);
  EXPECT_EQ(var("rpc_server_Hold.One_handler_us"), 0);
  EXPECT_EQ(var("rpc_server_Hold.One_send_us"), 0);
  EXPECT_EQ(srv.Start(0), 0);
  Channel ch;
  EXPECT_EQ(ch.Init("127.0.0.1:" + std::to_string(srv.port())), 0);
  HeldCall held{&ch};
  fiber_t fid;
  EXPECT_EQ(fiber_start(&fid, &call_held, &held, 0), 0);
  const int64_t deadline = monotonic_time_us() + 5'000'000;
  while (g_entered.value.load(std::memory_order_acquire) == 0 &&
         monotonic_time_us() < deadline) {
    usleep(1000);
  }
  EXPECT_EQ(g_entered.value.load(), 1u);
  // The one slot is held: the second call never reaches the handler.
  Controller rejected;
  rejected.set_timeout_ms(2000);
  IOBuf req, resp;
  ch.CallMethod("Hold.One", req, &resp, &rejected);
  EXPECT_EQ(rejected.error_code(), kELimit);
  // Its response carried stamps all the same: handler == done.
  EXPECT(rejected.call().srv.arrival_us != 0);
  EXPECT_EQ(rejected.call().srv.handler_us, rejected.call().srv.done_us);
  EXPECT(rejected.call().srv_same_clock);  // 127.0.0.1 is this host
  usleep(30'000);  // the held handler's time, at least
  g_release.value.fetch_add(1, std::memory_order_release);
  g_release.wake_all();
  fiber_join(fid);
  EXPECT_EQ(held.code, 0);
  const int64_t settle = monotonic_time_us() + 2'000'000;
  while (var("rpc_server_Hold.One_calls") < 2 &&
         monotonic_time_us() < settle) {
    usleep(1000);
  }
  EXPECT_EQ(var("rpc_server_Hold.One_calls"), 2);
  const int64_t handler = var("rpc_server_Hold.One_handler_us");
  EXPECT(handler >= 30'000);       // the held call's alone:
  EXPECT(handler < 5'000'000);     // the rejected one added 0
  srv.Stop();
  srv.Join();
}

TEST_MAIN
