// Batched asynchronous call pipeline — the Python data plane's hot path.
//
// One ctypes crossing submits N calls (trpc_batch_submit); an issuing
// fiber replays them IN ORDER as async CallMethods over the existing
// Channel/ClusterChannel (the trpc_bench_echo_rpc fiber-loop shape, so
// the native stack pipelines exactly as the bench proves it can); each
// completion lands in a lock-light MPSC ring that trpc_batch_poll drains
// with the GIL released — one GIL round-trip per batch instead of one
// blocked round-trip per call (the r05 0.2-0.3 GB/s Python-plane ceiling).
//
// Ownership protocol (mirrors the rdma submission-queue discipline from
// "RPC Considered Harmful"'s fabric-lib answer):
//  - request bytes enter the wire path BY REFERENCE (caller deleter runs
//    when the last IOBuf reference drops — which may be after a timeout
//    completion, so the caller must free on the deleter, not on poll);
//  - responses land in the caller's buffer (one native copy off-GIL from
//    the completion fiber, cut over the connection's rails when the
//    response is a one-sided window span — net/rma.h rma_land; blocks and
//    span slots recycled immediately) or ride out
//    as an IOBuf handle the caller owns (view in place, destroy to
//    recycle) — no Python bytes objects at the boundary either way;
//  - a BatchCall is freed at the LAST of {issuer done, completion polled},
//    so cancel/poll/destroy racing an inline completion can never
//    use-after-free (refcount of 2, registry lookups serialized on mu_).
//
// Staged requests: a caller whose request bytes are still on their way to
// the host (brpc_tpu/rpc/batch.py's stager: a device array's D2H started
// by zerocopy.host_view) takes its tokens first (trpc_batch_reserve) and
// hands the calls over when the bytes have landed
// (trpc_batch_submit_staged), each with the time its transfer was started;
// the poll folds that wait in front of the four phases as batch_stage_us.
#include <algorithm>
#include <atomic>
#include <cerrno>
#include <cstring>
#include <deque>
#include <memory>
#include <mutex>
#include <string>
#include <unordered_map>
#include <vector>

#include "base/iobuf.h"
#include "base/time.h"
#include "fiber/event.h"
#include "fiber/fiber.h"
#include "net/channel.h"
#include "net/cluster.h"
#include "net/controller.h"
#include "net/rma.h"
#include "net/span.h"
#include "net/wire_split.h"
#include "stat/latency_recorder.h"
#include "stat/reducer.h"
#include "stat/variable.h"

using namespace trpc;

extern "C" {
// Fixed-layout completion record (mirrored by ctypes.Structure in
// brpc_tpu/rpc/batch.py — field order/sizes are ABI).
struct trpc_batch_completion {
  uint64_t token;
  int32_t status;        // 0 ok, else errno-style code
  uint32_t resp_copied;  // 1 when the response landed in the caller buffer
  uint64_t resp_len;     // full response length in bytes
  void* resp_iobuf;      // non-null: caller owns, free via trpc_iobuf_destroy
  char err[120];
};

// What the stager knows of one call it hands to trpc_batch_submit_staged
// (mirrored by batch.py's BatchStage).
struct trpc_batch_stage {
  uint64_t token;        // from trpc_batch_reserve
  int64_t staged_us;     // monotonic us at which the request was staged
  int64_t fetch_us;      // us the stager was blocked resolving its bytes
  uint64_t fetch_bytes;  // bytes that wait resolved (0: they were there)
  int32_t status;        // non-zero: never issued, completes with it
  const char* err;       // text of that status (nullable)
};
}  // extern "C"

namespace {

struct Batch;

// Pipeline-wide observability (ISSUE 4): the pair of /vars series the
// perf PRs read first — how deep is the window NOW (batch_inflight) and
// how deep has it ever been (batch_depth) — plus the client-side latency
// recorder every batch member reports into (the mirror of the server's
// per-method recorder; the gap between the two is queueing + wire).
std::atomic<int64_t> g_batch_inflight{0};

struct BatchPipelineVars {
  PassiveStatus<long> inflight{[] {
    return static_cast<long>(
        g_batch_inflight.load(std::memory_order_relaxed));
  }};
  Maxer depth;
  LatencyRecorder latency;
  // Phase clocks (see BatchCall's stamps): where a served call waited,
  // summed over the calls handed out by trpc_batch_poll.  All of a
  // call's adds happen at that one moment, on the polling thread, so a
  // delta of these counters over any window holds whole calls only and
  // queue + wire + land + ready is exactly polled_us - enter_us; with
  // stage in front of them the five are polled_us - staged_us.
  Adder calls_polled;
  Adder calls_failed;
  Adder queue_us;
  Adder wire_us;
  Adder land_us;
  Adder ready_us;
  Adder resp_bytes;
  Adder land_copy_bytes;
  Adder land_fanout_bytes;
  Adder submits;
  Adder submit_us;
  Adder staged_calls;
  Adder stage_us;
  Adder stage_fetch_us;
  Adder stage_fetch_bytes;
  // The wire phase cut at the server's stamps (net/wire_split.h), for
  // the polled calls whose response carried them.
  Adder split_calls;
  Adder srv_queue_us;
  Adder srv_handler_us;
  Adder net_us;
  Adder leg_calls;
  Adder req_leg_us;
  BatchPipelineVars() {
    inflight.expose("batch_inflight",
                    "batch-pipeline calls currently in flight, summed "
                    "over all live batches");
    depth.expose("batch_depth",
                 "high-water pipeline depth (max concurrent in-flight "
                 "batch calls) since process start");
    latency.expose("rpc_client_batch",
                   "client-side latency of batch-pipeline calls");
    calls_polled.expose("batch_calls_polled",
                        "batch calls handed out by poll with status 0; "
                        "the divisor of the four batch_*_us phase sums");
    calls_failed.expose("batch_calls_failed",
                        "batch calls handed out by poll with an error "
                        "status; they count in no phase sum");
    queue_us.expose("batch_queue_us",
                    "us from submit entry to just before CallMethod: "
                    "waiting for the issuing fiber and, on one "
                    "connection, for the CallMethods issued before it");
    wire_us.expose("batch_wire_us",
                   "us from just before CallMethod to completion entry: "
                   "request out, server, response in and parsed");
    land_us.expose("batch_land_us",
                   "us the completion fiber spent copying the response "
                   "into the caller's buffer, its rails' join included "
                   "(0 when it landed in place)");
    ready_us.expose("batch_ready_us",
                    "us a finished call lay in the done-ring until the "
                    "caller's poll handed it out");
    resp_bytes.expose("batch_resp_bytes",
                      "response bytes of the calls in batch_calls_polled");
    land_copy_bytes.expose("batch_land_copy_bytes",
                           "the part of batch_resp_bytes that the "
                           "completion fiber's landing copy moved");
    land_fanout_bytes.expose("batch_land_fanout_bytes",
                             "the part of batch_land_copy_bytes whose "
                             "copy ran on more than one rail (one-sided "
                             "window spans of more than one chunk)");
    submits.expose("batch_submits", "accepted trpc_batch_submit crossings");
    submit_us.expose("batch_submit_us",
                     "us inside trpc_batch_submit, entry to return");
    staged_calls.expose("batch_staged_calls",
                        "the calls in batch_calls_polled whose request "
                        "went through the pipeline's stager");
    stage_us.expose("batch_stage_us",
                    "us from the staging of a request (host_view started "
                    "its device-to-host transfer) to the entry of the "
                    "native submit that carried the call");
    stage_fetch_us.expose("batch_stage_fetch_us",
                          "us the stager was blocked waiting for the "
                          "bytes of the requests of batch_staged_calls");
    stage_fetch_bytes.expose("batch_stage_fetch_bytes",
                             "request bytes those waits resolved");
    split_calls.expose("batch_split_calls",
                       "the calls in batch_calls_polled whose response "
                       "carried the server's phase stamps; the divisor of "
                       "batch_srv_queue_us, batch_srv_handler_us and "
                       "batch_net_us");
    srv_queue_us.expose("batch_srv_queue_us",
                        "the part of batch_wire_us between the request "
                        "being whole at the server and its handler being "
                        "entered, by the server's clock");
    srv_handler_us.expose("batch_srv_handler_us",
                          "the part of batch_wire_us between the handler "
                          "being entered and its done() running, by the "
                          "server's clock");
    net_us.expose("batch_net_us",
                  "batch_wire_us of those calls less the server's share "
                  "(arrival to done): the request's and the response's "
                  "leg together, valid whatever the peer's clock");
    leg_calls.expose("batch_leg_calls",
                     "the calls in batch_split_calls whose connection's "
                     "two ends read one clock (the shm ring, or a peer "
                     "address of this host); the divisor of "
                     "batch_req_leg_us");
    req_leg_us.expose("batch_req_leg_us",
                      "us from just before CallMethod to the request "
                      "being whole at the server; the response's leg is "
                      "batch_net_us less this, over the same calls");
  }
};

BatchPipelineVars& batch_vars() {
  // Leaked with the registry: completion fibers outlive static dtors.
  static auto* v = new BatchPipelineVars();
  return *v;
}

// One trpc_batch_submit's span: the parent every member's client span
// links under, carrying the submitter's ambient trace (so a Python
// trace() around submit+poll owns the whole batch).  Submitted into the
// ring when the LAST member completes — the span covers the window from
// submit to final completion.
struct SubmitGroup {
  Span* span = nullptr;
  std::atomic<int64_t> remaining{0};
  // First member failure: the batch span must not read error_code 0
  // when its members failed (a trace filtered for errors would skip
  // exactly the failing batches).
  std::atomic<int32_t> first_error{0};
  std::atomic<int64_t> failures{0};
};

struct BatchCall {
  Batch* batch = nullptr;
  uint64_t token = 0;
  std::string method;
  IOBuf request;
  IOBuf response;
  Controller cntl;
  void* resp_buf = nullptr;  // caller-provided landing buffer (optional)
  size_t resp_cap = 0;
  int64_t timeout_ms = 0;
  SubmitGroup* group = nullptr;  // non-null iff rpcz was on at submit
  // Phase clocks, each one monotonic_time_us() reading: CLOCK_MONOTONIC,
  // the clock of Python's time.perf_counter (so of the benchmark's window
  // and spans) and of rpcz and the timeline.  enter <= issue <= reply <=
  // landed <= polled; the fifth is read in trpc_batch_poll, which folds
  // the differences into BatchPipelineVars.  Plain fields: the issuer
  // hand-off, the done-ring's release push and poll's acquire pop
  // already order every write before the poll that reads it.  The
  // server's own three readings between issue and reply (arrival,
  // handler, done) come back in the response and lie in
  // cntl.call().srv; the poll cuts wire at them (net/wire_split.h).
  int64_t enter_us = 0;  // entry of the trpc_batch_submit that made it
  // Staged calls only (else 0): when the request's transfer to the host
  // was started, how long the stager was blocked on it, for what bytes.
  int64_t staged_us = 0;
  int64_t fetch_us = 0;
  int64_t fetch_bytes = 0;
  // Stamped just before CallMethod — also the batch's own clock for the
  // rpc_client_batch recorder.  (Channel stamps cntl.call().start_us,
  // but ClusterChannel never does; relying on it dropped every cluster
  // member from the recorder.)
  int64_t issue_us = 0;
  int64_t reply_us = 0;   // entry of on_call_done: the response is parsed
  int64_t landed_us = 0;  // after the copy into resp_buf; else == reply_us
  size_t land_copied = 0;  // bytes that copy moved (0: in place / no buf)
  bool land_fanned = false;  // that copy ran on more than one rail
  std::atomic<bool> canceled{false};
  // Published by the issuer after CallMethod returns, so a cancel can
  // reach the in-flight fid (0 = not yet issued / cluster-internal).
  std::atomic<fid_t> issued_cid{0};
  // Completion record, written exactly once on the completion path.
  int32_t status = 0;
  bool resp_copied = false;
  size_t resp_len = 0;
  std::string err;
  BatchCall* done_next = nullptr;  // MPSC completion-ring link
  // Two owners: the issuing fiber and the completion->ring->poll chain.
  std::atomic<int> refs{2};
};

void unref(BatchCall* c) {
  if (c->refs.fetch_sub(1, std::memory_order_acq_rel) == 1) {
    delete c;
  }
}

struct Batch {
  void* channel = nullptr;
  bool is_cluster = false;
  std::atomic<bool> closing{false};
  std::atomic<uint64_t> next_token{1};
  std::atomic<int64_t> outstanding{0};  // submitted, not yet in the ring
  std::atomic<int> issuers{0};          // live issuing fibers
  std::atomic<BatchCall*> done_head{nullptr};  // MPSC LIFO of completions
  Event ev;  // value bumps on every completion / issuer exit
  std::mutex mu_;  // token registry (per batch-op, never per byte)
  std::unordered_map<uint64_t, BatchCall*> calls;
  std::mutex poll_mu_;       // serializes consumers
  BatchCall* drained = nullptr;  // consumer-local FIFO (reversed chain)
  // Single-connection channels: calls waiting for the one issuing fiber,
  // in submit order over ALL submits (the stager crosses once per landed
  // request, and two issuing fibers could swap their first CallMethods).
  std::mutex issue_mu_;
  std::deque<BatchCall*> issue_q;
  bool issuer_live = false;
};

// Completion path — runs on whatever fiber finishes the call (dispatch
// fiber inline for responses, timeout fiber, canceller).  Bounded
// framework work only: status capture, the landing copy of a response
// that is not in place (rma_land: a one-sided window span is copied out
// by the connection's rails and this fiber joins them, anything else is
// one copy_to here), one atomic push, one wake.
void on_call_done(BatchCall* c) {
  Batch* b = c->batch;
  c->reply_us = c->landed_us = monotonic_time_us();
  // Client-side latency into the shared recorder (issue_us 0 means the
  // call failed before issue — nothing to time).
  if (c->issue_us != 0) {
    batch_vars().latency << c->reply_us - c->issue_us;
  }
  g_batch_inflight.fetch_sub(1, std::memory_order_relaxed);
  SubmitGroup* g = c->group;
  if (g != nullptr) {
    if (c->cntl.Failed()) {
      int32_t expect = 0;
      const int32_t code =
          c->cntl.error_code() != 0 ? c->cntl.error_code() : -1;
      g->first_error.compare_exchange_strong(expect, code,
                                             std::memory_order_relaxed);
      g->failures.fetch_add(1, std::memory_order_relaxed);
    }
    if (g->remaining.fetch_sub(1, std::memory_order_acq_rel) == 1) {
      // Last member: the batch span's window closes here, carrying the
      // first member failure (if any) as its error code.
      const int64_t failed = g->failures.load(std::memory_order_relaxed);
      if (failed > 0) {
        span_annotate(g->span,
                      std::to_string(failed) + " member(s) failed");
      }
      submit_span(g->span, g->first_error.load(std::memory_order_relaxed));
      delete g;
    }
  }
  if (c->cntl.Failed()) {
    c->status = c->cntl.error_code() != 0 ? c->cntl.error_code() : -1;
    c->err = c->cntl.error_text();
  } else if (c->resp_buf != nullptr) {
    const size_t n = c->response.size();
    c->resp_len = n;
    if (n > c->resp_cap) {
      c->status = EMSGSIZE;
      c->err = "response larger than caller buffer";
    } else {
      // Striped responses may ALREADY be in the caller's buffer (the
      // stripe layer landed chunks there in place); copying a buffer
      // onto itself would be both wasted bandwidth and UB.
      const bool in_place =
          c->response.block_count() == 1 &&
          c->response.ref_at(0).block->data + c->response.ref_at(0).offset ==
              c->resp_buf;
      if (!in_place) {
        c->land_fanned = rma_land(c->response, c->resp_buf, n) > 1;
        c->land_copied = n;
        c->landed_us = monotonic_time_us();  // after the rails' join
      }
      c->resp_copied = true;
      c->response.clear();  // recycle pool blocks / span slots now
    }
  } else {
    c->resp_len = c->response.size();
  }
  BatchCall* head = b->done_head.load(std::memory_order_relaxed);
  do {
    c->done_next = head;
  } while (!b->done_head.compare_exchange_weak(
      head, c, std::memory_order_release, std::memory_order_relaxed));
  // Wake FIRST, decrement LAST: trpc_batch_destroy frees the Batch as
  // soon as it observes outstanding==0 && issuers==0, so the decrement
  // must be this thread's final access to *b — signalling after it
  // would race the delete.  A waiter that saw the wake before the
  // decrement re-checks on its (bounded) wait timeout.
  b->ev.value.fetch_add(1, std::memory_order_release);
  b->ev.wake_all();
  b->outstanding.fetch_sub(1, std::memory_order_release);
}

// Issues ONE call asynchronously (the per-call body shared by both issue
// strategies).  Consumes the issuer reference.
void issue_call(Batch* b, BatchCall* c) {
  if (c->cntl.Failed()) {  // handed over already failed (staging)
    on_call_done(c);
    unref(c);
    return;
  }
  if (b->closing.load(std::memory_order_acquire) ||
      c->canceled.load(std::memory_order_acquire)) {
    c->cntl.SetFailed(ECANCELED, "canceled before issue");
    on_call_done(c);
    unref(c);
    return;
  }
  if (c->timeout_ms > 0) {
    c->cntl.set_timeout_ms(c->timeout_ms);
  }
  // Trace linkage: the member's client span (created inside CallMethod
  // when rpcz is on) must parent under the batch's submit span, and the
  // issuing context here is a fiber (or, pool-exhausted, the caller's
  // pthread) with its OWN ambient slot — install the batch span around
  // the issue and restore after (the pool-exhausted inline path would
  // otherwise leak it into the caller's thread-local context).
  uint64_t prev_trace = 0;
  uint64_t prev_span = 0;
  if (c->group != nullptr) {
    get_ambient_trace(&prev_trace, &prev_span);
    set_ambient_trace(c->group->span->trace_id, c->group->span->span_id);
  }
  const bool restore_ambient = c->group != nullptr;
  c->issue_us = monotonic_time_us();
  if (!b->is_cluster && c->resp_buf != nullptr) {
    // Stripe-aware landing (net/stripe.h): a striped response's chunks
    // memcpy straight into the caller's buffer instead of bouncing
    // through an arena block — the completion below detects the in-place
    // view and skips its copy.
    c->cntl.call().land_buf = c->resp_buf;
    c->cntl.call().land_cap = c->resp_cap;
  }
  BatchCall* cc = c;
  Closure done = [cc] { on_call_done(cc); };
  if (b->is_cluster) {
    static_cast<ClusterChannel*>(b->channel)
        ->CallMethod(c->method, c->request, &c->response, &c->cntl,
                     std::move(done));
  } else {
    static_cast<Channel*>(b->channel)
        ->CallMethod(c->method, c->request, &c->response, &c->cntl,
                     std::move(done));
  }
  if (restore_ambient) {
    // c->group may already be freed (inline completion of the last
    // member) — restore from the saved ids, never through the group.
    set_ambient_trace(prev_trace, prev_span);
  }
  // Single-channel async calls return with the fid live; publish it so
  // cancel can reach the in-flight call.  (Cluster members issue on
  // their own fiber — cancel covers them pre-issue only.)
  //
  // seq_cst on BOTH store/load pairs here and in trpc_batch_cancel: this
  // is a store-then-load-on-the-other's-atomic handshake (Dekker), and
  // with release/acquire both sides can legally miss — cancel would
  // report success while the call runs to its timeout (the same class
  // of race PR 2's writer handoff fixed with seq_cst).
  c->issued_cid.store(c->cntl.call_id(), std::memory_order_seq_cst);
  if (c->canceled.load(std::memory_order_seq_cst)) {
    // Cancel raced the issue: the flag alone missed the fid, so cancel
    // it here.  Stale fids (call already completed) are no-ops.
    StartCancel(c->issued_cid.load(std::memory_order_seq_cst));
  }
  unref(c);
}

void issuer_exit(Batch* b) {
  // Same ordering contract as on_call_done: the decrement is the final
  // access to *b, because destroy may free the Batch the moment it
  // reads issuers == 0.
  b->ev.value.fetch_add(1, std::memory_order_release);
  b->ev.wake_all();
  b->issuers.fetch_sub(1, std::memory_order_release);
}

// FIFO strategy (single-connection channels): replays the submitted
// calls IN ORDER on one fiber, so issue order IS wire order (one writer,
// FIFO write queue).  Completions are correlation-matched, not ordered.
// The fiber lives while issue_q has calls; the submit that finds none
// live starts the next.
void issuer_main(void* p) {
  auto* b = static_cast<Batch*>(p);
  for (;;) {
    BatchCall* c = nullptr;
    {
      std::lock_guard<std::mutex> g(b->issue_mu_);
      if (b->issue_q.empty()) {
        b->issuer_live = false;
        break;
      }
      c = b->issue_q.front();
      b->issue_q.pop_front();
    }
    issue_call(b, c);
  }
  issuer_exit(b);
}

// Fan-out strategy (pooled/short/cluster channels): one issue fiber per
// call, bulk-published with ONE ParkingLot signal (fiber_start_batch),
// so the inline request writes overlap across their per-call sockets
// instead of serializing 8x4MB on one issuing fiber.  Wire order across
// distinct connections is meaningless, so nothing is lost.
void issue_one_main(void* p) {
  auto* c = static_cast<BatchCall*>(p);
  Batch* b = c->batch;
  issue_call(b, c);
  issuer_exit(b);
}

// Pops the next completion in FIFO order (consumer-local reversal of the
// LIFO ring).  poll_mu_ held by the caller.
BatchCall* pop_completion(Batch* b) {
  if (b->drained == nullptr) {
    BatchCall* chain =
        b->done_head.exchange(nullptr, std::memory_order_acquire);
    while (chain != nullptr) {  // reverse LIFO -> FIFO
      BatchCall* next = chain->done_next;
      chain->done_next = b->drained;
      b->drained = chain;
      chain = next;
    }
  }
  BatchCall* c = b->drained;
  if (c != nullptr) {
    b->drained = c->done_next;
  }
  return c;
}

void fill_completion(BatchCall* c, trpc_batch_completion* out) {
  out->token = c->token;
  out->status = c->status;
  out->resp_copied = c->resp_copied ? 1 : 0;
  out->resp_len = c->resp_len;
  out->resp_iobuf = nullptr;
  if (!c->resp_copied && c->response.size() > 0) {
    out->resp_iobuf = new IOBuf(std::move(c->response));
  }
  out->err[0] = '\0';
  if (!c->err.empty()) {
    strncpy(out->err, c->err.c_str(), sizeof(out->err) - 1);
    out->err[sizeof(out->err) - 1] = '\0';
  }
}

// One drain's worth of phase sums: gathered while poll hands calls out,
// added to the registry once per drain (a dozen thread-local adds a poll,
// not a dozen a call).
struct PhaseSums {
  int64_t polled = 0;
  int64_t failed = 0;
  int64_t queue_us = 0;
  int64_t wire_us = 0;
  int64_t land_us = 0;
  int64_t ready_us = 0;
  int64_t resp_bytes = 0;
  int64_t land_copy_bytes = 0;
  int64_t land_fanout_bytes = 0;
  int64_t staged = 0;
  int64_t stage_us = 0;
  int64_t fetch_us = 0;
  int64_t fetch_bytes = 0;
  int64_t split = 0;
  int64_t srv_queue_us = 0;
  int64_t srv_handler_us = 0;
  int64_t net_us = 0;
  int64_t legs = 0;
  int64_t req_leg_us = 0;

  void count(const BatchCall* c, int64_t polled_us) {
    if (c->status != 0) {
      ++failed;  // in no sum; it may never have been issued
      return;
    }
    // Status 0: CallMethod ran and on_call_done saw a response, so
    // every stamp is set.
    ++polled;
    queue_us += c->issue_us - c->enter_us;
    wire_us += c->reply_us - c->issue_us;
    land_us += c->landed_us - c->reply_us;
    // One reading serves a whole drain, and a call may land after it
    // and still be popped by that drain: it waited no time, not less.
    ready_us += std::max<int64_t>(polled_us - c->landed_us, 0);
    resp_bytes += static_cast<int64_t>(c->resp_len);
    land_copy_bytes += static_cast<int64_t>(c->land_copied);
    if (c->land_fanned) {
      land_fanout_bytes += static_cast<int64_t>(c->land_copied);
    }
    // A response without the stamps (an older peer, a cluster member's
    // internal controller) splits nothing.
    const Controller::CallState& call = c->cntl.call();
    const WireSplit w =
        split_wire(c->issue_us, c->reply_us, call.srv, call.srv_same_clock);
    if (w.split) {
      ++split;
      srv_queue_us += w.srv_queue_us;
      srv_handler_us += w.srv_handler_us;
      net_us += w.net_us;
      if (w.legs) {
        ++legs;
        req_leg_us += w.req_leg_us;
      }
    }
    if (c->staged_us != 0) {
      ++staged;
      stage_us += c->enter_us - c->staged_us;
      fetch_us += c->fetch_us;
      fetch_bytes += c->fetch_bytes;
    }
  }

  void publish() const {
    BatchPipelineVars& v = batch_vars();
    if (failed != 0) {
      v.calls_failed << failed;
    }
    if (polled == 0) {
      return;
    }
    v.calls_polled << polled;
    v.queue_us << queue_us;
    v.wire_us << wire_us;
    v.land_us << land_us;
    v.ready_us << ready_us;
    v.resp_bytes << resp_bytes;
    v.land_copy_bytes << land_copy_bytes;
    v.land_fanout_bytes << land_fanout_bytes;
    if (split != 0) {
      v.split_calls << split;
      v.srv_queue_us << srv_queue_us;
      v.srv_handler_us << srv_handler_us;
      v.net_us << net_us;
      if (legs != 0) {
        v.leg_calls << legs;
        v.req_leg_us << req_leg_us;
      }
    }
    if (staged == 0) {
      return;
    }
    v.staged_calls << staged;
    v.stage_us << stage_us;
    v.stage_fetch_us << fetch_us;
    v.stage_fetch_bytes << fetch_bytes;
  }
};

// The body of both submits.  `stages` (nullable, n entries) carries the
// reserved token and the staging facts of each call; without it every
// call takes a fresh token.
size_t submit_calls(Batch* b, const char* method, const void* const* reqs,
                    const size_t* req_lens, void* const* resp_bufs,
                    const size_t* resp_caps, size_t n, int64_t timeout_ms,
                    void (*req_deleter)(void*, void*),
                    void* const* req_deleter_ctxs,
                    const trpc_batch_stage* stages, uint64_t* tokens_out) {
  const int64_t enter_us = monotonic_time_us();
  if (b == nullptr || n == 0 || method == nullptr ||
      b->closing.load(std::memory_order_acquire)) {
    return 0;
  }
  // rpcz: one parent span per submit.  start_span resolves the parent
  // from THIS thread's ambient context — ctypes callers run submit on
  // their own pthread, where a Python trace()/trpc_trace_set installed
  // it — so the whole batch hangs under the user's trace.
  SubmitGroup* group = nullptr;
  if (rpcz_enabled()) {
    group = new SubmitGroup();
    group->span =
        start_span(/*server_side=*/false, std::string("batch:") + method);
    span_annotate(group->span, "submit n=" + std::to_string(n));
    group->remaining.store(static_cast<int64_t>(n),
                           std::memory_order_relaxed);
  }
  const int64_t now_inflight =
      g_batch_inflight.fetch_add(static_cast<int64_t>(n),
                                 std::memory_order_relaxed) +
      static_cast<int64_t>(n);
  batch_vars().depth << now_inflight;
  std::vector<BatchCall*> calls;
  calls.reserve(n);
  for (size_t i = 0; i < n; ++i) {
    auto* c = new BatchCall();
    c->batch = b;
    c->group = group;
    c->enter_us = enter_us;
    if (stages != nullptr) {
      const trpc_batch_stage& st = stages[i];
      c->token = st.token;
      c->staged_us = st.staged_us;
      c->fetch_us = st.fetch_us;
      c->fetch_bytes = static_cast<int64_t>(st.fetch_bytes);
      if (st.status != 0) {
        c->cntl.SetFailed(st.status,
                          st.err != nullptr ? st.err : "request staging failed");
      }
    } else {
      c->token = b->next_token.fetch_add(1, std::memory_order_relaxed);
    }
    c->method = method;
    if (reqs != nullptr && reqs[i] != nullptr && req_lens[i] > 0) {
      if (req_deleter != nullptr) {
        c->request.append_user_data(
            const_cast<void*>(reqs[i]), req_lens[i], req_deleter,
            req_deleter_ctxs != nullptr ? req_deleter_ctxs[i] : nullptr);
      } else {
        c->request.append(reqs[i], req_lens[i]);
      }
    }
    if (resp_bufs != nullptr && resp_bufs[i] != nullptr) {
      c->resp_buf = resp_bufs[i];
      c->resp_cap = resp_caps != nullptr ? resp_caps[i] : 0;
    }
    c->timeout_ms = timeout_ms;
    // The completion closure is bounded framework work (memcpy + atomic
    // push + wake): safe to run inline on a dispatch fiber, no per-call
    // completion-fiber spawn.
    c->cntl.set_done_inline_safe(true);
    if (tokens_out != nullptr) {
      tokens_out[i] = c->token;
    }
    calls.push_back(c);
  }
  {
    std::lock_guard<std::mutex> g(b->mu_);
    for (BatchCall* c : calls) {
      b->calls.emplace(c->token, c);
    }
  }
  b->outstanding.fetch_add(static_cast<int64_t>(n),
                           std::memory_order_release);
  // Single-connection channels get ONE issuing fiber (issue order = wire
  // order); everything with per-call connections fans out one fiber per
  // call so their inline request writes run concurrently.
  const bool fifo =
      !b->is_cluster &&
      static_cast<Channel*>(b->channel)->conn_type_raw() == 0;
  if (fifo) {
    bool start = false;
    {
      std::lock_guard<std::mutex> g(b->issue_mu_);
      b->issue_q.insert(b->issue_q.end(), calls.begin(), calls.end());
      if (!b->issuer_live) {
        b->issuer_live = start = true;
        b->issuers.fetch_add(1, std::memory_order_release);
      }
    }
    if (start && fiber_start(nullptr, issuer_main, b, 0) != 0) {
      issuer_main(b);  // pool exhausted: issue on the caller (GIL
                       // already released by ctypes), never drop
    }
  } else {
    b->issuers.fetch_add(static_cast<int>(n), std::memory_order_release);
    const size_t started = fiber_start_batch(
        issue_one_main, reinterpret_cast<void* const*>(calls.data()), n, 0);
    for (size_t i = started; i < n; ++i) {
      issue_one_main(calls[i]);  // pool exhausted: issue inline
    }
  }
  batch_vars().submits << 1;
  batch_vars().submit_us << monotonic_time_us() - enter_us;
  return n;
}

}  // namespace

extern "C" {

// channel: a trpc_channel_* handle (is_cluster == 0) or a trpc_cluster_*
// handle (is_cluster != 0).  The channel must outlive the batch's
// in-flight calls; polling buffered completions needs no channel, so
// destroying the channel AFTER the last call completed and BEFORE the
// last poll is safe.
void* trpc_batch_create(void* channel, int is_cluster) {
  if (channel == nullptr) {
    return nullptr;
  }
  batch_vars();  // register batch_inflight/batch_depth before traffic
  auto* b = new Batch();
  b->channel = channel;
  b->is_cluster = is_cluster != 0;
  return b;
}

// Submits n calls in ONE crossing.  reqs[i]/req_lens[i] are the request
// payloads; with req_deleter set, the bytes enter the wire path by
// reference and req_deleter(reqs[i], req_deleter_ctxs[i]) runs when the
// last IOBuf reference drops (buffer-protocol zero-copy); with a null
// deleter the bytes are copied here.  resp_bufs/resp_caps (either array
// nullable, entries nullable) are caller-owned landing buffers: the
// response is memcpy'd there natively on the completion fiber and the
// pool blocks recycle immediately.  timeout_ms <= 0 uses the channel
// default.  Writes per-call tokens to tokens_out; returns the number of
// calls accepted (0 after close).
size_t trpc_batch_submit(void* batch, const char* method,
                         const void* const* reqs, const size_t* req_lens,
                         void* const* resp_bufs, const size_t* resp_caps,
                         size_t n, int64_t timeout_ms,
                         void (*req_deleter)(void*, void*),
                         void* const* req_deleter_ctxs,
                         uint64_t* tokens_out) {
  return submit_calls(static_cast<Batch*>(batch), method, reqs, req_lens,
                      resp_bufs, resp_caps, n, timeout_ms, req_deleter,
                      req_deleter_ctxs, nullptr, tokens_out);
}

// Takes n tokens for calls that trpc_batch_submit_staged will carry
// later: the caller can name its calls (return them, pin buffers under
// them, cancel them) before their request bytes exist on the host.
// Returns n, or 0 once the batch is closing.
size_t trpc_batch_reserve(void* batch, size_t n, uint64_t* tokens_out) {
  auto* b = static_cast<Batch*>(batch);
  if (b == nullptr || tokens_out == nullptr ||
      b->closing.load(std::memory_order_acquire)) {
    return 0;
  }
  const uint64_t first = b->next_token.fetch_add(n, std::memory_order_relaxed);
  for (size_t i = 0; i < n; ++i) {
    tokens_out[i] = first + i;
  }
  return n;
}

// trpc_batch_submit for calls whose tokens were reserved: stages[i] names
// call i's token, when its request was staged and what the stager waited
// for it; a call handed over with a non-zero status is never issued and
// completes through the ring with that status, in its turn.
size_t trpc_batch_submit_staged(void* batch, const char* method,
                                const void* const* reqs,
                                const size_t* req_lens,
                                void* const* resp_bufs,
                                const size_t* resp_caps, size_t n,
                                int64_t timeout_ms,
                                void (*req_deleter)(void*, void*),
                                void* const* req_deleter_ctxs,
                                const trpc_batch_stage* stages) {
  if (stages == nullptr) {
    return 0;
  }
  return submit_calls(static_cast<Batch*>(batch), method, reqs, req_lens,
                      resp_bufs, resp_caps, n, timeout_ms, req_deleter,
                      req_deleter_ctxs, stages, nullptr);
}

// Drains up to max completion records, blocking the calling PTHREAD (not
// a fiber — ctypes has already released the GIL) until at least one is
// available or timeout_ms elapses (0 = non-blocking, < 0 = wait
// forever).  Completions already buffered in the ring remain drainable
// after the channel is closed.  The consumer mutex covers only the
// DRAIN, never the wait — a parked infinite poller must not block a
// concurrent non-blocking poll (or destroy) behind it.  A quiesced
// batch wakes parked pollers and they drain out with whatever is left.
// Each drain stamps the calls it hands out (polled_us) and folds their
// phase clocks into the batch_* counters (PhaseSums).
// Returns the number of records written.
size_t trpc_batch_poll(void* batch, trpc_batch_completion* out, size_t max,
                       int64_t timeout_ms) {
  auto* b = static_cast<Batch*>(batch);
  if (b == nullptr || out == nullptr || max == 0) {
    return 0;
  }
  const int64_t deadline_us =
      timeout_ms < 0 ? -1 : monotonic_time_us() + timeout_ms * 1000;
  size_t n = 0;
  for (;;) {
    const uint32_t seq = b->ev.value.load(std::memory_order_acquire);
    {
      std::lock_guard<std::mutex> consumer(b->poll_mu_);
      PhaseSums sums;
      int64_t polled_us = 0;  // one reading per drain that finds a call
      while (n < max) {
        BatchCall* c = pop_completion(b);
        if (c == nullptr) {
          break;
        }
        if (polled_us == 0) {
          polled_us = monotonic_time_us();
        }
        fill_completion(c, &out[n]);
        sums.count(c, polled_us);
        ++n;
        std::lock_guard<std::mutex> g(b->mu_);
        b->calls.erase(c->token);
        unref(c);
      }
      sums.publish();
    }
    if (n > 0 || timeout_ms == 0) {
      return n;
    }
    if (deadline_us >= 0 && monotonic_time_us() >= deadline_us) {
      return n;
    }
    if (b->closing.load(std::memory_order_acquire)) {
      return n;  // quiesced and the ring is dry: drain out, don't re-park
    }
    b->ev.wait(seq, deadline_us);
  }
}

// Cancels one in-flight member (the existing StartCancel path: it
// completes with ECANCELED exactly once; a cancel racing the response is
// a stale-fid no-op and the call completes normally).  Cluster members
// cancel pre-issue only (their attempts run on internal controllers).
// Returns 0 when the token was live, -1 when unknown/already polled.
int trpc_batch_cancel(void* batch, uint64_t token) {
  auto* b = static_cast<Batch*>(batch);
  if (b == nullptr) {
    return -1;
  }
  fid_t cid = 0;
  {
    std::lock_guard<std::mutex> g(b->mu_);
    auto it = b->calls.find(token);
    if (it == b->calls.end()) {
      return -1;
    }
    // seq_cst pair with issue_call's publish/check (Dekker handshake —
    // see the comment there): at least one side must see the other.
    it->second->canceled.store(true, std::memory_order_seq_cst);
    cid = it->second->issued_cid.load(std::memory_order_seq_cst);
  }
  StartCancel(cid);  // outside mu_: the error path may complete inline
  return 0;
}

// Calls submitted but not yet drained by poll (in flight + ring).
size_t trpc_batch_outstanding(void* batch) {
  auto* b = static_cast<Batch*>(batch);
  if (b == nullptr) {
    return 0;
  }
  std::lock_guard<std::mutex> g(b->mu_);
  return b->calls.size();
}

// Calls still IN FLIGHT (not yet completed into the ring).  Zero means
// every submitted call has settled — the channel is no longer needed by
// this batch and closing it is safe; buffered completions remain
// drainable.
size_t trpc_batch_inflight(void* batch) {
  auto* b = static_cast<Batch*>(batch);
  if (b == nullptr) {
    return 0;
  }
  const int64_t n = b->outstanding.load(std::memory_order_acquire);
  return n > 0 ? static_cast<size_t>(n) : 0;
}

// Quiesces the batch WITHOUT freeing it: rejects further submits,
// cancels everything in flight, waits for issuers and completions to
// settle, then wakes any parked poller so it can observe the closed
// state and drain out.  After this returns the batch no longer touches
// its channel — buffered completions remain pollable, so the channel
// may be destroyed while results are still being harvested.
void trpc_batch_quiesce(void* batch) {
  auto* b = static_cast<Batch*>(batch);
  if (b == nullptr) {
    return;
  }
  b->closing.store(true, std::memory_order_seq_cst);
  {
    std::lock_guard<std::mutex> g(b->mu_);
    for (auto& kv : b->calls) {
      // Same seq_cst handshake as trpc_batch_cancel.
      kv.second->canceled.store(true, std::memory_order_seq_cst);
      StartCancel(kv.second->issued_cid.load(std::memory_order_seq_cst));
    }
  }
  for (;;) {
    const uint32_t seq = b->ev.value.load(std::memory_order_acquire);
    if (b->outstanding.load(std::memory_order_acquire) == 0 &&
        b->issuers.load(std::memory_order_acquire) == 0) {
      break;
    }
    b->ev.wait(seq, monotonic_time_us() + 50 * 1000);
  }
  // Kick parked pollers: they re-check closing and return instead of
  // re-parking on a batch that will produce nothing further.
  b->ev.value.fetch_add(1, std::memory_order_release);
  b->ev.wake_all();
}

// Quiesce, then free unpolled completions (their response pool blocks
// recycle) and destroy the batch.  Safe with calls in flight; callers
// must ensure no poller is INSIDE trpc_batch_poll when this runs (the
// Python wrapper quiesces first, waits for its pollers to drain out,
// then destroys).
void trpc_batch_destroy(void* batch) {
  auto* b = static_cast<Batch*>(batch);
  if (b == nullptr) {
    return;
  }
  trpc_batch_quiesce(b);
  {
    std::lock_guard<std::mutex> consumer(b->poll_mu_);
    for (BatchCall* c = pop_completion(b); c != nullptr;
         c = pop_completion(b)) {
      std::lock_guard<std::mutex> g(b->mu_);
      b->calls.erase(c->token);
      unref(c);
    }
  }
  delete b;
}

}  // extern "C"
