#include "net/messenger.h"

#include <errno.h>

#include <algorithm>

#include "base/flags.h"
#include "base/logging.h"
#include "base/time.h"
#include "base/tls_cache.h"
#include "fiber/analysis.h"
#include "fiber/fiber.h"
#include "net/hotpath_stats.h"
#include "net/protocol.h"
#include "net/qos.h"
#include "net/stream.h"
#include "net/rma.h"
#include "net/stripe.h"
#include "stat/timeline.h"

namespace trpc {

namespace {

constexpr size_t kReadChunk = 512 * 1024;
// Ceiling on one readv when the parser hinted a large frame remainder:
// big enough to amortize per-syscall cost, small enough that the cut
// budget below still interleaves other sockets' work.
constexpr size_t kMaxBulkRead = 8 * 1024 * 1024;

// Per-readable-sweep cut budget: after this many bytes are read+parsed
// in one sweep, the read fiber YIELDS its worker (re-armed, back of the
// run queue) so one 64MB socket cannot head-of-line-block the dispatch
// fibers of small RPCs queued behind it on the same worker.
Flag* cut_budget_flag() {
  static Flag* f = [] {
    Flag* flag = Flag::define_int64(
        "trpc_messenger_cut_budget", 8ll << 20,
        "bytes one readable sweep may read+parse before yielding its "
        "worker to queued fibers ([0, 1GB]; 0 = never yield)");
    if (flag != nullptr) {
      // Range validator + introspectable bounds (the tuner's AIMD rule
      // actuates this knob and clamps into the declared range).
      flag->set_int_range(0, 1ll << 30);
    }
    return flag;
  }();
  return f;
}

// Eager definition (settable before the first readable sweep).
[[maybe_unused]] Flag* const g_cut_budget_flag_eager = cut_budget_flag();

thread_local bool tls_inline_dispatch = false;

// TLS InputMessage freelist: one is allocated per parsed message — at
// 100k+ qps the malloc/free pair plus the meta's string/vector churn is
// measurable (r5 profile).  Same pattern as the WriteNode cache:
// cross-thread imbalance degrades to plain malloc.
struct InputMessageCacheTag {};

void drain_input_message(void*& m) { delete static_cast<InputMessage*>(m); }

std::vector<void*>* tls_msg_cache() {
  return TlsFreeCache<void*, InputMessageCacheTag>::get(
      &drain_input_message);
}

constexpr size_t kMaxCachedMessages = 64;

InputMessage* alloc_input_message() {
  std::vector<void*>* cache = tls_msg_cache();
  if (cache != nullptr && !cache->empty()) {
    auto* m = static_cast<InputMessage*>(cache->back());
    cache->pop_back();
    return m;
  }
  return new InputMessage();
}

void free_input_message(InputMessage* m) {
  std::vector<void*>* cache = tls_msg_cache();
  if (cache != nullptr && cache->size() < kMaxCachedMessages) {
    // Release payload refs and per-call state NOW; meta keeps its
    // string/vector capacity for reuse.
    m->payload.clear();
    m->ctx.reset();
    m->meta.reset();
    m->socket = 0;
    m->arrival_us = 0;
    cache->push_back(m);
    return;
  }
  delete m;
}

// Shared by the inline (first-of-batch) and fiber dispatch paths.
void process_parsed_message(InputMessage* msg) {
  const Protocol* p = protocol_at(0);  // resolved below via pinned index
  Socket* s = Socket::Address(msg->socket);
  if (s != nullptr) {
    p = protocol_at(s->pinned_protocol);
    s->Dereference();
  }
  if (p != nullptr) {
    // kResponse is the only client-bound type; kAuth etc. are served.
    if (msg->meta.type == RpcMeta::kResponse) {
      p->process_response(std::move(*msg));
    } else {
      p->process_request(std::move(*msg));
    }
  }
  free_input_message(msg);
}

void process_message_fiber(void* arg) {
  process_parsed_message(static_cast<InputMessage*>(arg));
}

// Upper bound on messages batched per dispatch round (also the bulk-
// enqueue fan-out cap; the reference flushes unconditionally at the end
// of each read sweep, input_messenger.cpp:307-309).
constexpr size_t kDispatchBatch = 64;

// Batch of concurrent-protocol messages cut in one sweep.  Flushing
// bulk-enqueues fiber-bound messages through the scheduler's
// single-signal path FIRST, then — when the first message is a client
// RESPONSE — runs it INLINE on this dispatch fiber: the common
// single-response event (sync small RPC) completes with zero fiber
// spawns and zero ParkingLot signals.  Requests are NEVER run inline:
// a handler is arbitrary user code and may park for seconds, and an
// inline handler would serialize every later message on this connection
// behind it (a response completion only wakes the waiting call — bounded
// framework work).
struct DispatchBatch {
  InputMessage* msgs[kDispatchBatch];
  size_t n = 0;

  void flush() {
    if (n == 0) {
      return;
    }
    HotPathVars& hv = hotpath_vars();
    hv.dispatch_batches << 1;
    hv.dispatch_msgs << static_cast<int64_t>(n);
    hv.dispatch_max << static_cast<int64_t>(n);
    if (hotpath_sample16()) {
      hv.dispatch_batch << static_cast<int64_t>(n);
    }
    InputMessage* inline_msg = nullptr;
    size_t spawn_from = 0;
    if (msgs[0]->meta.type == RpcMeta::kResponse) {
      inline_msg = msgs[0];
      spawn_from = 1;
      hv.dispatch_inline << 1;
    }
    if (n > spawn_from) {
      void* args[kDispatchBatch];
      for (size_t i = spawn_from; i < n; ++i) {
        args[i - spawn_from] = msgs[i];
      }
      const size_t started = fiber_start_batch(process_message_fiber, args,
                                               n - spawn_from, 0);
      // Pool exhaustion: never drop a parsed message — run stragglers
      // inline (slow, but the pool being empty means the process is
      // drowning in fibers anyway).  Inline-window flag stays set so
      // user done() callbacks still divert off this dispatch fiber.
      if (started < n - spawn_from) {
        tls_inline_dispatch = true;
        analysis::ScopedDispatch scope("messenger exhaustion-inline window");
        for (size_t i = spawn_from + started; i < n; ++i) {
          process_parsed_message(msgs[i]);
        }
        tls_inline_dispatch = false;
      }
    }
    n = 0;
    if (inline_msg != nullptr) {
      // Mark the inline window: completion paths divert user callbacks
      // (async done) to their own fiber so arbitrary user code never
      // parks this connection's dispatch fiber.  The analysis scope
      // (ISSUE 7) turns any park that slips through into a reported
      // no-pinned-read-fiber violation.
      const SocketId sid = inline_msg->socket;
      if (timeline::enabled()) {
        timeline::record(timeline::kInlineBegin, sid, 0);
      }
      tls_inline_dispatch = true;
      {
        analysis::ScopedDispatch scope("messenger inline-response window");
        process_parsed_message(inline_msg);
      }
      tls_inline_dispatch = false;
      if (timeline::enabled()) {
        timeline::record(timeline::kInlineEnd, sid, 0);
      }
    }
  }
};

// Cut as many whole messages as available per readable sweep; batch
// concurrent-protocol messages and dispatch them in bulk (first inline,
// rest via one bulk fiber wakeup).  Order-sensitive frames (streams,
// auth, in-order protocols) flush the batch first and run inline, so
// per-connection processing order is exactly the pre-batching order.
// Returns the number of whole messages cut (the flight recorder's
// sweep_end cut count).
size_t cut_and_dispatch(Socket* s, SocketId id) {
  IOBuf& buf = s->read_buf();
  DispatchBatch batch;
  size_t cuts = 0;
  // QoS lane routing (net/qos.h): hoisted flag read — one atomic load
  // per sweep, zero when disabled (the default).
  const int qos_lanes = qos_lane_count();
  while (!buf.empty()) {
    InputMessage* msg = alloc_input_message();
    msg->socket = id;
    ParseError rc = ParseError::kTryOtherProtocol;
    if (s->pinned_protocol >= 0) {
      rc = protocol_at(s->pinned_protocol)->parse(&buf, msg, s);
    } else if (buf.size() <= s->probe_stall_len) {
      // Probe memo: every protocol already saw this prefix length and
      // asked for more bytes — skip the whole sweep until they arrive.
      hotpath_vars().probe_stall_skips << 1;
      rc = ParseError::kNotEnoughData;
    } else {
      // Pin ONLY on a successful parse: with a partial prefix several
      // protocols may legitimately say "need more data", and pinning early
      // would misroute the connection once the real format shows.
      hotpath_vars().probe_rounds << 1;
      for (int i = 0; i < protocol_count(); ++i) {
        rc = protocol_at(i)->parse(&buf, msg, s);
        if (rc == ParseError::kOk) {
          s->pinned_protocol = i;
          s->probe_stall_len = 0;
          break;
        }
        if (rc == ParseError::kNotEnoughData ||
            rc == ParseError::kCorrupted) {
          break;
        }
      }
      if (rc == ParseError::kNotEnoughData) {
        s->probe_stall_len = buf.size();
      }
    }
    switch (rc) {
      case ParseError::kOk: {
        ++cuts;
        if (msg->meta.type == RpcMeta::kStreamFrame) {
          // Stream frames keep per-connection arrival order: handled inline
          // (the per-stream ExecutionQueue serializes the user callback).
          // A frame whose body went one-sided (net/rma.h) is resolved
          // here too, so it takes its place in that order with its body
          // in hand.  A failed resolve drops the chunk, and the stream
          // with it: a stream cannot time ONE chunk out and keep its
          // order.
          batch.flush();
          if (msg->meta.rma_rkey != 0 && !rma_resolve(msg, s)) {
            stream_on_chunk_lost(msg->meta.stream_id);
          } else {
            stream_on_frame(std::move(*msg));
          }
          free_input_message(msg);
          continue;
        }
        if (msg->meta.type == RpcMeta::kStripe) {
          // Stripe chunks are offset-addressed and order-free: consume
          // them here (the landing memcpy fans out to worker fibers) —
          // no batch flush, no dispatch fiber.
          stripe_on_chunk(std::move(*msg));
          free_input_message(msg);
          continue;
        }
        if (msg->meta.stripe_id != 0 &&
            (msg->meta.type == RpcMeta::kRequest ||
             msg->meta.type == RpcMeta::kResponse)) {
          // Striped HEAD: only chunk 0 rode this frame; the message
          // dispatches from the reassembly layer once every chunk lands.
          stripe_on_head(std::move(*msg));
          free_input_message(msg);
          continue;
        }
        if (msg->meta.rma_rkey != 0 &&
            (msg->meta.type == RpcMeta::kRequest ||
             msg->meta.type == RpcMeta::kResponse)) {
          // One-sided control frame (net/rma.h): the payload landed
          // out-of-band in a registered region.  Resolve swaps it in
          // (verifying the release-fenced completion bitmap) and the
          // message then dispatches like any other; a failed resolve
          // drops it whole — the call times out, never partial bytes.
          if (!rma_resolve(msg, s)) {
            free_input_message(msg);
            continue;
          }
          if (msg->meta.type == RpcMeta::kRequest) {
            // A one-sided request is whole now, not when its control
            // frame was cut.
            msg->arrival_us = monotonic_time_us();
          }
        }
        const Protocol* p = protocol_at(s->pinned_protocol);
        if (p != nullptr && msg->meta.type == RpcMeta::kAuth) {
          // Credential frames verify INLINE in the read fiber: requests
          // cut after this frame must observe auth_ok (the reference's
          // first-message verify fight, input_messenger.cpp:271-289 —
          // spawning a fiber here would let a request race the verify).
          batch.flush();
          p->process_request(std::move(*msg));
          free_input_message(msg);
          continue;
        }
        if (p != nullptr && p->process_in_order) {
          // FIFO protocols (no correlation id): run inline, keeping this
          // connection's response order.
          // kResponse is the only client-bound type; everything else
          // (requests, kAuth credentials) belongs to the serving path.
          batch.flush();
          if (msg->meta.type == RpcMeta::kResponse) {
            p->process_response(std::move(*msg));
          } else {
            p->process_request(std::move(*msg));
          }
          free_input_message(msg);
        } else if (qos_lanes > 0 && msg->meta.type == RpcMeta::kRequest) {
          // Priority lanes: server-bound requests route through the QoS
          // weighted-fair dequeue instead of direct batch dispatch, so a
          // high-priority small RPC dispatches ahead of queued bulk work
          // even when both arrived in the same sweep (or on different
          // sockets whose sweeps interleave on one worker).  Responses
          // never queue here — a parked caller is itself the backpressure.
          qos_enqueue(qos_lane_for(msg->meta.qos_priority, qos_lanes),
                      msg->meta.qos_tenant, msg, &process_message_fiber);
        } else {
          batch.msgs[batch.n++] = msg;
          if (batch.n == kDispatchBatch) {
            batch.flush();
          }
        }
        continue;
      }
      case ParseError::kNotEnoughData:
        free_input_message(msg);
        batch.flush();
        return cuts;
      default:
        LOG(Warning) << "corrupted input on " << endpoint2str(s->remote())
                     << " (pinned=" << s->pinned_protocol << " proto="
                     << (s->pinned_protocol >= 0 &&
                                 protocol_at(s->pinned_protocol) != nullptr
                             ? protocol_at(s->pinned_protocol)->name
                             : "?")
                     << "), closing";
        free_input_message(msg);
        // Messages cut intact BEFORE the corruption still get delivered.
        batch.flush();
        s->SetFailed(EBADMSG);
        return cuts;
    }
  }
  batch.flush();
  return cuts;
}

}  // namespace

bool messenger_in_inline_dispatch() { return tls_inline_dispatch; }

void messenger_on_readable(SocketId id, void* /*ctx*/) {
  Socket* s = Socket::Address(id);
  if (s == nullptr) {
    return;
  }
  const int64_t budget = cut_budget_flag()->int64_value();
  int64_t swept = 0;
  size_t cuts_total = 0;
  const bool tl = timeline::enabled();  // hoisted: one load per sweep
  if (tl) {
    timeline::record(timeline::kSweepStart, id, 0);
  }
  while (!s->Failed()) {
    // Bulk hint: a parser that knows the current frame's remainder lets
    // this sweep read it in few large-block readvs instead of 512KB
    // slivers of 8KB blocks.
    size_t want = kReadChunk;
    if (s->read_block_hint > want) {
      want = std::min(s->read_block_hint, kMaxBulkRead);
    }
    const ssize_t rc =
        s->transport()->append_to_iobuf(s, &s->read_buf(), want);
    if (rc > 0) {
      cuts_total += cut_and_dispatch(s, id);
      swept += rc;
      if (budget > 0 && swept >= budget) {
        // Cut budget spent: hand the worker to whatever queued behind
        // this sweep (small-RPC dispatch fibers), then resume.  The
        // socket's bytes wait in the kernel/read_buf; nothing re-arms
        // because this fiber IS still the armed reader.
        hotpath_vars().cut_budget_yields << 1;
        swept = 0;
        fiber_yield();
      }
      continue;
    }
    if (rc == 0) {
      break;  // EAGAIN: drained
    }
    // EOF or error.  A not-yet-connected client socket gets spurious
    // HUP/ERR edges from epoll registration racing the non-blocking
    // connect — the connect path owns failure reporting there.
    if (!s->connected()) {
      break;
    }
    s->SetFailed(errno != 0 ? errno : ECONNRESET);
    break;
  }
  if (tl) {
    timeline::record(timeline::kSweepEnd, id, cuts_total);
  }
  s->Dereference();
}

}  // namespace trpc
