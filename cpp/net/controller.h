// Controller — per-RPC state visible to user code on both sides.
//
// Parity: brpc::Controller (/root/reference/src/brpc/controller.h) condensed:
// error state, timeout, attachment, correlation id.  The client call
// lifecycle (response/timeout/failure racing) serializes on the fid the
// controller owns, mirroring the bthread_id protocol in controller.cpp:611.
#pragma once

#include <cstdint>
#include <functional>
#include <memory>
#include <string>
#include <utility>
#include <vector>

#include "base/iobuf.h"
#include "fiber/fid.h"
#include "net/data_pool.h"
#include "net/wire_split.h"

namespace trpc {

using Closure = std::function<void()>;

class ProgressiveAttachment;  // net/progressive.h
class ProgressiveReader;
class CancelScope;  // net/deadline.h

class Controller {
 public:
  // -- status ----------------------------------------------------------
  bool Failed() const { return error_code_ != 0; }
  int error_code() const { return error_code_; }
  const std::string& error_text() const { return error_text_; }
  void SetFailed(int code, const std::string& text) {
    error_code_ = code;
    error_text_ = text;
  }
  void Reset() {
    error_code_ = 0;
    error_text_.clear();
    request_attachment_.clear();
    response_attachment_.clear();
  }

  // -- knobs (client) --------------------------------------------------
  // timeout_ms is kUnsetTimeoutMs until the caller sets it; channels then
  // substitute their own Options::timeout_ms. An explicit 0 disables the
  // timer. A reachable legal value (like 1000) must NOT be the sentinel or
  // callers could never ask for it explicitly.
  static constexpr int64_t kUnsetTimeoutMs = -1;
  void set_timeout_ms(int64_t ms) { timeout_ms_ = ms; }
  int64_t timeout_ms() const { return timeout_ms_; }
  // The caller's timeout if set, else the channel's default.
  int64_t timeout_ms_or(int64_t dflt) const {
    return timeout_ms_ != kUnsetTimeoutMs ? timeout_ms_ : dflt;
  }

  // Compression of the request body (client) / response body (server),
  // negotiated in the meta (gzip_compress.* parity).  Attachments stay
  // raw, like the reference.
  void set_request_compress_type(uint8_t t) { req_compress_ = t; }
  uint8_t request_compress_type() const { return req_compress_; }
  void set_response_compress_type(uint8_t t) { resp_compress_ = t; }
  uint8_t response_compress_type() const { return resp_compress_; }
  // crc32c over the on-wire payload, verified by the receiving parser.
  void set_enable_checksum(bool on) { checksum_ = on; }
  bool checksum_enabled() const { return checksum_; }

  // -- QoS tag (net/qos.h) ---------------------------------------------
  // Client: per-call override of the channel's default tenant/priority
  // (set BEFORE CallMethod; rides the request meta's qos tail group).
  // Server: the arriving request's tag, readable in the handler.
  // Tenant names are capped at 64 bytes (wire decoder limit) — longer
  // ones are truncated at send.  Priority 0 is the highest lane.
  void set_qos(const std::string& tenant, uint8_t priority) {
    qos_tenant_ = tenant.size() > 64 ? tenant.substr(0, 64) : tenant;
    qos_priority_ = priority;
    qos_set_ = true;
  }
  bool qos_set() const { return qos_set_; }
  const std::string& qos_tenant() const { return qos_tenant_; }
  uint8_t qos_priority() const { return qos_priority_; }

  // Payload carried outside the main body (parity: attachment in
  // baidu_std; rides the same frame after the response body).
  IOBuf& request_attachment() { return request_attachment_; }
  IOBuf& response_attachment() { return response_attachment_; }

  int64_t latency_us() const { return latency_us_; }
  const std::string& method() const { return method_; }

  // -- cancellation ------------------------------------------------------
  // Parity: reference controller.h:717 StartCancel() / :983 free-function
  // StartCancel(CallId).  Rides the versioned-fid error path: the call
  // completes with ECANCELED exactly once, racing responses/timeouts
  // serialize on the fid, and a cancel after completion is a harmless
  // no-op (stale version).  Never blocks on the network.
  fid_t call_id() const { return call_.cid; }
  void StartCancel();

  // -- deadline plane (net/deadline.h) -----------------------------------
  // Server side: the request's absolute monotonic deadline, anchored at
  // arrival from the wire's remaining-budget stamp (0 = the caller set
  // no deadline).  Handlers poll remaining_us() to right-size or
  // abandon work; long transfer loops check it between chunks.
  void set_deadline_abs_us(int64_t abs_us) { deadline_abs_us_ = abs_us; }
  int64_t deadline_abs_us() const { return deadline_abs_us_; }
  // Remaining budget in µs: INT64_MAX when no deadline, 0 when already
  // past (never negative — callers compare against work estimates).
  int64_t remaining_us() const;
  // Server side: has the client gone away (socket failed/closed)?  A long
  // handler polls this to abandon work nobody will receive
  // (controller.h:308 IsCanceled parity).
  bool IsCanceled() const;

  // Async-completion hook (batch pipeline): a done closure marked
  // inline-safe is BOUNDED FRAMEWORK WORK (memcpy + atomic push + wake,
  // never parks, never runs user code) and may execute directly on a
  // connection's dispatch fiber instead of costing a completion-fiber
  // spawn per call (net/channel.cc complete_locked_call).  Default off:
  // arbitrary user dones must not stall everything behind them on the
  // connection.
  void set_done_inline_safe(bool on) { done_inline_safe_ = on; }
  bool done_inline_safe() const { return done_inline_safe_; }

  // -- progressive bodies (net/progressive.h) --------------------------
  // Server handler (HTTP serving): the response body will be streamed
  // incrementally; done() flushes headers (chunked) and the returned
  // attachment keeps writing from any fiber until close().
  std::shared_ptr<ProgressiveAttachment> CreateProgressiveAttachment();
  const std::shared_ptr<ProgressiveAttachment>& progressive_attachment()
      const {
    return progressive_;
  }
  // Client (h2): response DATA is delivered to `r` piece by piece
  // instead of accumulating; `r` must outlive the call and gets exactly
  // one on_done.
  void ReadProgressively(ProgressiveReader* r) { call_.preader = r; }

  // -- internal (framework) --------------------------------------------
  struct CallState {
    fid_t cid = 0;
    uint64_t timeout_timer = 0;
    void* span = nullptr;  // rpcz client Span (owned until submit)
    // Connection ownership for pooled/short calls (socket_map.h): the
    // completion path gives pooled sockets back / closes short ones.
    uint8_t conn_type = 0;      // ConnectionType
    const void* conn_auth = nullptr;  // pool key half (Authenticator*)
    IOBuf* response = nullptr;
    Closure done;
    int64_t start_us = 0;
    uint64_t socket_id = 0;
    // Streaming piggyback (net/stream.h): client-offered / request-carried /
    // server-accepted stream ids.
    uint64_t offered_stream = 0;
    uint64_t peer_stream = 0;
    uint64_t peer_stream_window = 0;
    uint64_t accepted_stream = 0;
    // Batch establishment (StreamIds parity): offers/acceptances beyond
    // the first, index-aligned through the meta's extra_streams tail.
    std::vector<uint64_t> extra_offered;
    std::vector<std::pair<uint64_t, uint64_t>> extra_peer;  // (sid, window)
    std::vector<uint64_t> extra_accepted;
    // h2/grpc calls: the stream id issued for this call, so a failed call
    // (timeout) can cancel its client-side stream state (h2_client.h).
    uint32_t h2_stream = 0;
    // Progressive response consumer (net/progressive.h; h2 client).
    ProgressiveReader* preader = nullptr;
    // Session-local data (net/data_pool.h): the server's pool and the
    // object lazily borrowed for this request.
    SimpleDataPool* sl_pool = nullptr;
    void* sl_data = nullptr;
    // Large-message striping (net/stripe.h).  Client: a caller-owned
    // response landing buffer (batch plane) — registered under the cid
    // so striped response chunks memcpy straight into it; unregistered
    // (with a lander drain) in complete_locked_call before the fid can
    // recycle.  Server: the rails the striped REQUEST arrived over, so
    // the response stripes back across the same connections.
    void* land_buf = nullptr;
    size_t land_cap = 0;
    bool land_registered = false;  // stripe landing (net/stripe.h)
    bool land_bound = false;       // one-sided bind (net/rma.h)
    // One-sided RMA (net/rma.h), server side: the request's advertised
    // response-landing region — when set (and the connection has an rma
    // session) the response is PUT straight into the caller's registered
    // buffer instead of riding frames back.
    uint64_t rma_resp_rkey = 0;
    uint64_t rma_resp_max = 0;
    uint64_t rma_resp_off = 0;
    std::vector<uint64_t> stripe_rails;
    // Cancellation scope of a DISPATCHED server request (net/deadline.h):
    // co-owned with the cancel registry so the response path (which may
    // run rma_try_send long after the handler fiber exited) can still
    // poll it between chunks.  Null on the client side and on requests
    // shed before dispatch.
    std::shared_ptr<CancelScope> cancel_scope;
    // The server's phase stamps (net/wire_split.h), its own monotonic
    // clock.  Server: srv.handler_us is read just before the handler is
    // called (0 = answered before any handler: shed, rejected, failed).
    // Client: the three a tstd response carried back (0 = it carried
    // none: an older peer, a protocol adaptor, a call that failed
    // locally) and whether that connection's two ends read one clock.
    SrvStamps srv;
    bool srv_same_clock = false;
  };
  CallState& call() { return call_; }
  const CallState& call() const { return call_; }

  // Pooled per-request scratch object, created by the server's
  // session_local_data_factory (simple_data_pool parity).  Null when no
  // factory is installed.  Returned to the pool after the response.
  void* session_local_data() {
    if (call_.sl_data == nullptr && call_.sl_pool != nullptr) {
      call_.sl_data = call_.sl_pool->Borrow();
    }
    return call_.sl_data;
  }

  void set_method(const std::string& m) { method_ = m; }
  void set_latency_us(int64_t us) { latency_us_ = us; }

 private:
  int error_code_ = 0;
  std::string error_text_;
  std::string method_;
  int64_t timeout_ms_ = kUnsetTimeoutMs;
  uint8_t req_compress_ = 0;
  uint8_t resp_compress_ = 0;
  bool checksum_ = false;
  bool done_inline_safe_ = false;
  bool qos_set_ = false;
  int64_t deadline_abs_us_ = 0;
  uint8_t qos_priority_ = 0;
  std::string qos_tenant_;
  int64_t latency_us_ = 0;
  IOBuf request_attachment_;
  IOBuf response_attachment_;
  std::shared_ptr<ProgressiveAttachment> progressive_;
  CallState call_;
};

// Cancels the call identified by `cid` (Controller::call_id(), safe to
// stash and invoke from any thread/fiber, even after the call completed —
// the versioned fid makes a stale cancel a no-op).
void StartCancel(fid_t cid);

}  // namespace trpc
