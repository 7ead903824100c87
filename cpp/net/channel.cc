#include "net/channel.h"

#include <functional>

#include <errno.h>

#include "base/compress.h"
#include "base/logging.h"
#include "base/time.h"
#include "fiber/fiber.h"
#include "fiber/timer.h"
#include "net/h2_client.h"
#include "net/messenger.h"
#include "net/deadline.h"
#include "net/progressive.h"
#include "net/protocol.h"
#include "net/ici_transport.h"
#include "net/shm_transport.h"
#include "net/socket_map.h"
#include "net/span.h"
#include "net/stream.h"
#include "net/rma.h"
#include "net/stripe.h"
#include "net/tls.h"

namespace trpc {

// Completes a call that is currently LOCKED via its fid: records latency,
// cancels the timeout timer, destroys the id (waking sync joiners) and runs
// the async done.  Mirrors Controller::OnVersionedRPCReturned ordering
// (controller.cpp:611): state is finalized before anyone can observe it.
// Shared with the h2 client response path (h2_client.cc).
void complete_locked_call(fid_t cid, Controller* cntl) {
  cntl->set_latency_us(monotonic_time_us() - cntl->call().start_us);
  // Progressive reads get exactly one terminal callback, success or not,
  // before the caller can observe completion.
  if (cntl->call().preader != nullptr) {
    ProgressiveReader* r = cntl->call().preader;
    cntl->call().preader = nullptr;
    r->on_done(cntl->error_code(), cntl->error_text());
  }
  // h2 calls completing WITHOUT a response (timeout / local failure) must
  // drop their client-side stream state, or dead streams accumulate on
  // the multiplexed connection for its whole lifetime.
  if (cntl->call().h2_stream != 0) {
    if (cntl->Failed()) {
      h2_client_cancel(cntl->call().socket_id, cntl->call().h2_stream);
    }
    cntl->call().h2_stream = 0;
  }
  // Same for tstd stream offers: a call that failed before any
  // acceptance arrived leaves its streams unestablished, and a parked
  // StreamWrite would otherwise re-arm its establishment wait forever.
  if (cntl->Failed() && cntl->call().offered_stream != 0) {
    StreamClose(cntl->call().offered_stream);
    cntl->call().offered_stream = 0;
    for (uint64_t sid : cntl->call().extra_offered) {
      StreamClose(sid);
    }
    cntl->call().extra_offered.clear();
  }
  // Connection-type epilogue: pooled connections go back to the shared
  // pool (socket.h:611-627 parity), short ones close now.
  const SocketId conn = cntl->call().socket_id;
  if (conn != 0) {
    const auto ct = static_cast<ConnectionType>(cntl->call().conn_type);
    if (ct == ConnectionType::kPooled) {
      SocketRef s(Socket::Address(conn));
      if (s) {
        if (cntl->Failed()) {
          // A failed/timed-out call may still have its response in
          // flight: pooling the connection would queue the next caller
          // behind stale bytes (the reference drops pooled sockets on
          // error for the same reason).
          s->SetFailed(ESHUTDOWN);
        } else {
          SocketMap::instance()->give_back(
              s->remote(),
              static_cast<const Authenticator*>(cntl->call().conn_auth),
              conn);
        }
      }
    } else if (ct == ConnectionType::kShort) {
      SocketRef s(Socket::Address(conn));
      if (s) {
        s->SetFailed(ESHUTDOWN);
      }
    }
  }
  auto* span = static_cast<Span*>(cntl->call().span);
  if (span != nullptr) {
    cntl->call().span = nullptr;
    if (cntl->call().response != nullptr) {
      span->response_bytes = cntl->call().response->size();
    }
    submit_span(span, cntl->error_code());
  }
  // Landing registration must die BEFORE the fid can recycle: a late
  // one-sided control frame for this cid must reject (unbind first), and
  // a late stripe chunk must never memcpy into a buffer the caller has
  // already reclaimed (stripe_unregister_landing drains in-flight
  // landers).  Cheap no-op for the unregistered (non-batch) hot path.
  if (cntl->call().land_bound) {
    rma_landing_unbind(cid);
    cntl->call().land_bound = false;
  }
  if (cntl->call().land_registered) {
    stripe_unregister_landing(cid);
    cntl->call().land_registered = false;
  }
  cntl->call().land_buf = nullptr;
  cntl->call().land_cap = 0;
  const uint64_t timer = cntl->call().timeout_timer;
  const bool inline_safe = cntl->done_inline_safe();
  Closure done = std::move(cntl->call().done);
  fid_unlock_and_destroy(cid);
  if (timer != 0) {
    TimerThread::instance()->unschedule(timer);
  }
  if (done) {
    // A non-empty done is the USER's async completion (sync callers join
    // the fid instead).  When this completion is running inline on a
    // connection's dispatch fiber (batched-dispatch fast path), arbitrary
    // user code must not park it — everything behind it on the connection
    // would stall — so the closure gets its own fiber there.  Dones the
    // framework marked inline-safe (batch-pipeline completions: bounded,
    // park-free) skip the spawn and run here directly.
    if (messenger_in_inline_dispatch() && !inline_safe) {
      auto* heap_done = new Closure(std::move(done));
      if (fiber_start(
              nullptr,
              [](void* p) {
                auto* d = static_cast<Closure*>(p);
                (*d)();
                delete d;
              },
              heap_done) != 0) {
        (*heap_done)();  // pool exhausted: inline beats dropping
        delete heap_done;
      }
    } else {
      done();
    }
  }
}

namespace {

int on_call_error(fid_t cid, void* data, int code) {
  Controller* cntl = static_cast<Controller*>(data);
  cntl->SetFailed(code,
                  code == ETIMEDOUT   ? "rpc timeout"
                  : code == ECANCELED ? "rpc canceled by caller"
                  : code == kEDeadlineExpired
                      ? "end-to-end deadline expired"
                      : "rpc failed");
  complete_locked_call(cid, cntl);
  return 0;
}

void timeout_fiber(void* arg) {
  fid_error(reinterpret_cast<fid_t>(arg), ETIMEDOUT);
}

// Runs on the TimerThread: must stay cheap (timer.h contract).  The actual
// completion — fid locking and the user's done() — moves to a fiber.
void timeout_cb(void* arg) {
  fiber_start(nullptr, timeout_fiber, arg, 0);
}

// Deadline-bound variant (net/deadline.h): when the AMBIENT end-to-end
// budget is strictly tighter than the call's own timeout, its expiry is
// budget exhaustion, not a per-hop timeout — surfaced as the typed
// kEDeadlineExpired so retry layers stop the chain instead of re-burning
// a budget that is equally dead everywhere.
void deadline_fiber(void* arg) {
  fid_error(reinterpret_cast<fid_t>(arg), kEDeadlineExpired);
}

void deadline_cb(void* arg) {
  fiber_start(nullptr, deadline_fiber, arg, 0);
}

}  // namespace

// Response path installed into the tstd protocol (messenger dispatch).
void tstd_process_response(InputMessage&& msg) {
  const fid_t cid = msg.meta.correlation_id;
  void* data = nullptr;
  if (fid_lock(cid, &data) != 0) {
    return;  // stale response (timed out / retried away): harmless
  }
  Controller* cntl = static_cast<Controller*>(data);
  if (cntl->call().offered_stream != 0) {
    const auto& offered = cntl->call().extra_offered;
    const auto& accepted = msg.meta.extra_streams;
    if (msg.meta.stream_id != 0) {
      // Server accepted: bind ids + adopt its advertised window.
      stream_on_accept_response(cntl->call().offered_stream,
                                msg.meta.stream_id,
                                cntl->call().socket_id,
                                msg.meta.ack_bytes);
      // Batch acceptances align by index with our extra offers.
      for (size_t i = 0; i < offered.size() && i < accepted.size(); ++i) {
        stream_on_accept_response(offered[i], accepted[i].first,
                                  cntl->call().socket_id,
                                  accepted[i].second);
      }
      // Extras the server did not accept are dead.
      for (size_t i = accepted.size(); i < offered.size(); ++i) {
        StreamClose(offered[i]);
      }
    } else {
      // The handler never accepted (plain response / older peer): a
      // hanging unestablished stream would park writers forever —
      // close the primary and EVERY extra, whatever a (buggy/hostile)
      // peer put in the extra_streams tail of a no-acceptance response.
      StreamClose(cntl->call().offered_stream);
      for (uint64_t sid : offered) {
        StreamClose(sid);
      }
    }
    cntl->call().offered_stream = 0;
    cntl->call().extra_offered.clear();
  }
  if (msg.meta.srv.arrival_us != 0) {
    // The server's phase stamps (net/wire_split.h), kept for whoever
    // folds the call (the batch pipeline's poll).  Whether they can be
    // set against OUR clock is the connection's to say.
    Controller::CallState& call = cntl->call();
    call.srv = msg.meta.srv;
    SocketRef conn(Socket::Address(msg.socket));
    call.srv_same_clock = conn && conn->peer_shares_clock();
  }
  if (msg.meta.error_code != 0) {
    cntl->SetFailed(msg.meta.error_code, msg.meta.error_text);
  } else {
    IOBuf payload = std::move(msg.payload);
    if (msg.meta.attachment_size > 0 &&
        msg.meta.attachment_size <= payload.size()) {
      IOBuf body;
      payload.cutn(&body, payload.size() - msg.meta.attachment_size);
      cntl->response_attachment() = std::move(payload);
      payload = std::move(body);
    }
    if (msg.meta.compress_type != 0) {
      const Compressor* c = find_compressor(
          static_cast<CompressType>(msg.meta.compress_type));
      IOBuf plain;
      if (c == nullptr ||
          !c->decompress(payload, &plain, 1ull << 30)) {
        cntl->SetFailed(EBADMSG, "response decompression failed");
        complete_locked_call(cid, cntl);
        return;
      }
      payload = std::move(plain);
    }
    if (cntl->call().response != nullptr) {
      *cntl->call().response = std::move(payload);
    }
  }
  complete_locked_call(cid, cntl);
}

Channel::~Channel() {
  SocketRef s(Socket::Address(sock_));
  if (s) {
    s->SetFailed(ESHUTDOWN);
  }
}

int Channel::Init(const std::string& addr, const Options* opts) {
  fiber_init(0);
  tstd_protocol();
  if (opts != nullptr) {
    opts_ = *opts;
  }
  if (opts_.protocol == "tstd") {
    proto_ = 0;
  } else if (opts_.protocol == "h2") {
    proto_ = 1;
  } else if (opts_.protocol == "grpc") {
    proto_ = 2;
  } else {
    return -1;  // unknown protocol must not silently mean tstd
  }
  ConnectionType ct;
  if (!parse_connection_type(opts_.connection_type, &ct)) {
    return -1;  // typo'd type must not silently mean "single"
  }
  if ((opts_.use_shm || opts_.use_ici) && ct != ConnectionType::kSingle) {
    return -1;  // shm/ici rings are inherently single-connection
  }
  if (opts_.use_shm && opts_.use_ici) {
    return -1;
  }
  if (opts_.use_tls &&
      (ct != ConnectionType::kSingle || opts_.use_shm || opts_.use_ici ||
       !tls_available())) {
    return -1;  // TLS rides the single TCP connection
  }
  if (!opts_.use_tls &&
      (!opts_.tls_cert.empty() || !opts_.tls_ca.empty())) {
    return -1;  // cert/CA options without use_tls must not silently no-op
  }
  if (proto_ != 0) {
    if (ct != ConnectionType::kSingle || opts_.use_shm || opts_.use_ici) {
      return -1;  // h2 multiplexes one connection by design
    }
    h2_client_protocol_index();  // register before any response arrives
  }
  conn_type_ = static_cast<uint8_t>(ct);
  sni_host_ = addr.rfind("unix:", 0) == 0 ? ""
                                          : addr.substr(0, addr.rfind(':'));
  return hostname2endpoint(addr.c_str(), &ep_);
}

std::string Channel::transport_name() {
  SocketRef s(Socket::Address(sock_));
  return s ? s->transport()->name() : "";
}

std::string Channel::alpn() {
  SocketRef s(Socket::Address(sock_));
  return s ? tls_alpn_selected(s.get()) : "";
}

// First write on a fresh connection: the credential frame (FIFO write
// queue guarantees it precedes every request).
static int send_credential(SocketId sid, const Authenticator* auth) {
  if (auth == nullptr) {
    return 0;
  }
  std::string cred;
  if (auth->generate_credential(&cred) != 0) {
    return -1;
  }
  RpcMeta meta;
  meta.type = RpcMeta::kAuth;
  IOBuf payload;
  payload.append(cred);
  IOBuf frame;
  tstd_pack(&frame, meta, payload);
  SocketRef s(Socket::Address(sid));
  return s && s->Write(std::move(frame)) == 0 ? 0 : -1;
}

// Ring-transport bootstrap (rdma_handshake-over-TCP parity, shared by the
// shm and ICI paths): ship the freshly-minted segment name over a
// throwaway TCP channel — which carries the channel's authenticator, so
// auth-gated servers accept the handshake — then install the fd-less ring
// socket via `attach` and send the credential frame over the rings (the
// ring connection is a fresh connection to an auth-checking server).
// Returns 0 with *sock live on success.
static int ring_bootstrap(const EndPoint& ep, const Channel::Options& copts,
                          const char* method, const std::string& seg_name,
                          const std::function<int(SocketId*)>& attach,
                          SocketId* sock) {
  Channel tcp;
  Channel::Options topts;
  topts.timeout_ms = copts.timeout_ms;
  topts.auth = copts.auth;
  if (tcp.Init(endpoint2str(ep), &topts) != 0) {
    return -1;
  }
  Controller cntl;
  cntl.set_timeout_ms(copts.timeout_ms);
  IOBuf req, resp;
  req.append(seg_name);
  tcp.CallMethod(method, req, &resp, &cntl);
  if (cntl.Failed() || !resp.equals("ok", 2) || attach(sock) != 0) {
    return -1;
  }
  if (send_credential(*sock, copts.auth) != 0) {
    SocketRef dead(Socket::Address(*sock));
    if (dead) {
      dead->SetFailed(EACCES);
    }
    return -1;
  }
  return 0;
}

int Channel::ensure_socket(SocketId* out) {
  LockGuard<FiberMutex> g(sock_mu_);
  Socket* s = Socket::Address(sock_);
  if (s != nullptr) {
    if (!s->Failed()) {
      *out = sock_;
      s->Dereference();
      return 0;
    }
    s->Dereference();
  }
  if (opts_.use_ici) {
    std::string name;
    auto conn = ici_conn_create(&name);
    if (conn != nullptr &&
        ring_bootstrap(ep_, opts_, kIciConnectMethod, name,
                       [&conn](SocketId* sid) {
                         return ici_socket_create(
                             conn, &messenger_on_readable, nullptr, sid);
                       },
                       &sock_) == 0) {
      *out = sock_;
      return 0;
    }
    LOG(Warning) << "ici handshake with " << endpoint2str(ep_)
                 << " failed; falling back to tcp";
  }
  if (opts_.use_shm) {
    std::string name;
    auto conn = shm_conn_create(&name);
    if (conn != nullptr &&
        ring_bootstrap(ep_, opts_, kShmConnectMethod, name,
                       [&conn](SocketId* sid) {
                         return shm_socket_create(
                             conn, &messenger_on_readable, nullptr, sid);
                       },
                       &sock_) == 0) {
      *out = sock_;
      return 0;
    }
    LOG(Warning) << "shm handshake with " << endpoint2str(ep_)
                 << " failed; falling back to tcp";
  }
  Socket::Options sopts;
  sopts.fd = -1;  // lazy connect in the write fiber
  sopts.remote = ep_;
  sopts.on_readable = &messenger_on_readable;
  if (opts_.use_tls) {
    std::string err;
    void* ctx = opts_.tls_cert.empty() && opts_.tls_ca.empty()
                    ? tls_client_ctx(&err)
                    : tls_client_ctx_mtls(opts_.tls_cert, opts_.tls_key,
                                          opts_.tls_ca, &err);
    if (ctx == nullptr) {
      LOG(Warning) << "tls client init failed: " << err;
      return -1;
    }
    sopts.transport = tls_transport();
    // h2/grpc channels advertise ALPN h2 (gRPC servers commonly require
    // it); tstd is not an IANA protocol, so it offers no ALPN.  SNI
    // carries the Init hostname (IP literals filtered by the factory).
    sopts.transport_ctx_holder =
        tls_conn_client(ctx, proto_ != 0 ? "\x02h2" : "", sni_host_);
  }
  if (Socket::Create(sopts, &sock_) != 0) {
    return -1;
  }
  if (proto_ != 0) {
    // h2/grpc: pin + install connection state while still single-threaded
    // (sock_mu_ held); the credential rides the "authorization" header per
    // request (h2_client_issue), not a tstd kAuth frame.
    h2_client_bind(sock_);
    *out = sock_;
    return 0;
  }
  if (send_credential(sock_, opts_.auth) != 0) {
    SocketRef dead(Socket::Address(sock_));
    if (dead) {
      dead->SetFailed(EACCES);
    }
    return -1;
  }
  *out = sock_;
  return 0;
}

void Channel::CallMethod(const std::string& method, const IOBuf& request,
                         IOBuf* response, Controller* cntl, Closure done) {
  cntl->set_method(method);
  cntl->call().response = response;
  cntl->call().done = std::move(done);
  cntl->call().start_us = monotonic_time_us();
  // Controller reuse: a previous call's connection ownership must not
  // leak into this call's early-failure paths, nor its server stamps
  // into a call that fails before any response.
  cntl->call().srv = {};
  cntl->call().srv_same_clock = false;
  cntl->call().socket_id = 0;
  cntl->call().conn_type = 0;
  cntl->call().conn_auth = nullptr;
  cntl->call().h2_stream = 0;
  const bool sync = !cntl->call().done;
  // rpcz: client span; a handler fiber's ambient server span becomes the
  // parent (channel.cpp:506-527 parity).
  Span* span = nullptr;
  if (rpcz_enabled()) {
    span = start_span(/*server_side=*/false, method);
    span->request_bytes = request.size();
    cntl->call().span = span;
  }

  fid_t cid = 0;
  if (fid_create(&cid, cntl, on_call_error) != 0) {
    cntl->SetFailed(ENOMEM, "out of call ids");
    if (span != nullptr) {
      cntl->call().span = nullptr;  // never reaches complete_locked_call
      submit_span(span, ENOMEM);
    }
    if (!sync && cntl->call().done) {
      cntl->call().done();
    }
    return;
  }
  cntl->call().cid = cid;
  // Hold the call lock through setup so a racing response or an eager
  // timeout cannot complete (and free) the call mid-construction —
  // responses/timeouts queue on the fid until we unlock (channel.cpp:481
  // parity).
  CHECK(fid_lock(cid, nullptr) == 0);

  // Deadline plane (net/deadline.h): the effective budget is
  // min(caller/channel timeout, the ambient deadline of the request this
  // fiber is serving) — a proxied call therefore re-stamps
  // budget-minus-elapsed at every hop.  The serving request's cancel
  // scope learns this call's id so a cascading cancel reaches it.
  int64_t deadline_abs = 0;
  bool ambient_bound = false;  // the ambient budget is the tight constraint
  const int64_t eff_timeout_ms = cntl->timeout_ms_or(opts_.timeout_ms);
  if (deadline_wire_enabled()) {
    if (eff_timeout_ms > 0) {
      deadline_abs = cntl->call().start_us + eff_timeout_ms * 1000;
    }
    const int64_t amb = ambient_deadline();
    if (amb != 0 && (deadline_abs == 0 || amb < deadline_abs)) {
      deadline_abs = amb;
      ambient_bound = true;
    }
  }
  CancelScope* parent_scope = ambient_cancel();
  if (parent_scope != nullptr) {
    parent_scope->add_call(cid);
  }
  if (deadline_abs != 0 && monotonic_time_us() >= deadline_abs) {
    // Budget already exhausted: fail fast without touching the wire —
    // dispatching a request nobody can wait for is exactly the wasted
    // work the plane exists to shed.
    deadline_vars().client_expired_total << 1;
    fid_unlock(cid);
    fid_error(cid, kEDeadlineExpired);
    if (sync) {
      fid_join(cid);
    }
    return;
  }

  SocketId sid = 0;
  const auto ct = static_cast<ConnectionType>(conn_type_);
  if (proto_ != 0 &&
      (cntl->call().offered_stream != 0 ||
       cntl->request_compress_type() != 0)) {
    // Streaming offers and tstd-negotiated compression have no h2
    // carrier; failing loudly beats silently dropping the option.
    fid_unlock(cid);
    fid_error(cid, EINVAL);
    if (sync) {
      fid_join(cid);
    }
    return;
  }
  if (cntl->call().offered_stream != 0 && ct != ConnectionType::kSingle) {
    // A stream outlives the call and pins its connection; pooled/short
    // connections are per-call by definition.
    fid_unlock(cid);
    fid_error(cid, EINVAL);
    if (sync) {
      fid_join(cid);
    }
    return;
  }
  int sock_rc;
  switch (ct) {
    case ConnectionType::kPooled: {
      bool fresh = false;
      sock_rc =
          SocketMap::instance()->take_pooled(ep_, opts_.auth, &sid, &fresh);
      if (sock_rc == 0 && fresh) {
        sock_rc = send_credential(sid, opts_.auth);
      }
      break;
    }
    case ConnectionType::kShort:
      sock_rc = SocketMap::instance()->create_short(ep_, &sid);
      if (sock_rc == 0) {
        sock_rc = send_credential(sid, opts_.auth);
      }
      break;
    case ConnectionType::kSingle:
    default:
      sock_rc = ensure_socket(&sid);
      break;
  }
  if (sock_rc != 0) {
    if (sid != 0) {
      // The socket exists but the credential could not be sent: close it
      // rather than leaking a connected fd per failed call.
      SocketRef dead(Socket::Address(sid));
      if (dead) {
        dead->SetFailed(EACCES);
      }
    }
    fid_unlock(cid);
    fid_error(cid, ECONNREFUSED);
    if (sync) {
      fid_join(cid);
    }
    return;
  }
  cntl->call().socket_id = sid;
  cntl->call().conn_type = static_cast<uint8_t>(ct);
  cntl->call().conn_auth = opts_.auth;

  // Local timer at the TIGHTER of the caller's timeout and the ambient
  // deadline: an explicit-0 timeout still dies when the end-to-end
  // budget does.
  int64_t timer_at =
      eff_timeout_ms > 0 ? cntl->call().start_us + eff_timeout_ms * 1000 : 0;
  if (deadline_abs != 0 && (timer_at == 0 || deadline_abs < timer_at)) {
    timer_at = deadline_abs;
  }
  if (timer_at > 0) {
    cntl->call().timeout_timer = TimerThread::instance()->schedule(
        timer_at, ambient_bound ? deadline_cb : timeout_cb,
        reinterpret_cast<void*>(cid));
  }

  if (proto_ != 0) {  // h2 / grpc path: PackH2Request-equivalent
    std::string auth_hdr;
    if (opts_.auth != nullptr &&
        opts_.auth->generate_credential(&auth_hdr) != 0) {
      fid_unlock(cid);
      fid_error(cid, EACCES);
      if (sync) {
        fid_join(cid);
      }
      return;
    }
    IOBuf body = request;  // zero-copy share
    if (!cntl->request_attachment().empty()) {
      body.append(cntl->request_attachment());  // h2 has no split concept
    }
    if (span != nullptr) {
      span_annotate(span, "request packed");
    }
    uint32_t stream_id = 0;
    const bool ok = h2_client_issue(sid, cid, method, body, proto_ == 2,
                                    endpoint2str(ep_), auth_hdr,
                                    &stream_id,
                                    cntl->call().preader) == 0;
    cntl->call().h2_stream = stream_id;
    fid_unlock(cid);
    if (!ok) {
      fid_error(cid, ECONNRESET);
    }
    if (sync) {
      fid_join(cid);
    }
    return;
  }

  RpcMeta meta;
  meta.type = RpcMeta::kRequest;
  meta.correlation_id = cid;
  meta.method = method;
  // QoS tag (net/qos.h): the caller's explicit tag wins, else the
  // channel default; untagged stays absent from the wire entirely.
  if (cntl->qos_set()) {
    meta.qos_priority = cntl->qos_priority();
    meta.qos_tenant = cntl->qos_tenant();
  } else {
    meta.qos_priority = opts_.qos_priority;
    meta.qos_tenant = opts_.qos_tenant;
  }
  meta.stream_id = cntl->call().offered_stream;  // stream offer piggyback
  if (meta.stream_id != 0) {
    meta.ack_bytes = stream_recv_window(meta.stream_id);  // advertise window
    for (uint64_t sid : cntl->call().extra_offered) {  // batch offers
      meta.extra_streams.emplace_back(sid, stream_recv_window(sid));
    }
  }
  if (span != nullptr) {
    meta.trace_id = span->trace_id;   // server links as our child
    meta.span_id = span->span_id;
    span_annotate(span, "request packed");
  }
  if (deadline_abs != 0) {
    // Wire stamp (tail-group 7): the REMAINING budget at send — never 0
    // here (0 means unset); a budget that just hit zero stamps 1µs and
    // sheds at the server instead.
    const int64_t rem = deadline_abs - monotonic_time_us();
    meta.deadline_us = static_cast<uint64_t>(rem > 0 ? rem : 1);
    deadline_vars().stamped_total << 1;
  }
  IOBuf body = request;  // zero-copy share
  if (cntl->request_compress_type() != 0) {
    const Compressor* c = find_compressor(
        static_cast<CompressType>(cntl->request_compress_type()));
    IOBuf squeezed;
    if (c == nullptr || !c->compress(body, &squeezed)) {
      fid_unlock(cid);
      fid_error(cid, EINVAL);
      if (sync) {
        fid_join(cid);
      }
      return;
    }
    body = std::move(squeezed);
    meta.compress_type = cntl->request_compress_type();
  }
  if (!cntl->request_attachment().empty()) {
    meta.attachment_size =
        static_cast<uint32_t>(cntl->request_attachment().size());
    body.append(cntl->request_attachment());
  }
  if (cntl->checksum_enabled()) {
    meta.has_checksum = true;  // striped sends CRC per chunk (stripe.cc)
  }
  // Response landing (batch plane), registered under the cid BEFORE the
  // request can reach the server.  Striped: so even a chunk that beats
  // the head frame lands in place; only worth it when the buffer could
  // hold a striped (above-threshold) response.  One-sided (net/rma.h):
  // when this connection has an rma session and the buffer lies in an
  // exportable region, the request's meta names a descriptor and the
  // range — the server then PUTS the response straight into the
  // caller's buffer, from kRmaDirectFloor up.  A smaller buffer pays
  // neither lookup.
  void* land_buf = cntl->call().land_buf;
  const size_t land_cap = cntl->call().land_cap;
  if (land_buf != nullptr && rma_direct_eligible(land_cap)) {
    if (stripe_eligible(land_cap)) {
      stripe_register_landing(cid, land_buf, land_cap);
      cntl->call().land_registered = true;
    }
    cntl->call().land_bound =
        rma_bind_response(sid, cid, land_buf, land_cap, &meta);
  }

  bool write_ok;
  // Long-transfer loops poll the token between chunks: a cancelled
  // caller (or an expired budget) stops writing within one chunk.
  const DeadlineToken dtok{parent_scope, deadline_abs};
  const int rma_rc = rma_try_send(sid, &meta, &body, 0, 0, 0, dtok);
  if (rma_rc == 0) {
    // Body written one-sided into the peer's window; the control frame
    // is queued.  Nothing rides the stripe layer.
    write_ok = true;
  } else if (rma_rc < 0) {
    write_ok = false;
  } else if (stripe_should(sid, meta.stream_id, body.size())) {
    // Multi-rail large-message path (net/stripe.h): cut the body into
    // chunk frames issued concurrently.  Pooled channels spread chunks
    // over extra pooled connections to the same endpoint (each rail has
    // its own kernel pipe + read fiber on the far side); single/shm
    // channels stripe over the one connection, which still pipelines the
    // receiver's landing memcpys against the wire.
    std::vector<SocketId> rails{sid};
    std::vector<SocketId> extra;
    if (ct == ConnectionType::kPooled) {
      const int want = stripe_rails();
      for (int i = 1; i < want; ++i) {
        SocketId rid = 0;
        bool fresh = false;
        if (SocketMap::instance()->take_pooled(ep_, opts_.auth, &rid,
                                               &fresh) != 0) {
          break;
        }
        if (fresh && send_credential(rid, opts_.auth) != 0) {
          SocketRef dead(Socket::Address(rid));
          if (dead) {
            dead->SetFailed(EACCES);
          }
          break;
        }
        extra.push_back(rid);
        rails.push_back(rid);
      }
    }
    write_ok = stripe_send(sid, rails, std::move(meta), std::move(body),
                           stripe_make_id(), dtok) == 0;
    // Rails go straight back to the pool: their chunk frames are queued
    // FIFO on each socket, so a later borrower's frames follow ours.
    for (SocketId rid : extra) {
      SocketMap::instance()->give_back(ep_, opts_.auth, rid);
    }
  } else {
    write_ok =
        stripe_frame_send(sid, std::move(meta), std::move(body)) == 0;
  }
  fid_unlock(cid);
  if (!write_ok) {
    // A send the DEADLINE TOKEN aborted mid-transfer is not a transport
    // fault: surface the cancel/budget code so retry layers stop the
    // chain and no healthy node gets quarantined for the caller's clock.
    if (dtok.aborted()) {
      fid_error(cid, parent_scope != nullptr && parent_scope->cancelled()
                         ? ECANCELED
                         : kEDeadlineExpired);
    } else {
      fid_error(cid, ECONNRESET);
    }
  }
  if (sync) {
    fid_join(cid);
  }
}

}  // namespace trpc
