// C ABI for the RPC runtime (Python ctypes binding surface).
//
// Handlers registered from Python are invoked on fiber stacks; ctypes
// callbacks re-acquire the GIL themselves.  Responses are completed via
// trpc_call_respond (sync or later — async handlers just stash the call
// handle).
#include <atomic>
#include <cstring>
#include <string>
#include <vector>

#include "base/iobuf.h"
#include "base/time.h"
#include "fiber/event.h"
#include "fiber/fiber.h"
#include "base/flags.h"
#include "net/span.h"
#include "net/channel.h"
#include "net/cluster.h"
#include "net/deadline.h"
#include "net/lb_hint.h"
#include "net/naming.h"
#include "net/controller.h"
#include "net/fault.h"
#include "base/proc.h"
#include "net/ici_transport.h"
#include "net/infer.h"
#include "net/kvstore.h"
#include "net/rma.h"
#include "stat/slo.h"
#include "net/server.h"

using namespace trpc;

namespace {

struct PendingCall {
  Controller* cntl;
  IOBuf* response;
  Closure done;
  std::atomic<bool> responded{false};
};

using HandlerCb = void (*)(void* call_handle, const char* req, size_t req_len,
                           void* user_ctx);

}  // namespace

namespace trpc {
// Internal accessor for sibling capi TUs (qos_capi.cc): the controller of
// an in-flight PendingCall handle.  Valid only while the handle is —
// i.e. before its trpc_call_respond.
Controller* trpc_internal_pending_controller(void* call_handle) {
  return static_cast<PendingCall*>(call_handle)->cntl;
}
}  // namespace trpc

extern "C" {

// ---- server -------------------------------------------------------------

void* trpc_server_create() { return new Server(); }

void trpc_server_destroy(void* srv) {
  // ~Server may run an owned Announcer's withdraw RPC (net/naming.h)
  // and fiber joins: pin like the sync call paths so a ctypes caller
  // returns on the pthread it entered on.
  ScopedPthreadWait pin;
  delete static_cast<Server*>(srv);
}

int trpc_server_register(void* srv, const char* method, HandlerCb cb,
                         void* user_ctx) {
  return static_cast<Server*>(srv)->RegisterMethod(
      method, [cb, user_ctx](Controller* cntl, const IOBuf& req,
                             IOBuf* resp, Closure done) {
        auto* pending = new PendingCall();
        pending->cntl = cntl;
        pending->response = resp;
        pending->done = std::move(done);
        const std::string flat = req.to_string();
        cb(pending, flat.data(), flat.size(), user_ctx);
      });
}

// Completes a call (callable from the handler callback or any thread
// later).  Idempotent: a second respond on the same handle is ignored, so
// an async-handler/error-path race cannot double-complete.  err_text may be
// null.  Returns 0 if this call completed the RPC, -1 if already done.
int trpc_call_respond(void* call_handle, const char* data, size_t len,
                      int err_code, const char* err_text) {
  auto* pending = static_cast<PendingCall*>(call_handle);
  bool expect = false;
  if (!pending->responded.compare_exchange_strong(
          expect, true, std::memory_order_acq_rel)) {
    return -1;
  }
  if (err_code != 0) {
    pending->cntl->SetFailed(err_code, err_text != nullptr ? err_text : "");
  } else if (data != nullptr && len > 0) {
    pending->response->append(data, len);
  }
  pending->done();
  delete pending;
  return 0;
}

// Registers a NATIVE zero-copy echo handler (response shares the request
// blocks by reference; no Python callback, no GIL).  The server-side
// anchor for the Python data-plane benchmarks and the batch-API perf
// floor: against a Python handler they would measure the server's GIL,
// not the client pipeline.
int trpc_server_register_echo(void* srv, const char* method) {
  return static_cast<Server*>(srv)->RegisterMethod(
      method, [](Controller*, const IOBuf& req, IOBuf* resp, Closure done) {
        resp->append(req);  // zero-copy ref share
        done();
      });
}

int trpc_server_start(void* srv, int port) {
  return static_cast<Server*>(srv)->Start(port);
}

int trpc_server_port(void* srv) { return static_cast<Server*>(srv)->port(); }

void trpc_server_stop(void* srv) { static_cast<Server*>(srv)->Stop(); }

// ---- single-server channel ---------------------------------------------

namespace {
void* create_channel(const char* addr, int64_t timeout_ms, bool use_shm,
                     const char* conn_type = nullptr) {
  auto* ch = new Channel();
  Channel::Options opts;
  opts.timeout_ms = timeout_ms;
  opts.use_shm = use_shm;
  if (conn_type != nullptr && conn_type[0] != '\0') {
    opts.connection_type = conn_type;
  }
  if (ch->Init(addr, &opts) != 0) {
    delete ch;
    return nullptr;
  }
  return ch;
}

// Flags register lazily from function-local statics (rpcz_enabled on its
// first check, per-method bounds at registration); a fresh process using
// ONLY the flag API would otherwise see "unknown flag".  Touch the static
// runtime flags here.
void ensure_runtime_flags() {
  rpcz_enabled();
  rpcz_ring_capacity();  // registers trpc_rpcz_ring_size
  fault_register_flag();
  cluster_ensure_registered();     // trpc_cluster_* knobs
  Server::drain_ensure_registered();  // trpc_drain_deadline_ms
  naming_ensure_registered();      // trpc_naming_* + trpc_fleet_publish
  deadline_ensure_registered();    // trpc_deadline_wire + retry budget
  slo::ensure_registered();        // trpc_slo + burn windows/alert
  kv_ensure_registered();          // trpc_kv_* incl. prefix block span
  infer_ensure_registered();       // trpc_infer_* serving knobs
}
}  // namespace

void* trpc_channel_create(const char* addr, int64_t timeout_ms) {
  return create_channel(addr, timeout_ms, false);
}

// Same-host shared-memory variant (falls back to TCP if the handshake
// fails; see net/shm_transport.h).
void* trpc_channel_create_shm(const char* addr, int64_t timeout_ms) {
  return create_channel(addr, timeout_ms, true);
}

// Full-option creation: conn_type "single"/"pooled"/"short"
// (socket_map.h matrix).  Returns nullptr on bad address/options.
void* trpc_channel_create_ex(const char* addr, int64_t timeout_ms,
                             const char* conn_type, int use_shm) {
  return create_channel(addr, timeout_ms, use_shm != 0, conn_type);
}

// Runtime flag access (base/flags.h; the /flags service's programmatic
// form).  Returns 0 on success (set) / found (get).
int trpc_flag_set(const char* name, const char* value) {
  ensure_runtime_flags();
  return Flag::set(name, value);
}

// Returns 0 on success, -1 unknown flag, -2 when the value does not fit
// (nothing written in that case; also guards degenerate buffers).
int trpc_flag_get(const char* name, char* out, size_t out_len) {
  ensure_runtime_flags();
  Flag* f = Flag::find(name);
  if (f == nullptr) {
    return -1;
  }
  const std::string v = f->value_string();
  if (out == nullptr || out_len == 0 || v.size() + 1 > out_len) {
    return -2;
  }
  memcpy(out, v.c_str(), v.size() + 1);
  return 0;
}

// Copies the live transport name ("tcp", "shm_ring", "" if unconnected).
void trpc_channel_transport(void* ch, char* out, size_t out_len) {
  const std::string name = static_cast<Channel*>(ch)->transport_name();
  strncpy(out, name.c_str(), out_len - 1);
  out[out_len - 1] = '\0';
}

void trpc_channel_destroy(void* ch) { delete static_cast<Channel*>(ch); }

// Synchronous call.  Returns 0 on success and fills *resp (a trpc_iobuf
// handle created by the caller); on failure returns the error code and
// copies the error text into err_buf.
namespace {
int call_channel_sync(void* ch, const char* method, const IOBuf& request,
                      void* resp_iobuf, int64_t timeout_ms, char* err_buf,
                      size_t err_buf_len) {
  // GIL safety: a ctypes caller must return on the pthread it entered on,
  // so any park inside the sync call blocks the thread, never migrates.
  ScopedPthreadWait pin;
  Controller cntl;
  if (timeout_ms > 0) {
    cntl.set_timeout_ms(timeout_ms);
  }
  static_cast<Channel*>(ch)->CallMethod(
      method, request, static_cast<IOBuf*>(resp_iobuf), &cntl);
  if (cntl.Failed()) {
    if (err_buf != nullptr && err_buf_len > 0) {
      strncpy(err_buf, cntl.error_text().c_str(), err_buf_len - 1);
      err_buf[err_buf_len - 1] = '\0';
    }
    return cntl.error_code() != 0 ? cntl.error_code() : -1;
  }
  return 0;
}
}  // namespace

int trpc_channel_call(void* ch, const char* method, const char* req,
                      size_t req_len, void* resp_iobuf, int64_t timeout_ms,
                      char* err_buf, size_t err_buf_len) {
  IOBuf request;
  request.append(req, req_len);
  return call_channel_sync(ch, method, request, resp_iobuf, timeout_ms,
                           err_buf, err_buf_len);
}

// IOBuf-request variant: the request IOBuf handle is used as-is (no
// flattening/copy — arena blocks ride straight to the wire).  The handle
// remains caller-owned; its payload is shared, not consumed.
int trpc_channel_call_buf(void* ch, const char* method, void* req_iobuf,
                          void* resp_iobuf, int64_t timeout_ms,
                          char* err_buf, size_t err_buf_len) {
  return call_channel_sync(ch, method, *static_cast<IOBuf*>(req_iobuf),
                           resp_iobuf, timeout_ms, err_buf, err_buf_len);
}

// ---- fault injection (net/fault.h) --------------------------------------

// Installs the process-wide transport fault schedule through the
// fault_schedule flag (so /flags and /faults observe the same value).
// Empty spec disables.  Returns 0, nonzero on a malformed spec.
int trpc_fault_set(const char* spec) {
  ensure_runtime_flags();
  return Flag::set("fault_schedule", spec != nullptr ? spec : "");
}

// Copies the canonical active schedule ("" when off).  Returns 0, or -2
// when the buffer is too small.
int trpc_fault_get(char* out, size_t out_len) {
  const std::string s = FaultActor::global().spec();
  if (out == nullptr || out_len == 0 || s.size() + 1 > out_len) {
    return -2;
  }
  memcpy(out, s.c_str(), s.size() + 1);
  return 0;
}

// Copies the injected-fault log ("#index point kind" lines, oldest
// first; truncated from the front if the buffer is too small).  Returns
// the number of bytes written (excluding the NUL).
size_t trpc_fault_log(char* out, size_t out_len) {
  if (out == nullptr || out_len == 0) {
    return 0;
  }
  std::string s = FaultActor::global().log_text();
  if (s.size() + 1 > out_len) {
    // Truncate from the front on a LINE boundary so the first returned
    // entry is never a garbled fragment.
    size_t start = s.size() + 1 - out_len;
    const size_t nl = s.find('\n', start);
    start = nl == std::string::npos ? s.size() : nl + 1;
    s = s.substr(start);
  }
  memcpy(out, s.c_str(), s.size() + 1);
  return s.size();
}

// Restarts the deterministic sequence (counter + log; schedule kept) —
// the seam the seed-replay assertion uses.
void trpc_fault_reset() { FaultActor::global().reset_counters(); }

uint64_t trpc_fault_injected() { return FaultActor::global().injected(); }

// Per-server dispatch/accept fault schedule (svr_* fields).  Returns 0,
// -1 on a malformed spec.
int trpc_server_fault_set(void* srv, const char* spec) {
  return static_cast<Server*>(srv)->SetFaults(spec != nullptr ? spec : "");
}

// ---- cluster channel ----------------------------------------------------

void* trpc_cluster_create_ex(const char* naming_url, const char* lb,
                             int64_t timeout_ms, int max_retry,
                             int64_t backup_request_ms,
                             const char* health_method,
                             int64_t health_timeout_ms,
                             int64_t refresh_interval_ms);

void* trpc_cluster_create(const char* naming_url, const char* lb,
                          int64_t timeout_ms, int max_retry) {
  return trpc_cluster_create_ex(naming_url, lb, timeout_ms, max_retry, 0,
                                nullptr, 0, 0);
}

// Full-option cluster creation: hedging (backup_request_ms > 0 races a
// second attempt after that budget), health-check probe method/timeout
// (empty method disables probing) and the re-resolve/probe cadence.
// Zero/negative numeric options mean "keep the default"; health_method
// nullptr keeps the default, "" disables.
void* trpc_cluster_create_ex(const char* naming_url, const char* lb,
                             int64_t timeout_ms, int max_retry,
                             int64_t backup_request_ms,
                             const char* health_method,
                             int64_t health_timeout_ms,
                             int64_t refresh_interval_ms) {
  auto* ch = new ClusterChannel();
  ClusterChannel::Options opts;
  opts.timeout_ms = timeout_ms;
  opts.max_retry = max_retry;
  if (backup_request_ms > 0) {
    opts.backup_request_ms = backup_request_ms;
  }
  if (health_method != nullptr) {
    opts.health_check_method = health_method;
  }
  if (health_timeout_ms > 0) {
    opts.health_check_timeout_ms = health_timeout_ms;
  }
  if (refresh_interval_ms > 0) {
    opts.refresh_interval_ms = refresh_interval_ms;
  }
  if (ch->Init(naming_url, lb, &opts) != 0) {
    delete ch;
    return nullptr;
  }
  return ch;
}

void trpc_cluster_destroy(void* ch) {
  delete static_cast<ClusterChannel*>(ch);
}

int trpc_cluster_call(void* ch, const char* method, const char* req,
                      size_t req_len, void* resp_iobuf, uint64_t hash_key,
                      char* err_buf, size_t err_buf_len) {
  ScopedPthreadWait pin;  // see trpc_channel_call
  Controller cntl;
  IOBuf request;
  request.append(req, req_len);
  static_cast<ClusterChannel*>(ch)->CallMethod(
      method, request, static_cast<IOBuf*>(resp_iobuf), &cntl, nullptr,
      hash_key);
  if (cntl.Failed()) {
    if (err_buf != nullptr && err_buf_len > 0) {
      strncpy(err_buf, cntl.error_text().c_str(), err_buf_len - 1);
      err_buf[err_buf_len - 1] = '\0';
    }
    return cntl.error_code() != 0 ? cntl.error_code() : -1;
  }
  return 0;
}

// Cache-aware variant (net/lb_hint.h): hint_addr ("host:port") names
// the member holding the longest cached prefix; the c_hash_bl walk
// honors it on attempt 0 unless bounded load vetoes.  An empty or
// unparseable hint degrades to trpc_cluster_call semantics — routing
// hints are advisory, never load-bearing for correctness.
int trpc_cluster_call_hinted(void* ch, const char* method, const char* req,
                             size_t req_len, void* resp_iobuf,
                             uint64_t hash_key, const char* hint_addr,
                             char* err_buf, size_t err_buf_len) {
  EndPoint hint;
  const bool have_hint = hint_addr != nullptr && hint_addr[0] != '\0' &&
                         hostname2endpoint(hint_addr, &hint) == 0;
  ScopedPthreadWait pin;  // see trpc_channel_call
  Controller cntl;
  IOBuf request;
  request.append(req, req_len);
  {
    // Scope the ambient hint to exactly this call: a leaked hint would
    // silently re-route the thread's next unrelated call.
    LbHintScope scope(have_hint ? hint : EndPoint());
    if (!have_hint) {
      lb_hint_clear();
    }
    static_cast<ClusterChannel*>(ch)->CallMethod(
        method, request, static_cast<IOBuf*>(resp_iobuf), &cntl, nullptr,
        hash_key);
  }
  if (cntl.Failed()) {
    if (err_buf != nullptr && err_buf_len > 0) {
      strncpy(err_buf, cntl.error_text().c_str(), err_buf_len - 1);
      err_buf[err_buf_len - 1] = '\0';
    }
    return cntl.error_code() != 0 ? cntl.error_code() : -1;
  }
  return 0;
}

// Hint routing outcomes since process start (hit = hinted member
// selected, veto = bounded load overrode the hint, miss = hinted member
// absent or unhealthy).
void trpc_lb_hint_counters(uint64_t* hit, uint64_t* veto, uint64_t* miss) {
  LbHintCounters& c = lb_hint_counters();
  if (hit != nullptr) {
    *hit = LbHintCounters::read(c.hit);
  }
  if (veto != nullptr) {
    *veto = LbHintCounters::read(c.veto);
  }
  if (miss != nullptr) {
    *miss = LbHintCounters::read(c.miss);
  }
}

}  // extern "C"

// ---- full-stack native benchmark ----------------------------------------

namespace {

struct NativeBenchWorker {
  Channel* ch = nullptr;
  const void* data = nullptr;
  size_t len = 0;
  int calls = 0;
  std::atomic<long>* failures = nullptr;
};

void noop_deleter(void*, void*) {}

void native_bench_fiber(void* p) {
  auto* w = static_cast<NativeBenchWorker*>(p);
  for (int i = 0; i < w->calls; ++i) {
    Controller cntl;
    cntl.set_timeout_ms(60000);
    // Payload enters the wire path BY REFERENCE from the pre-registered
    // staging buffer — zero client-side copies (append_user_data_with_meta
    // parity; the buffer outlives the synchronous loop by contract).
    IOBuf req, resp;
    req.append_user_data(const_cast<void*>(w->data), w->len, &noop_deleter);
    w->ch->CallMethod("Echo.Echo", req, &resp, &cntl);
    if (cntl.Failed() || resp.size() != w->len) {
      w->failures->fetch_add(1);
    }
  }
}

}  // namespace

extern "C" {

// Runs the ENTIRE echo loop inside the runtime — the calling pthread only
// parks, and ctypes released the GIL on entry, so Python is out of the
// measured path (the r3 0.36 GB/s ceiling was the per-call Python bounce).
// An in-process Server with a ref-sharing native echo handler serves
// `concurrency` fibers, each issuing synchronous calls whose payload is
// `len` bytes referenced (not copied) from `data`.  transport: "tcp",
// "shm" or "ici" (ici = the DMA-ring endpoint, net/ici_transport.h).
// Returns 0 and fills *out_gbps (payload bytes × calls / elapsed, the
// rpc_press goodput convention) and transport_used; nonzero on failure
// (first response mismatch, channel init failure, any call failure).
// resp_out (nullable, len bytes): receives one post-loop echo response so
// the caller can close the device→wire→device loop on REAL echoed bytes.
int trpc_bench_echo_rpc(const void* data, size_t len, int iters,
                        int concurrency, const char* transport,
                        void* resp_out, double* out_gbps,
                        char* transport_used, size_t tu_len, char* err,
                        size_t err_len) {
  auto fail = [&](const char* msg) {
    if (err != nullptr && err_len > 0) {
      strncpy(err, msg, err_len - 1);
      err[err_len - 1] = '\0';
    }
    return -1;
  };
  if (data == nullptr || len == 0 || iters <= 0 || concurrency <= 0) {
    return fail("bad arguments");
  }
  const std::string tr = transport != nullptr ? transport : "tcp";
  // Bench geometry is a process-global proposal for NEW client conns:
  // restore the embedder's configured value on every exit path so later
  // ICI connections don't silently inherit bench geometry.
  struct GeometryGuard {
    uint32_t bs = 0, sl = 0, mb = 0;
    bool armed = false;
    ~GeometryGuard() {
      if (armed) {
        ici_set_ring_geometry(bs, sl, mb);
      }
    }
  } geom_guard;
  if (tr == "ici") {
    ici_get_ring_geometry(&geom_guard.bs, &geom_guard.sl, &geom_guard.mb);
    // Wide window + 256KB DMA blocks so a 64MB payload is ~256 WRs and
    // the pool comfortably holds request+response in flight.
    geom_guard.armed = ici_set_ring_geometry(256 * 1024, 32, 1024);
  }
  Server server;
  server.RegisterMethod("Echo.Echo", [](Controller*, const IOBuf& req,
                                        IOBuf* resp, Closure done) {
    resp->append(req);  // zero-copy ref share
    done();
  });
  if (server.Start(0) != 0) {
    return fail("server start failed");
  }
  Channel ch;
  Channel::Options copts;
  copts.timeout_ms = 60000;
  copts.use_shm = tr == "shm";
  copts.use_ici = tr == "ici";
  char addr[64];
  snprintf(addr, sizeof(addr), "127.0.0.1:%d", server.port());
  if (ch.Init(addr, &copts) != 0) {
    server.Stop();
    return fail("channel init failed");
  }
  {
    // Warm + verify: one full round trip, content-checked.
    Controller cntl;
    cntl.set_timeout_ms(60000);
    IOBuf req, resp;
    req.append_user_data(const_cast<void*>(data), len, &noop_deleter);
    ch.CallMethod("Echo.Echo", req, &resp, &cntl);
    if (cntl.Failed()) {
      server.Stop();
      return fail(cntl.error_text().c_str());
    }
    std::string back = resp.to_string();
    if (back.size() != len || memcmp(back.data(), data, len) != 0) {
      server.Stop();
      return fail("echo verification mismatch");
    }
  }
  if (transport_used != nullptr && tu_len > 0) {
    const std::string name = ch.transport_name();
    strncpy(transport_used, name.c_str(), tu_len - 1);
    transport_used[tu_len - 1] = '\0';
  }
  std::atomic<long> failures{0};
  std::vector<NativeBenchWorker> workers(concurrency);
  std::vector<fiber_t> fids(concurrency);
  const int per = iters / concurrency > 0 ? iters / concurrency : 1;
  const int64_t t0 = monotonic_time_us();
  for (int i = 0; i < concurrency; ++i) {
    workers[i] = NativeBenchWorker{&ch, data, len, per, &failures};
    fiber_start(&fids[i], &native_bench_fiber, &workers[i], 0);
  }
  for (int i = 0; i < concurrency; ++i) {
    fiber_join(fids[i]);
  }
  const int64_t dt = monotonic_time_us() - t0;
  if (failures.load() > 0) {
    server.Stop();
    return fail("calls failed during the measured loop");
  }
  if (resp_out != nullptr) {
    Controller cntl;
    cntl.set_timeout_ms(60000);
    IOBuf req, resp;
    req.append_user_data(const_cast<void*>(data), len, &noop_deleter);
    ch.CallMethod("Echo.Echo", req, &resp, &cntl);
    if (cntl.Failed() || resp.copy_to(resp_out, len) != len) {
      server.Stop();
      return fail("post-loop response fetch failed");
    }
  }
  server.Stop();
  if (out_gbps != nullptr) {
    *out_gbps = static_cast<double>(len) * (per * concurrency) /
                (dt * 1e-6) / 1e9;
  }
  return 0;
}

// Sender-owned zero-copy staging (net/ici_transport.h): registered,
// shm-published payload memory the ICI ring ships WITHOUT its DMA copy —
// one descriptor per payload, receiver wraps the bytes in place.  Python
// views the slab via np.frombuffer and lands device fetches in it
// (zerocopy.alloc_staging; chip_smoke.py's staged leg).
void* trpc_ici_staging_alloc(size_t len, uint32_t* ordinal_out) {
  return ici_staging_alloc(len, ordinal_out);
}

void trpc_ici_staging_free(void* base) { ici_staging_free(base); }

void trpc_ici_zero_copy_counters(uint64_t* wrs, uint64_t* bytes) {
  ici_zero_copy_counters(wrs, bytes);
}

// One-sided RMA regions (net/rma.h).  trpc_rma_alloc returns `len`
// usable shm-backed bytes registered under *rkey_out; a batch resp_buf
// pointing at them becomes a genuine remote-write target (the request
// advertises the rkey, the server puts the response straight in).
// Python views the buffer via (ctypes.c_char * len).from_address.
void* trpc_rma_alloc(size_t len, uint64_t* rkey_out) {
  return rma_alloc(len, rkey_out);
}

void trpc_rma_free(void* data) { rma_free(data); }

// Local-only pin of arbitrary caller memory (0 on failure).
uint64_t trpc_rma_reg(const void* buf, size_t len) {
  return rma_reg(buf, len);
}

int trpc_rma_unreg(uint64_t rkey) { return rma_unreg(rkey); }

// Live regions (tests).
size_t trpc_rma_region_count() { return rma_region_count(); }

// Runtime kernel-capability probe (base/proc.h): 1 supported, 0 not,
// -1 unknown feature.  "io_uring" records the ROADMAP item 2 gate —
// this box's 4.4.0 kernel answers ENOSYS.
int trpc_kernel_supports(const char* feature) {
  return kernel_supports(feature);
}

// Full-option channel creation including the transport: "tcp", "shm",
// "ici".  conn_type as trpc_channel_create_ex.
void* trpc_channel_create_transport(const char* addr, int64_t timeout_ms,
                                    const char* conn_type,
                                    const char* transport) {
  auto* ch = new Channel();
  Channel::Options opts;
  opts.timeout_ms = timeout_ms;
  const std::string tr = transport != nullptr ? transport : "tcp";
  opts.use_shm = tr == "shm";
  opts.use_ici = tr == "ici";
  if (conn_type != nullptr && conn_type[0] != '\0') {
    opts.connection_type = conn_type;
  }
  if (ch->Init(addr, &opts) != 0) {
    delete ch;
    return nullptr;
  }
  return ch;
}

}  // extern "C"
