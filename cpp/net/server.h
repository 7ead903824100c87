// Server — service registry + acceptor + request execution.
//
// Parity: brpc::Server (/root/reference/src/brpc/server.h:489 AddService /
// Start lifecycle; server.cpp:831 StartInternal; acceptor.cpp:52,251 the
// accept-until-EAGAIN loop).  Condensed: services are method-name → handler
// entries in a FlatMap; each request runs in its own fiber with a done
// closure that packs and writes the response on the wait-free socket path.
#pragma once

#include <atomic>
#include <functional>
#include <memory>
#include <string>
#include <vector>

#include <errno.h>

#include "base/flat_map.h"
#include "net/auth.h"
#include "base/recordio.h"
#include "fiber/sync.h"
#include "net/concurrency_limiter.h"
#include "net/controller.h"
#include "net/data_pool.h"
#include "net/fault.h"
#include "net/qos.h"
#include "net/socket.h"
#include "stat/latency_recorder.h"
#include "stat/reducer.h"

namespace trpc {

class RedisService;   // net/redis.h
class ThriftService;  // net/thrift.h
class MemcacheService;  // net/memcache.h
class MongoService;     // net/mongo.h
class RtmpService;      // net/rtmp.h
class NsheadService;  // net/nshead.h
class EspService;     // net/nshead.h
class SloEngine;      // stat/slo.h

class Server {
 public:
  // Handler runs in a fiber; it may block on fiber primitives freely.
  // Call done() exactly once (async responses allowed).
  using Handler = std::function<void(
      Controller* cntl, const IOBuf& request, IOBuf* response, Closure done)>;

  // Per-method properties (parity: MethodProperty + MethodStatus,
  // server.h:399 / details/method_status.h — auto-created qps/latency vars).
  // Where the calls of one method spent their time inside this process,
  // from the four stamps of a tstd request (server.cc,
  // tstd_process_request): always-on, folded once a call at the end of
  // its done(), so a delta over any window holds whole calls only.
  // Exposed as rpc_server_<method>_{calls,queue_us,handler_us,send_us};
  // one set a method name in the process, shared by every Server that
  // registers it.
  struct MethodPhases {
    explicit MethodPhases(const std::string& method);
    Adder calls;       // requests answered, whatever the status
    Adder queue_us;    // request whole -> handler entered
    Adder handler_us;  // handler entered -> its done() ran (0: no handler)
    Adder send_us;     // done() entered -> response handed to the connection
  };
  struct MethodProperty {
    Handler handler;
    // Dispatch start (entry of tstd_process_request, or the adaptor's)
    // to response handed off: the limiter's input, unchanged by phases.
    std::shared_ptr<LatencyRecorder> latency;
    std::shared_ptr<ConcurrencyLimiter> limiter;  // null = unlimited
    std::shared_ptr<MethodPhases> phases;
  };

  // Admission control for one method: "" unlimited, "<N>" constant, "auto"
  // (AIMD).  Call before Start.
  int SetMethodMaxConcurrency(const std::string& method,
                              const std::string& spec);

  // Per-tenant QoS (net/qos.h TenantGovernor): weighted-fair tenants with
  // their own admission limiters, shedding kEOverloaded when a tenant is
  // over its bound.  Spec grammar (';'-separated):
  //   "<tenant>:weight=N,limit=<spec>" with tenant "*" as the default
  //   clause; limit uses the concurrency_limiter.h grammar.
  // Composes with (runs BEFORE) the per-method limiter.  "" removes.
  // Call before Start.  Returns 0, or -1 on a malformed spec (previous
  // governor kept).
  int SetQos(const std::string& spec);
  std::shared_ptr<TenantGovernor> qos_governor() const { return qos_; }

  // Per-tenant SLO targets (stat/slo.h SloEngine): windowed attainment +
  // multi-window error-budget burn rates, fed from the dispatch path when
  // the reloadable `trpc_slo` flag is on.  Spec grammar (';'-separated):
  //   "<tenant>:p99_us=N,avail=P" with tenant "*" as the default clause;
  //   avail is a percent like 99.9.  "" removes.  Call before Start.
  // Returns 0, or -1 on a malformed spec (previous engine kept).
  // Surfaced by /slo, slo_* vars and — with trpc_fleet_publish on —
  // the naming:// fleet publication.
  int SetSlo(const std::string& spec);
  std::shared_ptr<SloEngine> slo_engine() const { return slo_; }

  // Shards the TCP acceptor across `n` SO_REUSEPORT listen sockets
  // (1..kMaxAcceptShards), each registered with its own event-dispatcher
  // slot (trpc_event_dispatchers) so accept storms spread over epoll
  // threads instead of serializing on one listener.  The kernel spreads
  // connections across shards by 4-tuple hash.  Call before Start.
  static constexpr int kMaxAcceptShards = 16;
  int set_reuseport_shards(int n);
  int reuseport_shards() const { return reuseport_shards_; }
  // Connections accepted by each shard (accept-distribution telemetry).
  std::vector<uint64_t> accept_counts() const;

  // Installs connection authentication (auth.h; not owned).  Call before
  // Start.  With an authenticator set, every framed-protocol connection
  // must open with a valid kAuth credential or its requests are refused.
  void set_authenticator(const Authenticator* auth) { auth_ = auth; }
  const Authenticator* authenticator() const { return auth_; }

  // Pins this server's connections (read fibers, handlers, KeepWrite — the
  // whole downstream) to a tagged worker group (fiber.h kMaxFiberTags;
  // parity: ServerOptions::bthread_tag, server.h:280 + per-tag TaskControl
  // groups, task_control.h:94-99).  Saturating one server's tag cannot
  // starve another's workers.  Call before Start; the tag's worker group
  // is provisioned on Start (default size unless fiber_start_tag_workers
  // ran first).
  void set_worker_tag(int tag) { worker_tag_ = tag; }
  int worker_tag() const { return worker_tag_; }

  // Request interceptor (parity: brpc::Interceptor, interceptor.h:26,
  // whose Accept sees the Controller): runs before EVERY request on every
  // serving protocol — RPC methods AND builtin observability paths (only
  // /health stays open, like auth) — with the method-or-path and the
  // peer.  Return false (optionally setting *error_code/*error_text) to
  // reject without reaching the handler.  Call before Start.
  using Interceptor = std::function<bool(
      const std::string& method, const EndPoint& peer, int* error_code,
      std::string* error_text)>;
  void set_interceptor(Interceptor icpt) { interceptor_ = std::move(icpt); }
  const Interceptor& interceptor() const { return interceptor_; }

  // Makes this server speak redis (RESP) on its port alongside the other
  // protocols (net/redis.h; parity: ServerOptions::redis_service,
  // redis.h:194).  Not owned.  Call before Start.
  void set_redis_service(RedisService* rs) { redis_service_ = rs; }
  RedisService* redis_service() const { return redis_service_; }

  // Makes this server speak framed thrift (TBinaryProtocol) on its port
  // (net/thrift.h; parity: ServerOptions::thrift_service,
  // thrift_service.h).  Not owned.  Call before Start.
  void set_thrift_service(ThriftService* ts) { thrift_service_ = ts; }
  ThriftService* thrift_service() const { return thrift_service_; }

  // Makes this server speak the memcache binary protocol on its port
  // (net/memcache.h; the reference is client-only — policy/
  // memcache_binary_protocol.cpp — the serving side here doubles as the
  // in-process fixture its tests fake externally).  Not owned.
  void set_memcache_service(MemcacheService* ms) { memcache_service_ = ms; }
  MemcacheService* memcache_service() const { return memcache_service_; }

  // Runs method handlers on the usercode backup pthread pool instead of
  // fiber workers (net/usercode_pool.h; parity: usercode_in_pthread +
  // details/usercode_backup_pool.h:46).  For handlers that block on
  // pthread-level primitives, which would otherwise pin fiber workers.
  // Call before Start.
  void set_usercode_in_pthread(bool on) { usercode_in_pthread_ = on; }
  bool usercode_in_pthread() const { return usercode_in_pthread_; }

  // Session-local data: pooled per-request scratch objects handed to
  // handlers via Controller::session_local_data() (net/data_pool.h;
  // parity: ServerOptions::session_local_data_factory +
  // reserved_session_local_data, simple_data_pool.*).  Factory not
  // owned.  Call before Start.
  void set_session_local_data_factory(DataFactory* f, size_t reserve = 0) {
    session_data_factory_ = f;
    session_data_reserve_ = reserve;
  }
  SimpleDataPool* session_data_pool() const {
    return session_data_pool_.get();
  }

  // Makes this server answer mongo drivers (OP_MSG) on its port
  // (net/mongo.h; parity: policy/mongo_protocol.cpp server adaptor).
  // Not owned.  Call before Start.
  void set_mongo_service(MongoService* ms) { mongo_service_ = ms; }
  MongoService* mongo_service() const { return mongo_service_; }

  // Makes this server speak RTMP (handshake 0x03, publish/play relay)
  // on its port (net/rtmp.h; parity: ServerOptions::rtmp_service,
  // rtmp.h).  Not owned.  Call before Start.
  void set_rtmp_service(RtmpService* rs) { rtmp_service_ = rs; }
  RtmpService* rtmp_service() const { return rtmp_service_; }

  // nshead-family personalities (net/nshead.h, net/legacy_pbrpc.h).  The
  // 36-byte head's magic is the shared discriminator, so install at most
  // ONE nshead-riding personality per server (raw nshead / nova pbrpc /
  // public pbrpc) — parity: ServerOptions::nshead_service is singular.
  void set_nshead_service(NsheadService* ns) { nshead_service_ = ns; }
  NsheadService* nshead_service() const { return nshead_service_; }
  // esp has NO wire magic: an esp-enabled server dedicates its port.
  void set_esp_service(EspService* es) { esp_service_ = es; }
  EspService* esp_service() const { return esp_service_; }

  // nova / public_pbrpc personalities (net/legacy_pbrpc.h): dispatch
  // nshead-framed pb calls into the method registry ("Nova.#<idx>" /
  // "<service>.#<id>" keys).  Same one-per-server rule as nshead above.
  void enable_nova_pbrpc() { nova_pbrpc_ = true; }
  bool nova_pbrpc_enabled() const { return nova_pbrpc_; }
  void enable_public_pbrpc() { public_pbrpc_ = true; }
  bool public_pbrpc_enabled() const { return public_pbrpc_; }

  // Serves TLS on this server's port (net/tls.h; parity: ServerOptions::
  // mutable_ssl_options, details/ssl_helper.cpp).  Plaintext clients KEEP
  // working on the same port — each accepted connection sniffs its first
  // byte (0x16 = TLS handshake record) and picks the path, like the
  // reference's sniffing acceptor.  PEM cert + key.  Call before Start;
  // returns 0 on success.
  // With a non-empty ca_file, client certificates are REQUIRED and
  // verified against it (mTLS); plaintext sniffing on the same port is
  // unaffected.
  int EnableTls(const std::string& cert_file, const std::string& key_file,
                const std::string& ca_file = "");
  // Shared acceptance check (one body for all protocols).  True = admit;
  // false fills *error_code/*error_text.
  bool accept_request(const std::string& method, const EndPoint& peer,
                      int* error_code, std::string* error_text) const {
    if (!interceptor_) {
      return true;
    }
    *error_code = EACCES;
    *error_text = "rejected by interceptor";
    return interceptor_(method, peer, error_code, error_text);
  }

  ~Server();

  // Register before Start.  Name format "Service.Method" by convention.
  int RegisterMethod(const std::string& full_name, Handler handler);

  // Catch-all handler (parity: BaiduMasterService,
  // baidu_master_service.h:36 + generic call proxying): tstd requests
  // whose method has no registered handler route here with the raw
  // body; the method name is Controller::method().  tstd only, like the
  // reference (BaiduMasterService serves baidu_std exclusively) — HTTP
  // and h2 answer 404/unimplemented as usual.  Call before Start.
  void set_generic_handler(Handler h) { generic_handler_ = std::move(h); }
  const Handler& generic_handler() const { return generic_handler_; }

  // Maps an HTTP path pattern onto a registered method (parity: the
  // reference's RestfulMap, restful.h:62).  Patterns match whole path
  // segments; '*' matches exactly one segment, a trailing '*' matches the
  // remainder ("/v1/echo/*").  Call before Start.
  int MapRestful(const std::string& pattern, const std::string& method);
  // Method mapped by the best-matching pattern, or nullptr;
  // *method_name receives the mapped method's registered name.
  const MethodProperty* find_restful(const std::string& path,
                                     std::string* method_name = nullptr) const;

  // port <= 0 picks an ephemeral port (see port() after).  Returns 0 on ok.
  int Start(int port);
  // Hot-restart successor entry point (the receiving half of Drain's
  // listener handoff): connects to the predecessor's unix handoff socket
  // at `path` (retrying until timeout_ms — the predecessor may not be
  // serving the handoff yet), receives the SO_REUSEPORT listener fds via
  // SCM_RIGHTS, and starts THIS server on them — the shared accept
  // queues mean no SYN is ever refused across the restart.  Register
  // methods before calling, exactly like Start.  The successor's RMA
  // windows/regions are minted fresh in this process (new shm segments,
  // new rkeys) — clients re-handshake rings on reconnect and never see a
  // stale rkey.  Returns 0 on ok.
  int StartFromHandoff(const std::string& path, int64_t timeout_ms = 10000);
  // Graceful drain (zero-downtime leave; ISSUE 12): flips this server to
  // kEDraining (new requests answer immediately with that status — the
  // cluster client fails over WITHOUT quarantining us), runs the drain
  // hooks (naming withdrawal, KV-block tombstoning), then — with a
  // non-empty handoff_path — serves the duplicated listener fds to the
  // successor over a unix socket at that path BEFORE closing our own, so
  // the kernel accept queues never go unowned.  Finally waits out
  // in-flight requests AND in-flight RMA window spans under the
  // deadline (<= 0 uses trpc_drain_deadline_ms).  Returns 0 when fully
  // quiesced, ETIMEDOUT when the deadline cut the wait short (the
  // server is draining either way; call Stop()/destroy as usual).
  int Drain(int64_t deadline_ms = 0, const std::string& handoff_path = "");
  // True from the start of Drain until destruction: new requests are
  // being answered kEDraining.
  bool draining() const {
    return draining_.load(std::memory_order_acquire);
  }
  // Flag registration (idempotent): trpc_drain_deadline_ms — the capi
  // calls it so /flags sees the drain knob before the first Drain.
  static void drain_ensure_registered();
  // Attaches the self-tuning controller (stat/tuner.h): registers the
  // trpc_tuner* flags/vars and flips trpc_tuner through the validated
  // reload path — the embedder's one-liner for "tune this process".
  // The controller is process-wide (it actuates process-wide flags),
  // so this is a convenience attach point, not per-server state.
  // Callable before or after Start; on=false flips it back off.
  // Returns true on success.
  bool EnableTuner(bool on = true);
  // Registers a hook run at the START of Drain (before the in-flight
  // wait): the seam the naming announcer (withdraw), the KV store
  // (tombstone + withdraw_all) and embedders use to leave the fleet
  // before the listener handoff.  Callable before or after Start.
  void add_drain_hook(std::function<void()> hook);
  // Ties a component's lifetime to this server (freed after Stop+Join in
  // ~Server) — e.g. the Announcer created by server_announce.
  void own_component(std::shared_ptr<void> c);
  // Listens on an AF_UNIX path instead (reference: unix sockets are
  // first-class EndPoints).  A stale socket file is unlinked first;
  // Stop unlinks it again.  Channel::Init("unix:<path>") connects.
  int StartUnix(const std::string& path);
  // Stops accepting, fails live connections; in-flight handlers finish.
  void Stop();
  // Parks until every in-flight request has completed (bounded by
  // timeout_ms; -1 = forever).  ~Server runs Stop()+Join() so destruction
  // can never race a handler touching server state.
  int Join(int64_t timeout_ms = 5000);
  // Blocks the calling thread until SIGINT/SIGTERM (parity:
  // brpc::Server::RunUntilAskedToQuit — the "serve forever" idiom for a
  // standalone main()).  NOTE: Join() waits for in-flight REQUESTS only,
  // so a daemon must call this, not Join, to stay up.
  static void RunUntilAskedToQuit();
  int port() const { return port_; }
  bool running() const { return running_.load(std::memory_order_acquire); }

  // -- internals --------------------------------------------------------
  const MethodProperty* find_method(const std::string& name) const {
    return methods_.seek(name);
  }
  template <typename Fn>
  void for_each_method(Fn&& fn) const {
    methods_.for_each(
        [&fn](const std::string& name, const MethodProperty&) { fn(name); });
  }
  std::atomic<int64_t> requests_served{0};
  std::atomic<int> in_flight{0};
  int64_t start_time_us() const { return start_time_us_; }
  void track_connection(SocketId id);

  // rpc_dump parity (/root/reference/src/brpc/rpc_dump.h:40-67): sample
  // incoming requests into a recordio file replayable by tools/rpc_replay.
  int EnableDump(const std::string& path, double sample_rate = 0.01);
  void maybe_dump(const std::string& method, uint32_t attachment_size,
                  const IOBuf& payload);

  // Server-side fault injection (net/fault.h; svr_delay / svr_error /
  // svr_reject fields): a PRIVATE actor per server, so one node of an
  // in-process cluster can misbehave while its siblings stay clean (the
  // chaos soak's quarantine-isolation scenario).  "" disables; callable
  // at runtime (also reachable via this server's /faults?server=...).
  // Returns 0, or -1 on a malformed spec (previous schedule kept).
  int SetFaults(const std::string& spec) { return faults_.set(spec); }
  FaultActor& faults() { return faults_; }

 private:
  static void on_acceptable(SocketId id, void* ctx);
  // Shared pre-listen initialization (fibers, vars, protocol registry,
  // ring-handshake methods) for Start and StartFromHandoff.
  void start_runtime_init();
  // The serving half of the hot-restart handoff: listens on `path`,
  // waits (bounded) for the successor to connect, ships {port, nfds} +
  // dup'd listener fds via SCM_RIGHTS.  0 on success.
  int serve_handoff(const std::string& path, int64_t deadline_us);
  // Fails every listen socket (Drain hands off first; Stop reuses it).
  void fail_listeners();
  // One per listen shard; ctx handed to on_acceptable so the accept
  // counter attributes to the right shard.  Address-stable (unique_ptr)
  // for the sockets' lifetime.
  struct AcceptCtx {
    Server* srv;
    int shard;
  };
  // Creates + registers one listen socket for `fd` as shard `shard`.
  int install_listener(int fd, int shard);
  int64_t start_time_us_ = 0;
  std::unique_ptr<RecordWriter> dump_writer_;
  FiberMutex dump_mu_;
  std::atomic<double> dump_rate_{0.0};

  const Authenticator* auth_ = nullptr;
  Interceptor interceptor_;
  RedisService* redis_service_ = nullptr;
  ThriftService* thrift_service_ = nullptr;
  MemcacheService* memcache_service_ = nullptr;
  MongoService* mongo_service_ = nullptr;
  RtmpService* rtmp_service_ = nullptr;
  NsheadService* nshead_service_ = nullptr;
  EspService* esp_service_ = nullptr;
  bool usercode_in_pthread_ = false;
  int worker_tag_ = 0;
  Handler generic_handler_;
  DataFactory* session_data_factory_ = nullptr;
  size_t session_data_reserve_ = 0;
  std::unique_ptr<SimpleDataPool> session_data_pool_;
  bool nova_pbrpc_ = false;
  bool public_pbrpc_ = false;
  void* tls_ctx_ = nullptr;  // SSL_CTX (leaked singleton; net/tls.h)
  FlatMap<std::string, MethodProperty> methods_;
  // (pattern segments, trailing-wildcard, method name), longest first.
  struct RestfulRule {
    std::vector<std::string> segs;
    bool tail_wild = false;
    std::string method;
  };
  std::vector<RestfulRule> restful_;
  SocketId listen_id_ = 0;
  // REUSEPORT shards beyond the first (listen_id_ stays shard 0 so the
  // single-listener paths are untouched).
  std::vector<SocketId> extra_listen_ids_;
  std::vector<std::unique_ptr<AcceptCtx>> accept_ctxs_;
  std::atomic<uint64_t> accept_counts_[kMaxAcceptShards] = {};
  int reuseport_shards_ = 1;
  std::shared_ptr<TenantGovernor> qos_;
  std::shared_ptr<SloEngine> slo_;
  int port_ = -1;
  std::string unix_path_;  // non-empty when listening on AF_UNIX
  std::atomic<bool> running_{false};
  std::atomic<bool> draining_{false};
  std::mutex drain_mu_;  // guards drain_hooks_ and components_
  std::vector<std::function<void()>> drain_hooks_;
  std::vector<std::shared_ptr<void>> components_;
  std::mutex conns_mu_;
  std::vector<SocketId> conns_;      // stale ids harmless (versioned)
  size_t conns_prune_at_ = 4096;     // doubles with the live set (scale)
  std::vector<SocketId> drain_ids_;  // failed at Stop; awaited in ~Server
  // Server-side fault points; kServer scope rejects transport-only specs
  // that could never fire here (silent no-op prevention).
  FaultActor faults_{FaultScope::kServer};
};

}  // namespace trpc
