#include "net/server.h"

#include <signal.h>

#include <errno.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <sys/socket.h>
#include <unistd.h>

#include <algorithm>
#include <map>
#include <mutex>

#include "base/compress.h"
#include "base/flags.h"
#include "base/logging.h"
#include "base/rand.h"
#include "base/recordio.h"
#include "base/time.h"
#include "fiber/fiber.h"
#include "net/h2_protocol.h"
#include "net/http_protocol.h"
#include "net/redis.h"
#include "net/memcache.h"
#include "net/mongo.h"
#include "net/rtmp.h"
#include "net/usercode_pool.h"
#include "net/legacy_pbrpc.h"
#include "net/nshead.h"
#include "net/thrift.h"
#include "net/tls.h"
#include "net/deadline.h"
#include "net/messenger.h"
#include "net/ici_transport.h"
#include "net/shm_transport.h"
#include "net/span.h"
#include "stat/capture.h"
#include "stat/slo.h"
#include "stat/timeline.h"
#include "net/stream.h"
#include "net/rma.h"
#include "net/stripe.h"
#include "net/protocol.h"
#include "stat/tuner.h"

namespace trpc {

Server::~Server() {
  Stop();
  // A request fiber holds a strong socket ref across its entry section
  // (user_data read + in_flight registration), so once every failed
  // connection's refs have drained, in_flight is complete and Join() is
  // exact — no timing-based grace needed.
  const int64_t deadline = monotonic_time_us() + 5000000;
  {
    std::lock_guard<std::mutex> g(conns_mu_);
    for (SocketId id : drain_ids_) {
      while (Socket::Draining(id) && monotonic_time_us() < deadline) {
        usleep(1000);
      }
    }
  }
  Join();
  // Owned components (announcers etc.) die only after every in-flight
  // handler finished — their drain hooks may reference them.
  std::lock_guard<std::mutex> g(drain_mu_);
  components_.clear();
}

namespace {
std::vector<std::string> split_path(const std::string& p) {
  std::vector<std::string> segs;
  size_t pos = 0;
  while (pos < p.size()) {
    while (pos < p.size() && p[pos] == '/') {
      ++pos;
    }
    size_t end = p.find('/', pos);
    if (end == std::string::npos) {
      end = p.size();
    }
    if (end > pos) {
      segs.push_back(p.substr(pos, end - pos));
    }
    pos = end;
  }
  return segs;
}
}  // namespace

int Server::MapRestful(const std::string& pattern, const std::string& method) {
  if (running()) {
    return -1;  // same contract as RegisterMethod: configure before Start
  }
  if (methods_.seek(method) == nullptr) {
    return -1;  // map only registered methods
  }
  RestfulRule rule;
  rule.segs = split_path(pattern);
  if (!rule.segs.empty() && rule.segs.back() == "*") {
    // A trailing '*' matches one-or-more remaining segments.
    rule.tail_wild = true;
    rule.segs.pop_back();
  }
  rule.method = method;
  restful_.push_back(std::move(rule));
  // Longest (most specific) pattern wins at lookup.
  std::stable_sort(restful_.begin(), restful_.end(),
                   [](const RestfulRule& a, const RestfulRule& b) {
                     return a.segs.size() > b.segs.size();
                   });
  return 0;
}

const Server::MethodProperty* Server::find_restful(
    const std::string& path, std::string* method_name) const {
  if (restful_.empty()) {
    return nullptr;
  }
  const std::vector<std::string> segs = split_path(path);
  for (const RestfulRule& rule : restful_) {
    if (rule.tail_wild ? segs.size() <= rule.segs.size()
                       : segs.size() != rule.segs.size()) {
      continue;
    }
    bool ok = true;
    for (size_t i = 0; i < rule.segs.size(); ++i) {
      if (rule.segs[i] != "*" && rule.segs[i] != segs[i]) {
        ok = false;
        break;
      }
    }
    if (ok) {
      if (method_name != nullptr) {
        *method_name = rule.method;
      }
      return methods_.seek(rule.method);
    }
  }
  return nullptr;
}

Server::MethodPhases::MethodPhases(const std::string& method) {
  const std::string base = "rpc_server_" + method;
  calls.expose(base + "_calls",
               "tstd requests of " + method + " answered, whatever the "
               "status; the divisor of its three _us phase sums");
  queue_us.expose(base + "_queue_us",
                  "us from a request of " + method + " being cut from its "
                  "connection and whole to its handler being entered: "
                  "QoS lane, dispatch backlog, admission, injected delay");
  handler_us.expose(base + "_handler_us",
                    "us from the handler of " + method + " being entered "
                    "to its done() running (0 for a call answered before "
                    "any handler: shed, rejected, failed)");
  send_us.expose(base + "_send_us",
                 "us from the done() of " + method + " being entered to "
                 "the response being handed to the connection: the put "
                 "into a one-sided window, the stripes, or the frame");
}

namespace {

// One MethodPhases a method name in the process: a name in the variable
// registry has one owner, and two Servers that register the same method
// (a store on each of two ranks) are one series to whoever reads /vars.
// Weak: the series goes when the last Server with the method does.
std::shared_ptr<Server::MethodPhases> method_phases(
    const std::string& method) {
  static std::mutex* mu = new std::mutex();
  static auto* by_name =
      new std::map<std::string, std::weak_ptr<Server::MethodPhases>>();
  std::lock_guard<std::mutex> g(*mu);
  std::weak_ptr<Server::MethodPhases>& slot = (*by_name)[method];
  std::shared_ptr<Server::MethodPhases> phases = slot.lock();
  if (phases == nullptr) {
    phases = std::make_shared<Server::MethodPhases>(method);
    slot = phases;
  }
  return phases;
}

}  // namespace

int Server::RegisterMethod(const std::string& full_name, Handler handler) {
  if (running()) {
    return -1;
  }
  MethodProperty prop;
  prop.handler = std::move(handler);
  prop.latency = std::make_shared<LatencyRecorder>();
  prop.latency->expose("rpc_server_" + full_name,
                       "server-side latency of " + full_name);
  prop.phases = method_phases(full_name);
  methods_[full_name] = std::move(prop);
  return 0;
}

int Server::SetMethodMaxConcurrency(const std::string& method,
                                    const std::string& spec) {
  if (running()) {
    return -1;
  }
  MethodProperty* prop = methods_.seek(method);
  if (prop == nullptr) {
    return -1;
  }
  auto [ok, limiter] = parse_concurrency_spec(spec);
  if (!ok) {
    return -1;  // typo'd spec must not silently mean "unlimited"
  }
  prop->limiter = std::move(limiter);
  // A constant bound is exposed as a reloadable flag so /flags?setvalue
  // retargets the LIVE limiter (reloadable_flags.h + flags_service parity).
  // Flags are process-global while limiters are per-Server: the update
  // hook fans out to EVERY limiter ever bound to the name (weak refs, so
  // dead servers drop out) instead of the latest binding hijacking it.
  auto* constant = dynamic_cast<ConstantLimiter*>(prop->limiter.get());
  if (constant != nullptr) {
    std::string flag_name = "max_concurrency_" + method;
    for (char& c : flag_name) {
      if (c == '.') {
        c = '_';
      }
    }
    static std::mutex* bindings_mu = new std::mutex();
    static auto* bindings =
        new std::map<std::string,
                     std::vector<std::weak_ptr<ConcurrencyLimiter>>>();
    {
      std::lock_guard<std::mutex> g(*bindings_mu);
      (*bindings)[flag_name].push_back(prop->limiter);
    }
    Flag* f = Flag::define_int64(flag_name, constant->current_limit(),
                                 "admission bound for " + method);
    if (f != nullptr) {
      f->set_validator([](const std::string& v) {
        char* end = nullptr;
        const long n = strtol(v.c_str(), &end, 10);
        return end != v.c_str() && *end == '\0' && n > 0;
      });
      f->on_update([flag_name](Flag* self) {
        std::lock_guard<std::mutex> g(*bindings_mu);
        auto& vec = (*bindings)[flag_name];
        for (auto it = vec.begin(); it != vec.end();) {
          if (auto l = it->lock()) {
            static_cast<ConstantLimiter*>(l.get())
                ->set_limit(self->int64_value());
            ++it;
          } else {
            it = vec.erase(it);
          }
        }
      });
      // Explicit configuration is authoritative: push this limit into the
      // flag, which fans out to every limiter bound to the name (one knob,
      // one value — a pre-existing flag's stale value must not silently
      // override what this server just configured).
      f->set_from_string(std::to_string(constant->current_limit()));
    }
  }
  return 0;
}

int Server::SetQos(const std::string& spec) {
  if (running()) {
    return -1;
  }
  if (spec.empty()) {
    qos_.reset();
    return 0;
  }
  std::string err;
  auto gov = TenantGovernor::parse(spec, &err);
  if (gov == nullptr) {
    LOG(Warning) << "bad qos spec '" << spec << "': " << err;
    return -1;  // a typo must not silently mean "no QoS"
  }
  qos_ = std::move(gov);
  return 0;
}

int Server::SetSlo(const std::string& spec) {
  if (running()) {
    return -1;
  }
  if (spec.empty()) {
    slo_.reset();
    return 0;
  }
  std::string err;
  auto eng = SloEngine::parse(spec, &err);
  if (eng == nullptr) {
    LOG(Warning) << "bad slo spec '" << spec << "': " << err;
    return -1;  // a typo must not silently mean "no SLO"
  }
  slo_ = std::move(eng);
  return 0;
}

int Server::set_reuseport_shards(int n) {
  if (running() || n < 1 || n > kMaxAcceptShards) {
    return -1;
  }
  reuseport_shards_ = n;
  return 0;
}

std::vector<uint64_t> Server::accept_counts() const {
  std::vector<uint64_t> out(static_cast<size_t>(reuseport_shards_), 0);
  for (size_t i = 0; i < out.size(); ++i) {
    out[i] = accept_counts_[i].load(std::memory_order_relaxed);
  }
  return out;
}

int Server::install_listener(int fd, int shard) {
  auto actx = std::make_unique<AcceptCtx>();
  actx->srv = this;
  actx->shard = shard;
  Socket::Options opts;
  opts.fd = fd;
  opts.on_readable = &Server::on_acceptable;
  opts.ctx = actx.get();
  opts.user_data = this;
  opts.worker_tag = static_cast<uint8_t>(worker_tag_);
  SocketId id = 0;
  if (Socket::Create(opts, &id) != 0) {
    return -1;
  }
  accept_ctxs_.push_back(std::move(actx));
  if (shard == 0) {
    listen_id_ = id;
  } else {
    extra_listen_ids_.push_back(id);
  }
  return 0;
}

void expose_default_variables();  // stat/default_variables.cc
void expose_hotpath_variables();  // net/hotpath_stats.cc

void Server::start_runtime_init() {
  fiber_init(0);
  if (worker_tag_ != 0) {
    fiber_start_tag_workers(worker_tag_, 0);  // default size if not sized
  }
  expose_default_variables();
  expose_hotpath_variables();
  expose_qos_variables();
  if (session_data_factory_ != nullptr && session_data_pool_ == nullptr) {
    session_data_pool_ =
        std::make_unique<SimpleDataPool>(session_data_factory_);
    session_data_pool_->Reserve(session_data_reserve_);
  }
  tstd_protocol();  // ensure registered (first: most traffic is RPC)
  // hulu/sofa next: their 4-byte ASCII magics must be probed before the
  // HTTP parser sees the 'H'/'S' and holds the bytes as a method line.
  register_hulu_protocol();
  register_sofa_protocol();
  register_http_protocol();
  register_h2_protocol();
  if (thrift_service_ != nullptr) {
    register_thrift_protocol();
  }
  if (memcache_service_ != nullptr) {
    register_memcache_protocol();
  }
  if (mongo_service_ != nullptr) {
    register_mongo_protocol();
  }
  if (rtmp_service_ != nullptr) {
    register_rtmp_protocol();
  }
  // redis must precede the nshead family and esp: its '*' marker decides
  // instantly, while those probers HOLD short prefixes (no magic in the
  // first bytes) and would shadow a fragmented RESP command forever.
  if (redis_service_ != nullptr) {
    register_redis_protocol();
  }
  if (nshead_service_ != nullptr) {
    register_nshead_protocol();
  }
  if (nova_pbrpc_) {
    register_nova_protocol();
  }
  if (public_pbrpc_) {
    register_public_pbrpc_protocol();
  }
  if (esp_service_ != nullptr) {
    register_esp_protocol();  // last: esp has no magic to probe
  }
  start_time_us_ = monotonic_time_us();
  // Ring-transport handshakes (net/shm_transport.h, net/ici_transport.h):
  // a client sends the segment name it minted; we map it and serve that
  // connection over the rings.  Registered for every server — harmless if
  // unused.  If the client dies (or gives up) after our "ok", the ring
  // socket is not leaked: an attached-but-silent peer never bumps its
  // segment heartbeat, so the poller's 30s stall reaper fails the socket
  // and unlinks the segment.
  const auto register_ring = [this](const char* method, const char* what,
                                    int (*open_and_attach)(
                                        const std::string&, Server*,
                                        SocketId*)) {
    if (methods_.seek(method) != nullptr) {
      return;
    }
    RegisterMethod(method, [this, what, open_and_attach](
                               Controller* cntl, const IOBuf& req,
                               IOBuf* resp, Closure done) {
      SocketId sid = 0;
      if (open_and_attach(req.to_string(), this, &sid) != 0) {
        cntl->SetFailed(EINVAL, what);
        done();
        return;
      }
      track_connection(sid);
      resp->append("ok");
      done();
    });
  };
  register_ring(kShmConnectMethod, "bad shm segment",
                [](const std::string& name, Server* srv, SocketId* sid) {
                  auto conn = shm_conn_open(name);
                  return conn != nullptr
                             ? shm_socket_create(
                                   conn, &messenger_on_readable, srv, sid)
                             : -1;
                });
  register_ring(kIciConnectMethod, "bad ici segment",
                [](const std::string& name, Server* srv, SocketId* sid) {
                  auto conn = ici_conn_open(name);
                  return conn != nullptr
                             ? ici_socket_create(
                                   conn, &messenger_on_readable, srv, sid)
                             : -1;
                });
}

int Server::Start(int port) {
  if (worker_tag_ != 0 &&
      (worker_tag_ < 0 || worker_tag_ >= kMaxFiberTags)) {
    return -1;
  }
  start_runtime_init();
  int fd;
  if (!unix_path_.empty()) {
    EndPoint uep;
    uep.unix_path = unix_path_;
    sockaddr_un su = endpoint2sockaddr_un(uep);
    // Only a STALE socket file (crashed owner: connect refuses) may be
    // unlinked — silently stealing a live server's path would leave it
    // running yet unreachable.
    const int probe = ::socket(AF_UNIX, SOCK_STREAM, 0);
    if (probe >= 0) {
      if (::connect(probe, reinterpret_cast<sockaddr*>(&su),
                    sizeof(su)) == 0) {
        close(probe);
        errno = EADDRINUSE;
        return -1;  // a live server answers on this path
      }
      close(probe);
    }
    ::unlink(unix_path_.c_str());
    fd = ::socket(AF_UNIX, SOCK_STREAM | SOCK_NONBLOCK, 0);
    if (fd < 0) {
      return -1;
    }
    if (bind(fd, reinterpret_cast<sockaddr*>(&su), sizeof(su)) != 0 ||
        listen(fd, 1024) != 0) {
      close(fd);
      return -1;
    }
    port_ = 0;  // no port on AF_UNIX
  } else {
    fd = ::socket(AF_INET, SOCK_STREAM | SOCK_NONBLOCK, 0);
    if (fd < 0) {
      return -1;
    }
    int one = 1;
    setsockopt(fd, SOL_SOCKET, SO_REUSEADDR, &one, sizeof(one));
    if (reuseport_shards_ > 1) {
      // Every shard (this first socket included) must opt in BEFORE bind
      // for the kernel to co-bind them on one port.
      setsockopt(fd, SOL_SOCKET, SO_REUSEPORT, &one, sizeof(one));
    }
    sockaddr_in sa = {};
    sa.sin_family = AF_INET;
    sa.sin_addr.s_addr = htonl(INADDR_LOOPBACK);
    sa.sin_port = htons(port > 0 ? static_cast<uint16_t>(port) : 0);
    if (bind(fd, reinterpret_cast<sockaddr*>(&sa), sizeof(sa)) != 0 ||
        listen(fd, 4096) != 0) {
      close(fd);
      return -1;
    }
    socklen_t len = sizeof(sa);
    getsockname(fd, reinterpret_cast<sockaddr*>(&sa), &len);
    port_ = ntohs(sa.sin_port);
  }

  if (install_listener(fd, 0) != 0) {
    close(fd);
    return -1;
  }
  if (unix_path_.empty() && reuseport_shards_ > 1) {
    // Acceptor sharding (the 100k-connection front door): sibling
    // SO_REUSEPORT listeners on the discovered port.  Distinct fds land
    // on distinct event-dispatcher epoll threads (dispatcher.h for_fd),
    // so accept storms parallelize instead of serializing behind one
    // listener's read fiber.
    const auto fail_listeners = [this] {
      // running_ is still false here, so Stop() would no-op: tear the
      // partially-installed listeners down directly.
      Socket* s0 = Socket::Address(listen_id_);
      if (s0 != nullptr) {
        s0->SetFailed(ESHUTDOWN);
        s0->Dereference();
      }
      for (SocketId id : extra_listen_ids_) {
        Socket* s = Socket::Address(id);
        if (s != nullptr) {
          s->SetFailed(ESHUTDOWN);
          s->Dereference();
        }
      }
      extra_listen_ids_.clear();
    };
    for (int shard = 1; shard < reuseport_shards_; ++shard) {
      const int sfd = ::socket(AF_INET, SOCK_STREAM | SOCK_NONBLOCK, 0);
      if (sfd < 0) {
        fail_listeners();
        return -1;
      }
      int one = 1;
      setsockopt(sfd, SOL_SOCKET, SO_REUSEADDR, &one, sizeof(one));
      setsockopt(sfd, SOL_SOCKET, SO_REUSEPORT, &one, sizeof(one));
      sockaddr_in sa = {};
      sa.sin_family = AF_INET;
      sa.sin_addr.s_addr = htonl(INADDR_LOOPBACK);
      sa.sin_port = htons(static_cast<uint16_t>(port_));
      if (bind(sfd, reinterpret_cast<sockaddr*>(&sa), sizeof(sa)) != 0 ||
          listen(sfd, 4096) != 0 || install_listener(sfd, shard) != 0) {
        close(sfd);
        fail_listeners();
        return -1;
      }
    }
  }
  running_.store(true, std::memory_order_release);
  LOG(Info) << "server started on "
            << (unix_path_.empty()
                    ? "127.0.0.1:" + std::to_string(port_)
                    : "unix:" + unix_path_);
  return 0;
}

int Server::StartUnix(const std::string& path) {
  if (path.empty() || path.size() >= sizeof(sockaddr_un{}.sun_path)) {
    return -1;  // over-long paths would silently truncate at bind
  }
  if (reuseport_shards_ > 1) {
    // SO_REUSEPORT sharding is a TCP feature; silently ignoring it here
    // would leave the operator reading n-1 forever-zero accept counters
    // as a broken kernel spread instead of an unsupported config.
    LOG(Warning) << "reuseport shards unsupported on AF_UNIX";
    return -1;
  }
  unix_path_ = path;
  const int rc = Start(0);
  if (rc != 0) {
    unix_path_.clear();
  }
  return rc;
}

void Server::fail_listeners() {
  Socket* s = Socket::Address(listen_id_);
  if (s != nullptr) {
    s->SetFailed(ESHUTDOWN);
    s->Dereference();
  }
  for (SocketId id : extra_listen_ids_) {
    Socket* shard = Socket::Address(id);
    if (shard != nullptr) {
      shard->SetFailed(ESHUTDOWN);
      shard->Dereference();
    }
  }
}

void Server::Stop() {
  if (!running_.exchange(false)) {
    return;
  }
  fail_listeners();
  if (!unix_path_.empty()) {
    ::unlink(unix_path_.c_str());
  }
  // Fail live connections so no NEW request can reach this server while it
  // is being torn down (their user_data points at us).
  std::lock_guard<std::mutex> g(conns_mu_);
  for (SocketId id : conns_) {
    Socket* conn = Socket::Address(id);
    if (conn != nullptr) {
      conn->SetFailed(ESHUTDOWN);
      conn->Dereference();
      drain_ids_.push_back(id);  // ~Server waits for their refs to drain
    }
  }
  conns_.clear();
}

namespace {

Flag* drain_deadline_flag() {
  static Flag* f = [] {
    Flag* flag = Flag::define_int64(
        "trpc_drain_deadline_ms", 5000,
        "default Server::Drain quiesce budget (ms, [100, 600000]): how "
        "long a draining node waits for in-flight requests and RMA "
        "window spans before giving up (ETIMEDOUT) and proceeding with "
        "shutdown anyway");
    if (flag != nullptr) {
      flag->set_validator([](const std::string& v) {
        char* end = nullptr;
        const long long n = strtoll(v.c_str(), &end, 10);
        return end != v.c_str() && *end == '\0' && n >= 100 &&
               n <= 600000;
      });
    }
    return flag;
  }();
  return f;
}

}  // namespace

void Server::drain_ensure_registered() { drain_deadline_flag(); }

bool Server::EnableTuner(bool on) {
  tuner::ensure_registered();
  return Flag::set("trpc_tuner", on ? "true" : "false") == 0;
}

void Server::add_drain_hook(std::function<void()> hook) {
  std::lock_guard<std::mutex> g(drain_mu_);
  drain_hooks_.push_back(std::move(hook));
}

void Server::own_component(std::shared_ptr<void> c) {
  std::lock_guard<std::mutex> g(drain_mu_);
  components_.push_back(std::move(c));
}

int Server::Drain(int64_t deadline_ms, const std::string& handoff_path) {
  if (!running()) {
    return -1;
  }
  if (deadline_ms <= 0) {
    Flag* f = drain_deadline_flag();
    deadline_ms = f != nullptr ? f->int64_value() : 5000;
  }
  const int64_t deadline_us = monotonic_time_us() + deadline_ms * 1000;
  draining_.store(true, std::memory_order_release);
  // 1. Leave the fleet: naming withdrawal, KV-block tombstoning, watcher
  // wakeups.  Hooks run OUTSIDE drain_mu_ (a hook may add components).
  std::vector<std::function<void()>> hooks;
  {
    std::lock_guard<std::mutex> g(drain_mu_);
    hooks = drain_hooks_;
  }
  for (const auto& hook : hooks) {
    hook();
  }
  // 2. Hand the SO_REUSEPORT listener set to the successor BEFORE
  // closing our own fds: the shared accept queues stay owned throughout,
  // so no SYN is refused across the restart.  A handoff failure (no
  // successor showed up inside the deadline) degrades to a plain drain.
  if (!handoff_path.empty()) {
    if (serve_handoff(handoff_path, deadline_us) != 0) {
      LOG(Warning) << "drain: listener handoff on " << handoff_path
                   << " failed; draining without a successor";
    }
  }
  fail_listeners();
  // 3. Quiesce: every in-flight request completed AND every peer-held
  // RMA window span freed (a span outlives its request until the
  // payload's last IOBuf reference drops).
  while (in_flight.load(std::memory_order_acquire) > 0 ||
         rma_spans_in_use() > 0) {
    if (monotonic_time_us() >= deadline_us) {
      return ETIMEDOUT;
    }
    if (in_fiber()) {
      fiber_sleep_us(1000);
    } else {
      usleep(1000);
    }
  }
  return 0;
}

int Server::serve_handoff(const std::string& path, int64_t deadline_us) {
  if (path.empty() || path.size() >= sizeof(sockaddr_un{}.sun_path)) {
    return -1;
  }
  // Dup every listener fd: the dup shares the open file description (and
  // its accept queue), so the successor's copies keep working after we
  // fail our Socket objects (which close the originals).
  std::vector<int> fds;
  const auto grab = [&fds](SocketId id) {
    Socket* s = Socket::Address(id);
    if (s != nullptr) {
      const int d = ::dup(s->fd());
      if (d >= 0) {
        fds.push_back(d);
      }
      s->Dereference();
    }
  };
  grab(listen_id_);
  for (SocketId id : extra_listen_ids_) {
    grab(id);
  }
  const auto fail = [&fds](int lfd, const std::string& p) {
    for (int fd : fds) {
      close(fd);
    }
    if (lfd >= 0) {
      close(lfd);
      ::unlink(p.c_str());
    }
    return -1;
  };
  if (fds.empty()) {
    return fail(-1, path);
  }
  sockaddr_un su = {};
  su.sun_family = AF_UNIX;
  memcpy(su.sun_path, path.c_str(), path.size() + 1);
  ::unlink(path.c_str());
  const int lfd = ::socket(AF_UNIX, SOCK_STREAM | SOCK_NONBLOCK, 0);
  if (lfd < 0 ||
      bind(lfd, reinterpret_cast<sockaddr*>(&su), sizeof(su)) != 0 ||
      listen(lfd, 1) != 0) {
    return fail(lfd, path);
  }
  int cfd = -1;
  while (monotonic_time_us() < deadline_us) {
    cfd = ::accept(lfd, nullptr, nullptr);
    if (cfd >= 0) {
      break;
    }
    usleep(10000);
  }
  if (cfd < 0) {
    return fail(lfd, path);
  }
  // {port, nfds} + every fd in ONE SCM_RIGHTS control block.
  int32_t head[2] = {static_cast<int32_t>(port_),
                     static_cast<int32_t>(fds.size())};
  iovec iov = {head, sizeof(head)};
  char cbuf[CMSG_SPACE(sizeof(int) * kMaxAcceptShards)] = {};
  msghdr msg = {};
  msg.msg_iov = &iov;
  msg.msg_iovlen = 1;
  msg.msg_control = cbuf;
  msg.msg_controllen = CMSG_SPACE(sizeof(int) * fds.size());
  cmsghdr* cm = CMSG_FIRSTHDR(&msg);
  cm->cmsg_level = SOL_SOCKET;
  cm->cmsg_type = SCM_RIGHTS;
  cm->cmsg_len = CMSG_LEN(sizeof(int) * fds.size());
  memcpy(CMSG_DATA(cm), fds.data(), sizeof(int) * fds.size());
  const ssize_t sent = ::sendmsg(cfd, &msg, MSG_NOSIGNAL);
  close(cfd);
  const int rc = sent == static_cast<ssize_t>(sizeof(head)) ? 0 : -1;
  fail(lfd, path);  // close OUR dups + the handoff listener either way
  return rc;
}

int Server::StartFromHandoff(const std::string& path, int64_t timeout_ms) {
  if (running() || path.empty() ||
      path.size() >= sizeof(sockaddr_un{}.sun_path)) {
    return -1;
  }
  if (worker_tag_ != 0 &&
      (worker_tag_ < 0 || worker_tag_ >= kMaxFiberTags)) {
    return -1;
  }
  const int64_t deadline_us = monotonic_time_us() + timeout_ms * 1000;
  sockaddr_un su = {};
  su.sun_family = AF_UNIX;
  memcpy(su.sun_path, path.c_str(), path.size() + 1);
  int cfd = -1;
  // Retry until the predecessor starts serving the handoff: the two
  // processes race by design (the successor is launched first so the
  // drain window stays minimal).
  while (monotonic_time_us() < deadline_us) {
    cfd = ::socket(AF_UNIX, SOCK_STREAM, 0);
    if (cfd < 0) {
      return -1;
    }
    if (::connect(cfd, reinterpret_cast<sockaddr*>(&su), sizeof(su)) == 0) {
      break;
    }
    close(cfd);
    cfd = -1;
    usleep(20000);
  }
  if (cfd < 0) {
    return -1;
  }
  int32_t head[2] = {0, 0};
  iovec iov = {head, sizeof(head)};
  char cbuf[CMSG_SPACE(sizeof(int) * kMaxAcceptShards)] = {};
  msghdr msg = {};
  msg.msg_iov = &iov;
  msg.msg_iovlen = 1;
  msg.msg_control = cbuf;
  msg.msg_controllen = sizeof(cbuf);
  const ssize_t got = ::recvmsg(cfd, &msg, MSG_CMSG_CLOEXEC);
  close(cfd);
  std::vector<int> fds;
  for (cmsghdr* cm = CMSG_FIRSTHDR(&msg); cm != nullptr;
       cm = CMSG_NXTHDR(&msg, cm)) {
    if (cm->cmsg_level == SOL_SOCKET && cm->cmsg_type == SCM_RIGHTS) {
      const size_t n = (cm->cmsg_len - CMSG_LEN(0)) / sizeof(int);
      const int* data = reinterpret_cast<const int*>(CMSG_DATA(cm));
      fds.assign(data, data + n);
    }
  }
  const auto close_all = [&fds] {
    for (int fd : fds) {
      close(fd);
    }
    return -1;
  };
  if (got != static_cast<ssize_t>(sizeof(head)) || fds.empty() ||
      static_cast<size_t>(head[1]) != fds.size() ||
      fds.size() > static_cast<size_t>(kMaxAcceptShards)) {
    return close_all();
  }
  start_runtime_init();
  port_ = head[0];
  reuseport_shards_ = static_cast<int>(fds.size());
  for (size_t i = 0; i < fds.size(); ++i) {
    if (install_listener(fds[i], static_cast<int>(i)) != 0) {
      for (size_t j = i; j < fds.size(); ++j) {
        close(fds[j]);
      }
      fail_listeners();
      return -1;
    }
  }
  running_.store(true, std::memory_order_release);
  LOG(Info) << "server adopted " << fds.size()
            << " handed-off listener(s) on 127.0.0.1:" << port_;
  return 0;
}

int Server::Join(int64_t timeout_ms) {
  const int64_t deadline =
      timeout_ms >= 0 ? monotonic_time_us() + timeout_ms * 1000 : INT64_MAX;
  while (in_flight.load(std::memory_order_acquire) > 0) {
    if (monotonic_time_us() >= deadline) {
      return ETIMEDOUT;
    }
    if (in_fiber()) {
      fiber_sleep_us(1000);
    } else {
      usleep(1000);
    }
  }
  return 0;
}

namespace {
std::atomic<bool> g_asked_to_quit{false};
void quit_signal_handler(int) {
  g_asked_to_quit.store(true, std::memory_order_release);
}
}  // namespace

void Server::RunUntilAskedToQuit() {
  struct sigaction sa = {};
  sa.sa_handler = &quit_signal_handler;
  sigaction(SIGINT, &sa, nullptr);
  sigaction(SIGTERM, &sa, nullptr);
  while (!g_asked_to_quit.load(std::memory_order_acquire)) {
    usleep(100 * 1000);
  }
}

void Server::track_connection(SocketId id) {
  std::lock_guard<std::mutex> g(conns_mu_);
  if (conns_.size() >= conns_prune_at_) {
    // Prune stale versioned ids.  The threshold then moves to 2x the
    // LIVE count: a fixed threshold would re-walk the whole vector on
    // every accept once past it — O(n^2) across a 100k-connection ramp
    // (the scale harness found exactly that); doubling amortizes the
    // walk to O(1) per accept at any connection count.
    std::vector<SocketId> live;
    live.reserve(conns_.size());
    for (SocketId sid : conns_) {
      Socket* s = Socket::Address(sid);
      if (s != nullptr) {
        live.push_back(sid);
        s->Dereference();
      }
    }
    conns_.swap(live);
    conns_prune_at_ = std::max<size_t>(4096, conns_.size() * 2);
  }
  conns_.push_back(id);
}

// Accept-until-EAGAIN (acceptor.cpp:251 parity); runs in the listen
// socket's read fiber.
void Server::on_acceptable(SocketId id, void* ctx) {
  auto* actx = static_cast<AcceptCtx*>(ctx);
  Server* srv = actx->srv;
  Socket* listener = Socket::Address(id);
  if (listener == nullptr) {
    return;
  }
  while (true) {
    sockaddr_storage peer_sa = {};
    socklen_t peer_len = sizeof(peer_sa);
    const int fd =
        accept4(listener->fd(), reinterpret_cast<sockaddr*>(&peer_sa),
                &peer_len, SOCK_NONBLOCK | SOCK_CLOEXEC);
    if (fd < 0) {
      break;  // EAGAIN or error; ET will refire on next connection
    }
    srv->accept_counts_[actx->shard].fetch_add(1,
                                               std::memory_order_relaxed);
    EndPoint peer_ep;
    if (peer_sa.ss_family == AF_UNIX) {
      // Unix peers are anonymous; identify them by our listening path.
      peer_ep.unix_path = srv->unix_path_;
    } else {
      const auto* sin = reinterpret_cast<const sockaddr_in*>(&peer_sa);
      peer_ep.ip = sin->sin_addr.s_addr;
      peer_ep.port = ntohs(sin->sin_port);
    }
    // Fault point: reject-at-accept (net/fault.h svr_reject) — the peer
    // sees an immediate close, exercising its connect-retry path.
    if (srv->faults_.active() &&
        srv->faults_.decide(FaultPoint::kAccept, peer_ep).kind ==
            FaultKind::kSvrReject) {
      close(fd);
      continue;
    }
    Socket::Options opts;
    opts.fd = fd;
    opts.remote = peer_ep;
    if (peer_sa.ss_family != AF_UNIX) {
      int one = 1;
      setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
    }
    opts.on_readable = &messenger_on_readable;
    opts.user_data = srv;
    opts.worker_tag = static_cast<uint8_t>(srv->worker_tag_);
    if (srv->tls_ctx_ != nullptr) {
      // First-byte sniff decides TLS vs plaintext per connection.
      opts.transport = tls_transport();
      opts.transport_ctx_holder = tls_conn_server(srv->tls_ctx_);
    }
    SocketId conn_id = 0;
    if (Socket::Create(opts, &conn_id) != 0) {
      close(fd);
      continue;
    }
    srv->track_connection(conn_id);
  }
  listener->Dereference();
}

int Server::EnableTls(const std::string& cert_file,
                      const std::string& key_file,
                      const std::string& ca_file) {
  std::string err;
  tls_ctx_ = tls_server_ctx(cert_file, key_file, &err, ca_file);
  if (tls_ctx_ == nullptr) {
    LOG(Warning) << "EnableTls failed: " << err;
    return -1;
  }
  return 0;
}

int Server::EnableDump(const std::string& path, double sample_rate) {
  auto writer = std::make_unique<RecordWriter>(path);
  if (!writer->valid()) {
    return -1;
  }
  LockGuard<FiberMutex> g(dump_mu_);
  dump_writer_ = std::move(writer);
  dump_rate_.store(sample_rate, std::memory_order_release);
  return 0;
}

void Server::maybe_dump(const std::string& method, uint32_t attachment_size,
                        const IOBuf& payload) {
  const double rate = dump_rate_.load(std::memory_order_acquire);
  if (rate <= 0.0 ||
      fast_rand_less_than(1000000) >= static_cast<uint64_t>(rate * 1000000)) {
    return;
  }
  // Each record is a complete tstd request frame — replay just re-sends it.
  RpcMeta meta;
  meta.type = RpcMeta::kRequest;
  meta.method = method;
  meta.attachment_size = attachment_size;
  IOBuf frame;
  tstd_pack(&frame, meta, payload);
  LockGuard<FiberMutex> g(dump_mu_);
  if (dump_writer_ != nullptr) {
    dump_writer_->write(frame);
    dump_writer_->flush();
  }
}

// ---- request execution (tstd protocol hook) -----------------------------

void tstd_process_request(InputMessage&& msg) {
  SocketRef sock(Socket::Address(msg.socket));
  if (!sock) {
    return;
  }
  Server* srv = static_cast<Server*>(sock->user_data);
  // Connection authentication (auth.h; input_messenger.cpp:271-289
  // parity).  The credential frame verifies once and marks the socket;
  // with an authenticator installed, requests on an unverified socket
  // are refused and the connection failed.
  if (msg.meta.type == RpcMeta::kAuth) {
    const Authenticator* auth =
        srv != nullptr ? srv->authenticator() : nullptr;
    if (auth != nullptr &&
        auth->verify_credential(msg.payload.to_string(), sock->remote()) ==
            0) {
      sock->auth_ok.store(true, std::memory_order_release);
    } else if (auth != nullptr) {
      LOG(Warning) << "auth credential rejected; closing connection";
      sock->SetFailed(EACCES);
    }
    return;  // credential frames carry no request
  }
  if (msg.meta.type == RpcMeta::kCancel) {
    // Cascading-cancel control frame (net/deadline.h): fans out to the
    // named in-flight request's downstream calls and transfers.  Never
    // answered (the caller already abandoned the call); dropped on an
    // unauthenticated connection — an unverified peer must not cancel
    // other clients' work.
    if (srv == nullptr || srv->authenticator() == nullptr ||
        sock->auth_ok.load(std::memory_order_acquire)) {
      if (cancel_fire(msg.socket, msg.meta.correlation_id) &&
          timeline::enabled()) {
        timeline::record(timeline::kDeadline, msg.meta.correlation_id,
                         timeline::kDeadlineCancelFanout << 56);
      }
    }
    return;
  }
  if (srv != nullptr && srv->authenticator() != nullptr &&
      !sock->auth_ok.load(std::memory_order_acquire)) {
    RpcMeta meta;
    meta.type = RpcMeta::kResponse;
    meta.correlation_id = msg.meta.correlation_id;
    meta.error_code = EACCES;
    meta.error_text = "connection not authenticated";
    IOBuf frame;
    tstd_pack(&frame, meta, IOBuf());
    // Flush-then-close: an explicit SetFailed would bump the socket
    // version before the KeepWrite fiber re-Addresses it, dropping the
    // EACCES reply and leaving the client with a bare reset.
    sock->Write(std::move(frame), /*close_after=*/true);
    return;
  }
  const SocketId socket_id = msg.socket;
  const uint64_t cid = msg.meta.correlation_id;
  const std::string method = msg.meta.method;

  auto* cntl = new Controller();
  cntl->set_method(method);
  // Surface the request's QoS tag to the handler (and the capi).
  cntl->set_qos(msg.meta.qos_tenant, msg.meta.qos_priority);
  cntl->call().socket_id = socket_id;
  cntl->call().peer_stream = msg.meta.stream_id;
  cntl->call().peer_stream_window = msg.meta.ack_bytes;
  cntl->call().extra_peer = std::move(msg.meta.extra_streams);
  if (msg.ctx != nullptr && msg.meta.stripe_id != 0) {
    // Reassembled striped request: remember the rails it arrived over so
    // the response stripes back across the same connections.
    cntl->call().stripe_rails =
        static_cast<StripeArrival*>(msg.ctx.get())->rails;
  }
  // One-sided response target (net/rma.h): the caller advertised a
  // registered landing region — the response puts straight into it.
  cntl->call().rma_resp_rkey = msg.meta.rma_resp_rkey;
  cntl->call().rma_resp_max = msg.meta.rma_resp_max;
  cntl->call().rma_resp_off = msg.meta.rma_resp_off;
  cntl->call().sl_pool =
      srv != nullptr ? srv->session_data_pool() : nullptr;
  auto* response = new IOBuf();
  // The first two of a request's four stamps (net/wire_split.h; the
  // other two are read in done()).  start_us has ONE meaning: this
  // function's entry, i.e. the dispatch fiber picked the request up
  // (behind the QoS lane and the fiber spawn, in front of admission and
  // any injected delay).  It anchors the method's LatencyRecorder and
  // everything fed the same number (limiter, tenant governor, SLO):
  // dispatch start to response handed off, as it always was.
  // arrival_us is the parser's (InputMessage::arrival_us): the request
  // whole; a message no parser stamped arrived when it was picked up.
  const int64_t start_us = monotonic_time_us();
  const int64_t arrival_us = msg.arrival_us != 0 ? msg.arrival_us : start_us;
  // Deadline plane (net/deadline.h): anchor the wire's relative budget
  // to the request's arrival clock, so QoS-lane queueing and dispatch
  // backlog count against it.  A budget that already expired is shed
  // below, BEFORE it can consume an admission slot or a handler.
  int64_t deadline_abs = 0;
  if (msg.meta.deadline_us != 0 && deadline_wire_enabled()) {
    // Gated on the SAME flag that controls stamping: trpc_deadline_wire
    // off is the operator kill-switch for the whole plane on this node
    // — incoming stamps from flag-on peers are then ignored too, as the
    // flag's help text promises.
    // The wire value is untrusted (the frame CRC covers only the
    // payload): clamp to a sane ceiling before anchoring, or a hostile
    // u64 near INT64_MAX signed-overflows the add (UB) and wraps a
    // live request into an instant shed.
    constexpr uint64_t kMaxBudgetUs = 24ull * 3600 * 1000 * 1000;  // 24h
    const uint64_t budget = msg.meta.deadline_us < kMaxBudgetUs
                                ? msg.meta.deadline_us
                                : kMaxBudgetUs;
    deadline_abs = arrival_us + static_cast<int64_t>(budget);
    cntl->set_deadline_abs_us(deadline_abs);
  }
  const bool deadline_dead = deadline_abs != 0 && start_us >= deadline_abs;
  // rpcz: server span, linked to the client span via the meta's trace
  // context (baidu_rpc_protocol.cpp:648-661 parity).  Ambient context
  // makes client calls issued from inside the handler children of this
  // span.
  Span* span = nullptr;
  if (rpcz_enabled()) {
    span = start_span(/*server_side=*/true, method, msg.meta.trace_id,
                      msg.meta.span_id);
    span->request_bytes = msg.payload.size();
    set_ambient_span(span);
  }
  // The ambient context must be cleared by THIS fiber on every exit path
  // (the read fiber processes the last message of a batch inline and then
  // keeps serving the connection — stale ambient would leak into later
  // requests).  The done closure may run on a different fiber entirely,
  // so it is the wrong place to clear.
  struct AmbientGuard {
    bool active;
    ~AmbientGuard() {
      if (active) {
        set_ambient_span(nullptr);
      }
    }
  } ambient_guard{span != nullptr};
  const Server::MethodProperty* prop =
      (srv != nullptr && srv->running()) ? srv->find_method(method) : nullptr;
  std::shared_ptr<LatencyRecorder> lat =
      prop != nullptr ? prop->latency : nullptr;
  std::shared_ptr<Server::MethodPhases> phases =
      prop != nullptr ? prop->phases : nullptr;
  std::shared_ptr<ConcurrencyLimiter> limiter =
      prop != nullptr ? prop->limiter : nullptr;
  // Per-tenant QoS admission (net/qos.h): runs FIRST so a shed request
  // never consumes a per-method slot.  A shed answers kEOverloaded —
  // distinct from kELimit so the cluster client fails over immediately.
  std::shared_ptr<TenantGovernor> gov =
      srv != nullptr ? srv->qos_governor() : nullptr;
  // SLO scoring (stat/slo.h): flag-off this is ONE relaxed load and the
  // engine is never even ref-counted into the closure.
  std::shared_ptr<SloEngine> slo =
      (srv != nullptr && slo::enabled()) ? srv->slo_engine() : nullptr;
  TenantGovernor::Entry* tenant_entry = nullptr;
  bool tenant_admitted = true;
  if (gov != nullptr && !deadline_dead) {
    tenant_entry = gov->admit(msg.meta.qos_tenant, &tenant_admitted);
    if (!tenant_admitted) {
      tenant_entry = nullptr;  // no on_response for shed calls
    }
  }
  // Admission gate (MethodStatus parity): rejected calls never reach the
  // handler and answer immediately with kELimit.  An already-expired
  // request skips admission entirely — it is shed below without ever
  // billing a tenant or a concurrency slot.
  const bool admitted =
      deadline_dead ||
      (tenant_admitted && (limiter == nullptr || limiter->on_request()));
  if (!admitted || deadline_dead) {
    limiter = nullptr;  // no on_response for rejected/shed calls
  }

  if (srv != nullptr) {
    srv->in_flight.fetch_add(1, std::memory_order_acq_rel);
  }
  // Traffic capture (stat/capture.h): freeze the pre-dispatch facts now
  // — msg.payload is consumed below.  done() submits the record so it
  // also carries status, response bytes and handler latency; shed paths
  // run done() too, so the recorded error mix covers kEOverloaded /
  // kEDeadlineExpired sheds, not just handler outcomes.
  const bool cap_on = capture::enabled();
  const uint64_t cap_req_bytes = msg.payload.size();
  const uint32_t cap_budget = static_cast<uint32_t>(
      std::min<uint64_t>(msg.meta.deadline_us, 0xffffffffull));
  const uint64_t cap_trace = msg.meta.trace_id;
  const uint64_t cap_pspan = msg.meta.span_id;
  Closure done = [socket_id, cid, cntl, response, start_us, arrival_us,
                  srv, lat, phases, limiter, gov, slo, tenant_entry, span,
                  cap_on, cap_req_bytes, cap_budget, cap_trace, cap_pspan] {
    // Third stamp.  A call answered before any handler (shed, rejected,
    // failed on the way) entered none: its handler time is 0 and all of
    // arrival -> here is queue.
    const int64_t done_us = monotonic_time_us();
    const int64_t handler_us = cntl->call().srv.handler_us != 0
                                   ? cntl->call().srv.handler_us
                                   : done_us;
    RpcMeta meta;
    meta.type = RpcMeta::kResponse;
    meta.correlation_id = cid;
    meta.srv = {arrival_us, handler_us, done_us};
    meta.error_code = cntl->error_code();
    meta.error_text = cntl->error_text();
    meta.stream_id = cntl->call().accepted_stream;  // acceptance piggyback
    if (meta.stream_id != 0) {
      meta.ack_bytes = stream_recv_window(meta.stream_id);
      for (uint64_t sid : cntl->call().extra_accepted) {
        meta.extra_streams.emplace_back(sid, stream_recv_window(sid));
      }
    }
    if (!cntl->Failed() && cntl->response_compress_type() != 0) {
      const Compressor* c = find_compressor(
          static_cast<CompressType>(cntl->response_compress_type()));
      IOBuf squeezed;
      if (c != nullptr && c->compress(*response, &squeezed)) {
        *response = std::move(squeezed);
        meta.compress_type = cntl->response_compress_type();
      }
    }
    if (!cntl->response_attachment().empty()) {
      meta.attachment_size =
          static_cast<uint32_t>(cntl->response_attachment().size());
      response->append(std::move(cntl->response_attachment()));
    }
    if (cntl->checksum_enabled()) {
      meta.has_checksum = true;  // striped sends CRC per chunk
    }
    const size_t response_bytes = response->size();
    // One-sided first (net/rma.h): over shm/ici rings the response body
    // is WRITTEN into the caller's advertised region (or this
    // connection's window) and only a control frame rides back; 1 =
    // not applicable / window full — the stripe/frame path carries it.
    // Long response transfers poll the request's cancel scope and
    // remaining budget between chunks (net/deadline.h): a caller that
    // cancelled, died, or ran out of budget stops the put within one
    // chunk instead of shipping bytes nobody will read.
    const DeadlineToken resp_tok{cntl->call().cancel_scope.get(),
                                 cntl->deadline_abs_us()};
    const int rma_rc =
        rma_try_send(socket_id, &meta, response,
                     cntl->call().rma_resp_rkey,
                     cntl->call().rma_resp_max,
                     cntl->call().rma_resp_off, resp_tok);
    if (rma_rc != 1) {
      // Sent (0) or hard-failed (-1, socket dead: the client times out
      // exactly as a failed stripe_send would have left it).
    } else if (stripe_should(socket_id, meta.stream_id, response_bytes)) {
      // Large response: stripe it back over the rails the request
      // arrived on (or just this connection).  stripe_id is the cid —
      // unique in the client process, and the key its registered
      // landing buffer (batch plane) waits under.
      std::vector<SocketId> rails = cntl->call().stripe_rails;
      if (rails.empty()) {
        rails.push_back(socket_id);
      }
      stripe_send(socket_id, rails, std::move(meta),
                  std::move(*response), cid, resp_tok);
    } else {
      stripe_frame_send(socket_id, std::move(meta),
                        std::move(*response));
    }
    // Fourth stamp: the send call returned (the one-sided put is
    // written, the stripes or the frame are the connection's).
    const int64_t sent_us = monotonic_time_us();
    const int64_t latency_us = sent_us - start_us;
    if (phases != nullptr) {
      phases->calls << 1;
      phases->queue_us << handler_us - arrival_us;
      phases->handler_us << done_us - handler_us;
      phases->send_us << sent_us - done_us;
    }
    if (limiter != nullptr) {
      limiter->on_response(latency_us, cntl->Failed());
    }
    if (gov != nullptr && tenant_entry != nullptr) {
      // Frees the tenant's slot and feeds its qos_tenant_<name> series.
      gov->on_response(tenant_entry, latency_us, cntl->Failed());
    }
    if (lat != nullptr) {
      *lat << latency_us;
    }
    if (slo != nullptr) {
      // Sheds run done() too, so kEOverloaded/kEDeadlineExpired count
      // against the tenant's error budget — an overloaded tenant can't
      // look healthy by shedding its way under its latency target.
      slo->on_response(cntl->qos_tenant(), latency_us, cntl->Failed());
    }
    if (cap_on && capture::enabled()) {
      capture::Sample cs;
      cs.arrival_mono_us = arrival_us;
      cs.trace_id = cap_trace;
      cs.parent_span_id = cap_pspan;
      cs.request_bytes = cap_req_bytes;
      cs.response_bytes = response_bytes;
      cs.status = cntl->error_code();
      cs.queue_us = static_cast<uint32_t>(
          std::max<int64_t>(0, handler_us - arrival_us));
      cs.handler_us = static_cast<uint32_t>(
          std::max<int64_t>(0, sent_us - handler_us));
      cs.deadline_budget_us = cap_budget;
      cs.priority = cntl->qos_priority();
      cs.method = cntl->method();
      cs.tenant = cntl->qos_tenant();
      capture::record(std::move(cs));
    }
    if (span != nullptr) {
      span->response_bytes = response_bytes;
      submit_span(span, cntl->error_code());
    }
    if (cntl->call().sl_data != nullptr) {
      cntl->call().sl_pool->Return(cntl->call().sl_data);
    }
    if (cntl->call().cancel_scope != nullptr) {
      // Unregistered only AFTER the response send: a kCancel racing the
      // response must still find the scope to abort an in-flight
      // one-sided put.
      cancel_unregister(socket_id, cid);
    }
    delete response;
    delete cntl;
    if (srv != nullptr) {
      srv->requests_served.fetch_add(1, std::memory_order_relaxed);
      // LAST touch of srv: once in_flight hits 0, Join may free the server.
      srv->in_flight.fetch_sub(1, std::memory_order_acq_rel);
    }
  };

  if (srv == nullptr || !srv->running()) {
    cntl->SetFailed(ESHUTDOWN, "server stopped");
    done();
    return;
  }
  if (srv->draining()) {
    // Graceful leave (Server::Drain): the node is healthy but exiting —
    // answer a WELL-FORMED status the cluster client fails over around
    // WITHOUT quarantining us (kEDraining, concurrency_limiter.h), so
    // the successor that revives on this endpoint moments later isn't
    // serving into a poisoned breaker.
    cntl->SetFailed(kEDraining, "server draining: fail over");
    done();
    return;
  }
  if (deadline_dead) {
    // The caller's end-to-end budget expired before we could dispatch
    // (in flight, or queued in a QoS lane — arrival was stamped at
    // parse).  Shed with the distinct non-retriable status: executing
    // (or retrying) a dead budget is pure wasted work.
    deadline_vars().shed_total << 1;
    if (timeline::enabled()) {
      timeline::record(timeline::kDeadline, cid,
                       (timeline::kDeadlineShedPreDispatch << 56) |
                           static_cast<uint64_t>(msg.meta.deadline_us &
                                                 0xffffffffffffffull));
    }
    cntl->SetFailed(kEDeadlineExpired,
                    "deadline expired before dispatch: " + method);
    done();
    return;
  }
  if (prop == nullptr && !srv->generic_handler()) {
    cntl->SetFailed(ENOENT, "no such method: " + method);
    done();
    return;
  }
  if (!admitted) {
    if (!tenant_admitted) {
      cntl->SetFailed(kEOverloaded,
                      "overloaded: tenant '" + msg.meta.qos_tenant +
                          "' shed by admission control");
    } else {
      cntl->SetFailed(kELimit, "rejected by concurrency limiter");
    }
    done();
    return;
  }
  {
    int ec = 0;
    std::string et;
    if (!srv->accept_request(method, sock->remote(), &ec, &et)) {
      cntl->SetFailed(ec, et);
      done();
      return;
    }
  }
  // Fault points: forced error / delayed dispatch (net/fault.h svr_error,
  // svr_delay).  A forced error is a CLEAN failure — the client gets a
  // well-formed response frame carrying the injected code; a delay parks
  // this request's fiber, exercising client timeout/hedging machinery.
  if (srv->faults().active()) {
    const FaultDecision fd =
        srv->faults().decide(FaultPoint::kDispatch, sock->remote());
    if (fd.kind == FaultKind::kSvrError) {
      cntl->SetFailed(fd.error_code, "injected server fault");
      done();
      return;
    }
    if (fd.kind == FaultKind::kSvrDelay) {
      fiber_sleep_us(fd.delay_ms * 1000);
    }
  }
  if (deadline_abs != 0 && monotonic_time_us() >= deadline_abs) {
    // Expired while parked in the (injected) dispatch delay — the
    // queueing class the plane exists to shed: never half-execute work
    // whose caller has already given up.
    deadline_vars().shed_total << 1;
    if (timeline::enabled()) {
      timeline::record(timeline::kDeadline, cid,
                       timeline::kDeadlineShedQueued << 56);
    }
    cntl->SetFailed(kEDeadlineExpired,
                    "deadline expired in dispatch queue: " + method);
    done();
    return;
  }
  srv->maybe_dump(method, msg.meta.attachment_size, msg.payload);
  // Split the attachment tail off the payload.
  IOBuf request = std::move(msg.payload);
  if (msg.meta.attachment_size > 0 &&
      msg.meta.attachment_size <= request.size()) {
    IOBuf body;
    request.cutn(&body, request.size() - msg.meta.attachment_size);
    cntl->request_attachment() = std::move(request);
    request = std::move(body);
  }
  if (msg.meta.compress_type != 0) {
    const Compressor* c = find_compressor(
        static_cast<CompressType>(msg.meta.compress_type));
    IOBuf plain;
    if (c == nullptr || !c->decompress(request, &plain, 1ull << 30)) {
      cntl->SetFailed(EBADMSG, "request decompression failed");
      done();
      return;
    }
    request = std::move(plain);
    // Symmetric default: reply compressed the same way unless the
    // handler overrides (reference: response follows request unless
    // set_response_compress_type).
    if (cntl->response_compress_type() == 0) {
      cntl->set_response_compress_type(msg.meta.compress_type);
    }
  }
  if (msg.meta.has_checksum) {
    cntl->set_enable_checksum(true);  // checksum the response too
  }
  // Cascading cancellation (net/deadline.h): every DISPATCHED request
  // owns a cancel scope, registered under (connection, cid) so a
  // kCancel control frame — or a poller observing the dead connection /
  // expired budget — fans out to the downstream calls and transfers the
  // handler starts.  Shed/early-error paths above never create one:
  // they own no work worth cancelling.
  auto cancel_scope = std::make_shared<CancelScope>();
  cancel_scope->socket = socket_id;
  cancel_scope->deadline_us = deadline_abs;
  if (!cancel_register(socket_id, cid, cancel_scope)) {
    // The caller's kCancel raced ahead of dispatch (request was still
    // queued when it arrived): shed as cancelled — executing work
    // nobody wants is the waste this plane exists to stop.  The scope
    // was never registered, so done() has nothing to unregister.
    deadline_vars().tombstone_shed << 1;
    if (timeline::enabled()) {
      timeline::record(timeline::kDeadline, cid,
                       timeline::kDeadlineCancelFanout << 56);
    }
    cntl->SetFailed(ECANCELED, "request cancelled before dispatch");
    done();
    return;
  }
  cntl->call().cancel_scope = cancel_scope;
  // Ambient deadline + scope for the handler extent (cleared by this
  // fiber on every exit path, like the span ambient): client calls the
  // handler issues inherit the remaining budget and register for
  // cancellation automatically.  The pthread-pool path skips it — the
  // handler runs off-fiber there and polls the Controller instead.
  struct DeadlineAmbientGuard {
    bool active = false;
    ~DeadlineAmbientGuard() {
      if (active) {
        set_ambient_deadline(0);
        set_ambient_cancel(nullptr);
      }
    }
  } deadline_ambient_guard;
  if (!srv->usercode_in_pthread()) {
    set_ambient_deadline(deadline_abs);
    set_ambient_cancel(cancel_scope.get());
    deadline_ambient_guard.active = true;
  }
  // Registered handler, else the catch-all (generic-call parity).  A
  // pointer, not a copy: both live in server-owned storage that
  // in_flight keeps alive until the last done() runs.
  const Server::Handler* handler =
      prop != nullptr ? &prop->handler : &srv->generic_handler();
  if (srv->usercode_in_pthread()) {
    // Blocking-tolerant path: the handler runs on a backup pthread so a
    // pthread-blocking body cannot pin this fiber worker.  done() is
    // thread-agnostic (Socket::Write is callable from any thread).
    UsercodePool::instance()->run(
        [handler, cntl, request = std::move(request), response,
         done = std::move(done)]() mutable {
          // Second stamp, on the backup pthread: the pool's own queue
          // is queue too.
          cntl->call().srv.handler_us = monotonic_time_us();
          (*handler)(cntl, request, response, std::move(done));
        });
    return;
  }
  // Second stamp: everything up to here (QoS lane, dispatch backlog,
  // admission, an injected svr_delay) was queue.
  cntl->call().srv.handler_us = monotonic_time_us();
  (*handler)(cntl, request, response, std::move(done));
}

}  // namespace trpc
