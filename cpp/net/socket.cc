#include "net/socket.h"

#include <arpa/inet.h>
#include <errno.h>
#include <fcntl.h>
#include <netinet/tcp.h>
#include <sys/socket.h>
#include <unistd.h>

#include "base/logging.h"
#include "base/tls_cache.h"
#include "base/time.h"
#include "base/tsan.h"
#include "fiber/fiber.h"
#include "fiber/scheduler.h"
#include "net/fault.h"
#include "net/hotpath_stats.h"
#include "net/protocol.h"
#include "net/dispatcher.h"

namespace trpc {

extern std::atomic<int64_t> g_socket_count;  // exposed via /connections

namespace {
using SocketPool = ResourcePool<Socket>;

constexpr uint64_t kRefUnit = 1;
inline uint32_t ver_of(uint64_t rv) { return static_cast<uint32_t>(rv >> 32); }
inline uint32_t ref_of(uint64_t rv) { return static_cast<uint32_t>(rv); }
inline uint64_t pack(uint32_t ver, uint32_t ref) {
  return (static_cast<uint64_t>(ver) << 32) | ref;
}
}  // namespace

int Socket::Create(const Options& opts, SocketId* out) {
  Socket* s = nullptr;
  const uint32_t slot = SocketPool::instance()->acquire(&s);
  if (s == nullptr) {
    return -1;
  }
  // Relaxed: the release store of ref_ver_ below is the single
  // publication point — nothing reads slot_/count before it lands.
  s->slot_.store(slot, std::memory_order_relaxed);
  s->reset_for_reuse(opts);
  const uint32_t ver =
      ver_of(s->ref_ver_.load(std::memory_order_relaxed)) + 1;  // → odd
  // One owner reference.
  s->ref_ver_.store(pack(ver, 1), std::memory_order_release);
  g_socket_count.fetch_add(1, std::memory_order_relaxed);
  *out = pack(ver, 0) | slot;  // ver<<32 | slot (ref bits reused as slot)
  if (s->fd_ >= 0) {
    make_nonblocking(s->fd_);
    if (EventDispatcher::for_fd(s->fd_)->add(s->fd_, *out) != 0) {
      LOG(Error) << "epoll add failed for fd " << s->fd_;
    }
  }
  return 0;
}

void Socket::reset_for_reuse(const Options& opts) {
  fd_ = opts.fd;
  mode_ = opts.mode;
  remote_ = opts.remote;
  // Every socket's transport rides behind the fault-injection decorator
  // (net/fault.h): one atomic load when inactive, schedule-driven chaos
  // when armed — runtime-togglable without touching live sockets.
  transport_ = fault_wrap(
      opts.transport != nullptr ? opts.transport : tcp_transport());
  transport_ctx_holder_ = opts.transport_ctx_holder;
  transport_ctx = transport_ctx_holder_.get();
  // Relaxed init stores through wq_head_ below: this slot is not yet
  // published (Address() can't hand out refs until Create()'s release
  // store of ref_ver_), so there is no concurrent reader to order with.
  failed_.store(false, std::memory_order_relaxed);
  // fd-less transports (shm/ICI) are born connected.
  connected_.store(opts.fd >= 0 ||
                       (opts.transport != nullptr && !opts.transport->fd_based()),
                   std::memory_order_relaxed);
  nevent_.store(0, std::memory_order_relaxed);
  peer_clock_.store(-1, std::memory_order_relaxed);    // pre-publication
  on_readable_ = opts.on_readable;
  ctx_ = opts.ctx;
  read_buf_.clear();
  pinned_protocol = -1;
  user_data = opts.user_data;
  worker_tag = opts.worker_tag;
  wr_ev_.value.store(0, std::memory_order_relaxed);   // pre-publication
  writing_.store(false, std::memory_order_relaxed);    // pre-publication
  pending_.clear();
  pending_close_ = false;
  probe_stall_len = 0;
  read_block_hint = 0;
  parse_state.reset();
  parse_state_owner = nullptr;
  auth_ok.store(false, std::memory_order_relaxed);    // pre-publication
  wq_head_.store(nullptr, std::memory_order_relaxed);  // pre-publication
}

bool Socket::peer_shares_clock() {
  int8_t known = peer_clock_.load(std::memory_order_relaxed);
  if (known >= 0) {
    return known == 1;
  }
  bool same = false;
  if (mode_ == SocketMode::kShm || remote_.is_unix() ||
      (ntohl(remote_.ip) >> 24) == 127) {  // the ring, a path, loopback
    same = true;
  } else if (fd_ >= 0 && connected()) {
    sockaddr_in local{};
    socklen_t len = sizeof(local);
    same = getsockname(fd_, reinterpret_cast<sockaddr*>(&local), &len) == 0 &&
           local.sin_family == AF_INET &&
           local.sin_addr.s_addr == remote_.ip;
  } else {
    return false;  // not connected yet: decide when it is
  }
  peer_clock_.store(same ? 1 : 0, std::memory_order_relaxed);
  return same;
}

Socket* Socket::Address(SocketId id) {
  const uint32_t slot = static_cast<uint32_t>(id);
  const uint32_t ver = static_cast<uint32_t>(id >> 32);
  if ((ver & 1) == 0) {
    return nullptr;
  }
  Socket* s = SocketPool::instance()->at(slot);
  if (s == nullptr) {
    return nullptr;
  }
  // Acquire: pairs with Create()'s release publication so a ref taken
  // here sees the fully-initialized socket state behind it.
  uint64_t rv = s->ref_ver_.load(std::memory_order_acquire);
  while (true) {
    if (ver_of(rv) != ver) {
      return nullptr;
    }
    if (s->ref_ver_.compare_exchange_weak(rv, rv + kRefUnit,
                                          std::memory_order_acq_rel)) {
      return s;
    }
  }
}

bool Socket::Draining(SocketId id) {
  Socket* s = SocketPool::instance()->at(static_cast<uint32_t>(id));
  if (s == nullptr) {
    return false;
  }
  // Acquire: must observe SetFailed's generation bump, not a stale odd
  // version that would misreport a draining socket as live.
  const uint64_t rv = s->ref_ver_.load(std::memory_order_acquire);
  // SetFailed bumped the generation to id_ver+1 (even); refs drain to 0.
  return ver_of(rv) == static_cast<uint32_t>(id >> 32) + 1 && ref_of(rv) > 0;
}

SocketId Socket::id() const {
  // Acquire on the version (diagnostic readers must not see a stale
  // generation); slot_ is immutable after Create → relaxed.
  return pack(ver_of(ref_ver_.load(std::memory_order_acquire)), 0) |
         slot_.load(std::memory_order_relaxed);
}

std::string Socket::DumpAll(size_t max_rows) {
  return dump_pool_table<Socket>(
      "live sockets (id  fd  remote  mode  proto  state)\n", max_rows,
      [](uint32_t slot, Socket* s, std::string* line) {
        // Acquire: liveness must see the latest generation/refcount.
        const uint64_t rv = s->ref_ver_.load(std::memory_order_acquire);
        if ((ver_of(rv) & 1) == 0 || ref_of(rv) == 0) {
          return false;  // even generation = recycled/failed slot
        }
        if (line == nullptr) {
          return true;  // counted, rows already capped
        }
        // Hold a real reference while reading the non-atomic fields —
        // a bare snapshot would race reset_for_reuse on a recycled
        // slot.  Address re-validates the generation; a slot recycled
        // since the check above simply drops out of the table.
        SocketRef ref(Socket::Address(pack(ver_of(rv), 0) | slot));
        if (!ref) {
          return false;
        }
        const Protocol* p = protocol_at(ref->pinned_protocol);
        char buf[192];
        snprintf(buf, sizeof(buf), "%016llx  %3d  %s  %s  %s  %s\n",
                 static_cast<unsigned long long>(pack(ver_of(rv), slot)),
                 ref->fd(), endpoint2str(ref->remote()).c_str(),
                 ref->mode() == SocketMode::kTcp
                     ? "tcp"
                     : ref->mode() == SocketMode::kShm
                           ? "shm"
                           : ref->mode() == SocketMode::kIci ? "ici" : "?",
                 p != nullptr ? p->name : "-",
                 ref->connected() ? "connected" : "connecting");
        *line = buf;
        return true;
      });
}

std::string Socket::DumpHotState() {
  return dump_pool_table<Socket>(
      "socket hot state (fd  nevent  writing  queued  conn  failed)\n",
      200, [](uint32_t slot, Socket* s, std::string* line) {
        // Acquire: liveness must see the latest generation/refcount.
        const uint64_t rv = s->ref_ver_.load(std::memory_order_acquire);
        if ((ver_of(rv) & 1) == 0 || ref_of(rv) == 0) {
          return false;
        }
        if (line == nullptr) {
          return true;
        }
        SocketRef ref(Socket::Address(pack(ver_of(rv), 0) | slot));
        if (!ref) {
          return false;
        }
        // Atomics only — never walk the write chain (a concurrent drain
        // frees/reuses nodes) and never touch the read buffer (owned by
        // the read fiber).  queued=1 with writing=0 is the wedge
        // signature this view exists to catch.
        const bool queued =
            ref->wq_head_.load(std::memory_order_acquire) != nullptr;
        char buf[160];
        snprintf(buf, sizeof(buf),
                 "fd=%d nevent=%d writing=%d queued=%d conn=%d failed=%d\n",
                 ref->fd(), ref->nevent_.load(), (int)ref->writing_.load(),
                 (int)queued, (int)ref->connected(), (int)ref->Failed());
        *line = buf;
        return true;
      });
}

void Socket::Dereference() {
  const uint64_t prev = ref_ver_.fetch_sub(kRefUnit, std::memory_order_acq_rel);
  if (ref_of(prev) == 1) {
    // Last reference.  SetFailed already bumped the version to even, so
    // Address() cannot revive this slot — teardown is single-threaded here.
    if (fd_ >= 0) {
      EventDispatcher::for_fd(fd_)->remove(fd_);
      close(fd_);
      fd_ = -1;
    }
    drop_write_queue();
    pending_.clear();
    pending_close_ = false;
    read_buf_.clear();
    transport_ctx = nullptr;
    transport_ctx_holder_.reset();  // releases e.g. the shm mapping
    g_socket_count.fetch_sub(1, std::memory_order_relaxed);
    SocketPool::instance()->release(slot_.load(std::memory_order_relaxed));
  }
}

namespace {
std::atomic<void (*)(SocketId)> g_failure_observer{nullptr};
}  // namespace

void Socket::set_failure_observer(void (*cb)(SocketId)) {
  g_failure_observer.store(cb, std::memory_order_release);
}

void Socket::SetFailed(int err) {
  bool expect = false;
  if (!failed_.compare_exchange_strong(expect, true,
                                       std::memory_order_acq_rel)) {
    return;  // already failed
  }
  (void)err;
  // Captured BEFORE the version bump: this is the id every holder (stream
  // bindings, pending calls) stored; id() after the bump names the next
  // incarnation.
  const SocketId failed_id = id();
  // Bump the version to even FIRST: from this point Address() fails, so the
  // refcount can only drain — the teardown in Dereference can never race a
  // revival (the ordering socket.h:498's versioned-ref pattern exists for).
  uint64_t rv = ref_ver_.load(std::memory_order_relaxed);
  while (!ref_ver_.compare_exchange_weak(
      rv, pack(ver_of(rv) + 1, ref_of(rv)), std::memory_order_acq_rel)) {
  }
  // Wake any fiber parked on writability so it observes the failure.
  wr_ev_.value.fetch_add(1, std::memory_order_release);
  wr_ev_.wake_all();
  void (*observer)(SocketId) =
      g_failure_observer.load(std::memory_order_acquire);
  if (observer != nullptr) {
    observer(failed_id);
  }
  // Drop the owner reference (Create's).
  Dereference();
}

namespace {

// TLS WriteNode freelist.  One node is allocated per Socket::Write; at
// 100k+ qps that malloc/free pair plus the inner IOBuf refs-vector churn
// is measurable (r5 1KB-echo profile).  Nodes freed on one thread serve
// later Writes on the same thread; cross-thread imbalance just degrades
// to plain malloc.
struct WriteNodeCacheTag {};

void drain_write_node(void*& n) { Socket::destroy_write_node_opaque(n); }

std::vector<void*>* tls_write_node_cache() {
  return TlsFreeCache<void*, WriteNodeCacheTag>::get(&drain_write_node);
}

constexpr size_t kMaxCachedWriteNodes = 64;
// Byte cap on what the freelist may pin: a cached node's cleared IOBuf
// still owns its refs-vector capacity (a 64MB write sliced into 16KB
// blocks leaves a ~64KB vector), so 64 nodes could silently hold MBs per
// thread.  Nodes over the per-thread budget get their storage shrunk
// before caching.
constexpr size_t kMaxCachedWriteBytes = 256 * 1024;

// Refs-vector capacity bytes currently pinned by this thread's cache.
thread_local size_t tls_write_node_cache_bytes = 0;

}  // namespace

Socket::WriteNode* Socket::alloc_write_node(IOBuf&& data, bool close_after) {
  std::vector<void*>* cache = tls_write_node_cache();
  if (cache != nullptr && !cache->empty()) {
    auto* n = static_cast<WriteNode*>(cache->back());
    cache->pop_back();
    const size_t held = n->data.ref_capacity_bytes();
    tls_write_node_cache_bytes -=
        std::min(tls_write_node_cache_bytes, held);
    n->data = std::move(data);
    n->close_after = close_after;
    n->next = nullptr;
    return n;
  }
  return new WriteNode{std::move(data), close_after, nullptr};
}

void Socket::free_write_node(WriteNode* n) {
  std::vector<void*>* cache = tls_write_node_cache();
  if (cache != nullptr && cache->size() < kMaxCachedWriteNodes) {
    n->data.clear();  // release block refs NOW, not at reuse time
    size_t held = n->data.ref_capacity_bytes();
    if (tls_write_node_cache_bytes + held > kMaxCachedWriteBytes) {
      n->data.shrink_storage();  // over budget: drop the vector heap too
      held = n->data.ref_capacity_bytes();
    }
    tls_write_node_cache_bytes += held;
    cache->push_back(n);
    return;
  }
  delete n;
}

void Socket::destroy_write_node_opaque(void* n) {
  delete static_cast<WriteNode*>(n);
}

void Socket::drop_write_queue() {
  // Acquire: claims the chain — must see every producer's node payload
  // (their CAS push released it into wq_head_).
  WriteNode* n = wq_head_.exchange(nullptr, std::memory_order_acquire);
  while (n != nullptr) {
    WriteNode* next = n->next;
    free_write_node(n);
    n = next;
  }
}

// ---- input path ---------------------------------------------------------

void Socket::on_input_event() {
  if (nevent_.fetch_add(1, std::memory_order_acq_rel) == 0 &&
      on_readable_ != nullptr) {
    // Hand off to a fiber carrying the versioned id (the fiber re-Addresses).
    // The tag pin routes a tagged server's whole pipeline (this read fiber,
    // and by inheritance its handler + KeepWrite fibers) to its group.
    fiber_start(nullptr, &Socket::read_fiber_thunk,
                reinterpret_cast<void*>(id()),
                kFiberUrgent | fiber_tag_flags(worker_tag));
  }
}

void Socket::read_fiber_thunk(void* arg) {
  const SocketId id = reinterpret_cast<uint64_t>(arg);
  Socket* s = Socket::Address(id);
  if (s == nullptr) {
    return;
  }
  // Close the connect→first-readable kernel edge (see ensure_connected).
  TRPC_TSAN_ACQUIRE(s);
  while (true) {
    const int seen = s->nevent_.load(std::memory_order_acquire);
    s->on_readable_(id, s->ctx_);
    int expect = seen;
    if (s->nevent_.compare_exchange_strong(expect, 0,
                                           std::memory_order_acq_rel)) {
      break;
    }
  }
  s->Dereference();
}

void Socket::on_output_event() {
  wr_ev_.value.fetch_add(1, std::memory_order_release);
  wr_ev_.wake_all();
}

int Socket::wait_writable(uint32_t snap, int64_t deadline_us) {
  const int rc = wr_ev_.wait(snap, deadline_us);
  return rc == ETIMEDOUT ? rc : 0;
}

// ---- connect ------------------------------------------------------------

int Socket::ensure_connected() {
  if (connected_.load(std::memory_order_acquire)) {
    return 0;
  }
  if (fd_ < 0) {
    const bool un = remote_.is_unix();
    const int fd =
        ::socket(un ? AF_UNIX : AF_INET, SOCK_STREAM | SOCK_NONBLOCK, 0);
    if (fd < 0) {
      return -1;
    }
    if (!un) {
      int one = 1;
      setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
    }
    fd_ = fd;
    if (EventDispatcher::for_fd(fd_)->add(fd_, id()) != 0) {
      return -1;
    }
  }
  const int rc = transport_->connect(this);
  if (rc == 0) {
    // Kernel-mediated edge TSan cannot model: the read fiber's first
    // readv is ordered after connect() by the kernel (a readable event
    // needs delivered bytes, which need an established connection), but
    // TSan only draws epoll_ctl→epoll_wait.  Pairs with the acquire at
    // read_fiber_thunk entry; replaces the old blanket
    // race:trpc::Socket::ensure_connected suppression (ISSUE 7).
    TRPC_TSAN_RELEASE(this);
    connected_.store(true, std::memory_order_release);
  }
  return rc;
}

// ---- wait-free write path ----------------------------------------------
//
// One MPSC Treiber chain + a writer-role flag.  The producer that pushes
// onto an EMPTY chain claims the role; everyone else just enqueues.  The
// role-holder drains the WHOLE reversed chain into pending_ (one
// coalesced buffer → one writev/doorbell per drain) and, on the fast
// path, flushes it INLINE on the caller — no KeepWrite fiber, no
// ParkingLot signal, no context switch.  Only EAGAIN leftovers, lazy
// connects and close_after teardown fall back to the KeepWrite fiber.
//
// The role handoff is the delicate part: the exit sequence
// [writing_=false; re-check head] races the producer sequence
// [push head; try-claim writing_].  Both sides are seq_cst — with
// anything weaker the StoreLoad pairs can miss each other (x86 reorders
// a release-store past a later acquire-load of a DIFFERENT word), each
// side concludes the other owns the drain, and the queued node wedges
// the connection forever.  That exact lost-wakeup shipped in the seed
// and capped the 1KB bench at a few hundred QPS per wedge window.

int Socket::Write(IOBuf&& data, bool close_after) {
  if (Failed()) {
    return -1;
  }
  WriteNode* node = alloc_write_node(std::move(data), close_after);
  // Relaxed initial read: the CAS below (seq_cst, see the role-handoff
  // comment above Write) is what orders the push; a stale head only
  // costs one CAS retry.
  WriteNode* old = wq_head_.load(std::memory_order_relaxed);
  do {
    node->next = old;
  } while (!wq_head_.compare_exchange_weak(old, node,
                                           std::memory_order_seq_cst,
                                           // failure: retry re-reads head
                                           std::memory_order_relaxed));
  if (old != nullptr) {
    return 0;  // an active writer owns the drain
  }
  bool expect = false;
  if (!writing_.compare_exchange_strong(expect, true,
                                        std::memory_order_seq_cst)) {
    return 0;  // the exiting writer's re-check adopts our node
  }
  // We hold the writer role.  Fast path: flush inline on this thread.
  // A true return covers graceful close_after teardown and transport
  // errors too — like the KeepWrite path, those surface through the
  // socket's failed state, not through this (already-accepted) Write.
  if (try_inline_write()) {
    return 0;
  }
  // Leftovers (EAGAIN / not yet connected / bounded rounds exhausted):
  // continue in a KeepWrite fiber that inherits pending_ with the role.
  // Take a strong ref for the fiber's lifetime.
  Socket* self = Socket::Address(id());
  if (self == nullptr) {
    // Failed under us; nothing will ever drain — purge and bail.
    abort_writer(ECONNRESET);
    return -1;
  }
  fiber_start(nullptr, &Socket::keep_write_thunk, self,
              kFiberUrgent | fiber_tag_flags(worker_tag));
  return 0;
}

size_t Socket::drain_queue_into_pending() {
  // Acquire: claims the chain — pairs with producers' CAS release so
  // the drain sees every node's IOBuf payload.
  WriteNode* chain = wq_head_.exchange(nullptr, std::memory_order_acquire);
  if (chain == nullptr) {
    return 0;
  }
  WriteNode* fifo = nullptr;
  while (chain != nullptr) {  // LIFO chain → FIFO
    WriteNode* next = chain->next;
    chain->next = fifo;
    fifo = chain;
    chain = next;
  }
  size_t n = 0;
  while (fifo != nullptr) {
    pending_.append(std::move(fifo->data));
    pending_close_ |= fifo->close_after;
    WriteNode* done = fifo;
    fifo = fifo->next;
    free_write_node(done);
    ++n;
  }
  HotPathVars& hv = hotpath_vars();
  hv.write_coalesce_drains << 1;
  hv.write_coalesce_nodes << static_cast<int64_t>(n);
  hv.write_coalesce_max << static_cast<int64_t>(n);
  if (hotpath_sample16()) {
    hv.write_coalesce_batch << static_cast<int64_t>(n);
  }
  return n;
}

bool Socket::release_writer_role() {
  writing_.store(false, std::memory_order_seq_cst);
  if (wq_head_.load(std::memory_order_seq_cst) != nullptr) {
    bool expect = false;
    if (writing_.compare_exchange_strong(expect, true,
                                         std::memory_order_seq_cst)) {
      return false;  // adopted a late node; keep draining
    }
  }
  return true;
}

void Socket::abort_writer(int err) {
  SetFailed(err);
  pending_.clear();
  pending_close_ = false;
  drop_write_queue();
  // writing_ stays true: the socket is failed, so no producer will ever
  // need the role again; reset_for_reuse re-arms it with the slot.
}

bool Socket::try_inline_write() {
  // Lazy connects park the calling fiber — never inline-eligible.
  if (!connected_.load(std::memory_order_acquire)) {
    return false;
  }
  HotPathVars& hv = hotpath_vars();
  hv.inline_write_attempts << 1;
  // Bounded rounds: an inline writer should flush what WAS queued, not
  // become an unwitting forever-writer for every concurrent producer.
  for (int round = 0; round < 4; ++round) {
    drain_queue_into_pending();
    if (pending_.empty()) {
      if (pending_close_) {
        // An empty-payload close_after batch (everything before it
        // already flushed): honor the close now — releasing the role
        // here would drop the close AND leave the latch armed for an
        // unrelated later batch.
        drop_write_queue();
        SetFailed(ESHUTDOWN);
        return true;
      }
      if (release_writer_role()) {
        hv.inline_write_hits << 1;
        return true;
      }
      continue;  // late node adopted with the role
    }
    while (!pending_.empty()) {
      const ssize_t rc = transport_->cut_from_iobuf(this, &pending_);
      if (rc < 0) {
        transport_->flush(this);
        abort_writer(errno);
        return true;  // role retired with the socket
      }
      if (rc == 0) {  // EAGAIN: the KeepWrite fiber parks on the edge
        transport_->flush(this);
        return false;
      }
    }
    transport_->flush(this);
    if (pending_close_) {
      // Fully flushed Connection:-close batch — graceful close here;
      // anything enqueued after it is void by contract.
      drop_write_queue();
      SetFailed(ESHUTDOWN);
      return true;
    }
  }
  // Rounds exhausted with the queue still live: hand off.
  return false;
}

void Socket::keep_write_thunk(void* arg) {
  Socket* s = static_cast<Socket*>(arg);
  s->keep_write();
  s->Dereference();
}

void Socket::keep_write() {
  while (true) {
    // Drain newly queued nodes on top of any inline-path leftovers.
    drain_queue_into_pending();
    if (pending_.empty()) {
      if (pending_close_) {  // empty-payload close_after: honor it now
        drop_write_queue();
        SetFailed(ESHUTDOWN);
        return;
      }
      if (release_writer_role()) {
        return;
      }
      continue;
    }
    if (ensure_connected() != 0) {
      abort_writer(errno);
      return;
    }
    while (!pending_.empty()) {
      const uint32_t snap = writable_snap();
      const ssize_t rc = transport_->cut_from_iobuf(this, &pending_);
      if (rc < 0) {
        transport_->flush(this);
        abort_writer(errno);
        return;
      }
      if (rc == 0) {  // EAGAIN: park until the writable edge
        // Publish staged descriptors BEFORE parking: a ring that only
        // learns of them at the next flush would never drain, and the
        // writable edge this fiber waits for would never come.
        transport_->flush(this);
        if (Failed()) {
          abort_writer(ECONNRESET);
          return;
        }
        // Sliced wait: fd-less transports have no HUP edge, so a dead peer
        // is only noticed through Failed() re-checks.
        wait_writable(snap, monotonic_time_us() + 1000000);
      }
    }
    transport_->flush(this);
    if (pending_close_) {
      // This batch carried a Connection: close response and it has fully
      // flushed — graceful close (anything enqueued after it is void).
      drop_write_queue();
      SetFailed(ESHUTDOWN);
      return;
    }
  }
}

// ---- misc ---------------------------------------------------------------

void make_nonblocking(int fd) {
  const int flags = fcntl(fd, F_GETFL, 0);
  fcntl(fd, F_SETFL, flags | O_NONBLOCK);
}

}  // namespace trpc
