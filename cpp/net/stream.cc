#include "net/stream.h"

#include <cerrno>
#include <mutex>
#include <unordered_map>
#include <vector>

#include "base/logging.h"
#include "base/resource_pool.h"
#include "base/time.h"
#include "fiber/event.h"
#include "fiber/execution_queue.h"
#include "fiber/fiber.h"
#include "net/protocol.h"
#include "net/rma.h"
#include "net/socket.h"
#include "net/stripe.h"
#include "stat/reducer.h"

namespace trpc {

namespace {

// Always-on counters of the plane, over every stream of the process (both
// ends of a stream whose ends share it).  A chunk is "written" when
// StreamWrite handed its frame to the socket and "consumed" when its
// bytes were given back to the writer's window.
struct StreamVars {
  Adder chunks_written;
  Adder bytes_written;
  Adder one_sided_bytes;
  Adder chunks_consumed;
  Adder bytes_consumed;
  Adder credit_wait_us;
  Adder acks_sent;
  Maxer unread_high_water;
  StreamVars() {
    chunks_written.expose("stream_chunks_written",
                          "stream chunks handed to the socket by "
                          "StreamWrite");
    bytes_written.expose("stream_bytes_written",
                         "payload bytes of stream_chunks_written");
    one_sided_bytes.expose("stream_one_sided_bytes",
                           "the part of stream_bytes_written whose chunk "
                           "was put into the peer's receive window "
                           "(net/rma.h), its frame carrying the descriptor "
                           "alone");
    chunks_consumed.expose("stream_chunks_consumed",
                           "stream chunks whose bytes the consumer gave "
                           "back to the writer's window");
    bytes_consumed.expose("stream_bytes_consumed",
                          "payload bytes of stream_chunks_consumed");
    credit_wait_us.expose("stream_credit_wait_us",
                          "time StreamWrite spent parked on an exhausted "
                          "window");
    acks_sent.expose("stream_acks_sent",
                     "ACK (feedback) frames sent to writers");
    unread_high_water.expose("stream_unread_high_water_bytes",
                             "most bytes any one stream has held received "
                             "and not yet given back (bound: its window "
                             "plus one chunk)");
    unread_high_water << 0;  // a Maxer nobody fed reads INT64_MIN
  }
};

// Leaked with the registry, as the other always-on counters are.
StreamVars& g_vars = *new StreamVars();

struct StreamMeta {
  std::atomic<uint32_t> version{0};  // even = idle slot
  uint32_t slot = 0;
  // Guards version transitions vs queue submission (closes the
  // validated-then-recycled race on arriving frames).
  std::atomic_flag mu = ATOMIC_FLAG_INIT;
  void lock() {
    while (mu.test_and_set(std::memory_order_acquire)) {
    }
  }
  void unlock() { mu.clear(std::memory_order_release); }

  SocketId sock = 0;
  std::atomic<uint64_t> peer_sid{0};  // 0 until established
  Event established_ev;               // value flips 0→1 when peer_sid set

  StreamOptions opts;
  // The call that opened the stream asked for checksums: every data frame
  // carries one (over the frame's payload, or per chunk of a one-sided
  // transfer).
  bool checksum = false;

  // Sender credit (bytes we may still send before more ACKs).
  std::atomic<int64_t> send_window{0};
  // Data frames written so far: makes each one's transfer token.
  // Relaxed: uniqueness only.
  std::atomic<uint64_t> tx_seq{0};
  Event window_ev;  // bumped on every ACK / close

  // Receiver: consumed-but-unacked bytes; ACK when above half window.
  std::atomic<int64_t> unacked{0};
  // Receiver: bytes that arrived and were not yet given back, and the
  // most that ever was.
  std::atomic<int64_t> unread{0};
  std::atomic<int64_t> unread_high_water{0};

  std::atomic<bool> closed{false};
  Event close_ev;  // value flips 0→1 on close

  // Allocated once per slot and REUSED across stream incarnations (type-
  // stable, like the meta itself) so late frames can never touch freed
  // memory; stopped_ rejects them instead.
  ExecutionQueue<IOBuf*>* consume_q = nullptr;

  StreamId id() const {
    return (static_cast<uint64_t>(version.load(std::memory_order_relaxed))
            << 32) |
           slot;
  }
};

using StreamPool = ResourcePool<StreamMeta>;

void mark_closed(StreamMeta* m);

// socket id → live StreamIds bound to it, so a connection failure can
// close its streams eagerly (stream_on_connection_failed).  Bound at
// establishment (when m->sock is set), unbound at StreamClose.  A plain
// mutex: establishment/close are per-stream events, not per-frame.
std::mutex& by_socket_mu() {
  static std::mutex mu;
  return mu;
}
std::unordered_multimap<uint64_t, StreamId>& by_socket() {
  // Heap-allocated and intentionally never destroyed: detached consumer
  // fibers can still be delivering deferred CLOSEs (→ StreamClose →
  // unbind_socket) while static destructors run at process exit, and an
  // at-exit teardown of this map races them.
  static auto* m = new std::unordered_multimap<uint64_t, StreamId>();
  return *m;
}

void bind_socket(uint64_t sock, StreamId sid) {
  if (sock == 0) {
    return;
  }
  std::lock_guard<std::mutex> g(by_socket_mu());
  by_socket().emplace(sock, sid);
}

void unbind_socket(uint64_t sock, StreamId sid) {
  if (sock == 0) {
    return;
  }
  std::lock_guard<std::mutex> g(by_socket_mu());
  auto range = by_socket().equal_range(sock);
  for (auto it = range.first; it != range.second; ++it) {
    if (it->second == sid) {
      by_socket().erase(it);
      return;
    }
  }
}

void drop_chunk(IOBuf*& chunk) { delete chunk; }

StreamMeta* stream_of(StreamId id) {
  const uint32_t ver = static_cast<uint32_t>(id >> 32);
  if ((ver & 1) == 0) {
    return nullptr;
  }
  StreamMeta* m = StreamPool::instance()->at(static_cast<uint32_t>(id));
  if (m == nullptr || m->version.load(std::memory_order_acquire) != ver) {
    return nullptr;
  }
  return m;
}

// Sends accumulated credit back when above half the granted window.  A
// stream whose peer is not yet bound (early frames racing the accept
// response) keeps accumulating; the bind path re-tries the ack.
void maybe_send_ack(StreamMeta* m) {
  const uint64_t peer = m->peer_sid.load(std::memory_order_acquire);
  if (peer == 0) {
    return;
  }
  if (m->unacked.load(std::memory_order_acquire) <
      m->opts.window_bytes / 2) {
    return;
  }
  // One taker: the consume fiber and a reader's StreamConsumed may both
  // be here.
  const int64_t unacked = m->unacked.exchange(0, std::memory_order_acq_rel);
  if (unacked <= 0) {
    return;
  }
  g_vars.acks_sent << 1;
  RpcMeta ack;
  ack.type = RpcMeta::kStreamFrame;
  ack.stream_flags = RpcMeta::kStreamAck;
  ack.stream_id = peer;
  ack.ack_bytes = static_cast<uint64_t>(unacked);
  stripe_frame_send(m->sock, std::move(ack), IOBuf());  // best effort
}

// The consumer has used `bytes` of one chunk: they leave the unread count
// and go back to the writer.
void give_back(StreamMeta* m, int64_t bytes) {
  g_vars.chunks_consumed << 1;
  g_vars.bytes_consumed << bytes;
  m->unread.fetch_sub(bytes, std::memory_order_acq_rel);
  m->unacked.fetch_add(bytes, std::memory_order_acq_rel);
  maybe_send_ack(m);  // feedback frame parity
}

int consume_handler(void* meta, IOBuf** chunks, size_t n) {
  StreamMeta* m = static_cast<StreamMeta*>(meta);
  const StreamId sid = m->id();
  for (size_t i = 0; i < n; ++i) {
    IOBuf* chunk = chunks[i];
    if (chunk == nullptr) {
      // CLOSE sentinel: rides the queue so every data chunk ahead of it is
      // delivered first (ordered close).  Data frames racing the close may
      // land BEHIND the sentinel in this same batch — they are dropped, but
      // their heap chunks must still be freed (consume() only deletes the
      // batch array).  Nothing may touch `m` after mark_closed — on_closed
      // typically calls StreamClose which recycles the meta.
      for (size_t j = i + 1; j < n; ++j) {
        delete chunks[j];
      }
      mark_closed(m);
      return 1;
    }
    const int64_t bytes = static_cast<int64_t>(chunk->size());
    const bool delivered =
        m->opts.on_message && !m->closed.load(std::memory_order_acquire);
    if (delivered) {
      m->opts.on_message(sid, std::move(*chunk));
    }
    delete chunk;
    // A chunk only taken delivery of is given back by StreamConsumed.
    if (!(delivered && m->opts.credit_on_consumed)) {
      give_back(m, bytes);
    }
  }
  return 0;
}

StreamId new_stream(const StreamOptions& opts, const Controller* cntl) {
  // First stream in the process arms the socket-failure observer so
  // connection death reaches every bound stream (closes the wedge where a
  // reader with no pending write never learns the peer died).
  static const bool hooked = [] {
    Socket::set_failure_observer(&stream_on_connection_failed);
    return true;
  }();
  (void)hooked;
  StreamMeta* m = nullptr;
  const uint32_t slot = StreamPool::instance()->acquire(&m);
  if (m == nullptr) {
    return 0;
  }
  if (m->consume_q != nullptr) {
    // Previous incarnation's consumer must finish BEFORE any state is
    // reset, not merely before the queue is reconfigured: a peer CLOSE
    // sentinel that raced into the queue just ahead of the local
    // StreamClose is still draining here, and its mark_closed must land
    // on the old incarnation (where `closed` is already true — a no-op)
    // rather than close the next stream at birth.  Found as a ~2%
    // born-closed rate under sequential completion traffic.
    while (!m->consume_q->idle()) {
      if (in_fiber()) {
        fiber_yield();
      } else {
        sched_yield();
      }
    }
  }
  m->slot = slot;
  m->opts = opts;
  m->checksum = cntl->checksum_enabled();
  m->sock = 0;
  m->peer_sid.store(0, std::memory_order_relaxed);
  m->established_ev.value.store(0, std::memory_order_relaxed);
  m->send_window.store(opts.window_bytes, std::memory_order_relaxed);
  m->window_ev.value.store(0, std::memory_order_relaxed);
  m->tx_seq.store(0, std::memory_order_relaxed);
  m->unacked.store(0, std::memory_order_relaxed);
  m->unread.store(0, std::memory_order_relaxed);
  m->unread_high_water.store(0, std::memory_order_relaxed);
  m->closed.store(false, std::memory_order_relaxed);
  m->close_ev.value.store(0, std::memory_order_relaxed);
  m->lock();
  if (m->consume_q == nullptr) {
    m->consume_q = new ExecutionQueue<IOBuf*>();
    m->consume_q->start(consume_handler, m, drop_chunk);
  } else {
    m->consume_q->restart(consume_handler, m, drop_chunk);
  }
  const uint32_t ver = m->version.load(std::memory_order_relaxed) + 1;
  m->version.store(ver, std::memory_order_release);
  m->unlock();
  return m->id();
}

// Best-effort CLOSE frame to the peer's end.
void send_close(StreamMeta* m) {
  const uint64_t peer = m->peer_sid.load(std::memory_order_acquire);
  if (peer == 0) {
    return;
  }
  RpcMeta meta;
  meta.type = RpcMeta::kStreamFrame;
  meta.stream_flags = RpcMeta::kStreamClose;
  meta.stream_id = peer;
  stripe_frame_send(m->sock, std::move(meta), IOBuf());
}

void mark_closed(StreamMeta* m) {
  if (m->closed.exchange(true, std::memory_order_acq_rel)) {
    return;
  }
  m->close_ev.value.store(1, std::memory_order_release);
  m->close_ev.wake_all();
  m->window_ev.value.fetch_add(1, std::memory_order_release);
  m->window_ev.wake_all();
  // Writers parked awaiting establishment must observe the death NOW
  // (an unaccepted batch offer would otherwise wait out its timeout).
  m->established_ev.wake_all();
  if (m->opts.on_closed) {
    m->opts.on_closed(m->id());
  }
}

// Closes incarnation `sid` of `m` behind the chunks already queued: the
// close rides the consume queue as a sentinel, under the meta lock.  The
// version bump and the queue's stop happen under this same lock, so a
// stale sid (a concurrent StreamClose and the slot's reuse since the
// caller's stream_of) cannot close the NEXT incarnation at birth; a
// sentinel that lands anyway drains against the old incarnation before
// new_stream resets state.
void close_in_order(StreamMeta* m, StreamId sid) {
  m->lock();
  const bool ver_ok = m->version.load(std::memory_order_relaxed) ==
                      static_cast<uint32_t>(sid >> 32);
  const bool queued = ver_ok && m->consume_q != nullptr &&
                      m->consume_q->execute(nullptr) == 0;
  m->unlock();
  if (ver_ok && !queued) {
    mark_closed(m);
  }
}

// A data frame's transfer token (it rides the frame's correlation_id,
// which a stream frame does not otherwise use): the receiver's stream id
// mixed with the frame's sequence number on its stream, so that no two
// transfers on a connection share one, and a put that comes late into a
// window span since recycled fails rma_resolve's compare of the span's
// token with its control frame's.
uint64_t transfer_token(uint64_t peer_sid, uint64_t seq) {
  return (peer_sid * 0x9E3779B97F4A7C15ull) ^ seq;
}

}  // namespace

int StreamCreate(StreamId* out, Controller* cntl, const StreamOptions& opts) {
  const StreamId sid = new_stream(opts, cntl);
  if (sid == 0) {
    return ENOMEM;
  }
  cntl->call().offered_stream = sid;
  *out = sid;
  return 0;
}

namespace {

// Accepts ONE offered (peer_sid, peer_window); returns the local id.
StreamId accept_one(Controller* cntl, const StreamOptions& opts,
                    uint64_t peer_sid, uint64_t peer_window) {
  const StreamId sid = new_stream(opts, cntl);
  if (sid == 0) {
    return 0;
  }
  StreamMeta* m = stream_of(sid);
  m->sock = cntl->call().socket_id;
  m->peer_sid.store(peer_sid, std::memory_order_release);
  // Our send credit is whatever receive window the CLIENT advertised.
  m->send_window.store(static_cast<int64_t>(peer_window),
                       std::memory_order_release);
  m->established_ev.value.store(1, std::memory_order_release);
  m->established_ev.wake_all();
  bind_socket(m->sock, sid);
  return sid;
}

}  // namespace

int StreamAccept(StreamId* out, Controller* cntl, const StreamOptions& opts) {
  if (cntl->call().peer_stream == 0) {
    return EINVAL;  // request offered no stream
  }
  const StreamId sid = accept_one(cntl, opts, cntl->call().peer_stream,
                                  cntl->call().peer_stream_window);
  if (sid == 0) {
    return ENOMEM;
  }
  cntl->call().accepted_stream = sid;  // rides back in the response meta
  *out = sid;
  return 0;
}

int StreamCreateBatch(std::vector<StreamId>* out, int count,
                      Controller* cntl, const StreamOptions& opts) {
  if (count <= 0 || count > 256) {
    return EINVAL;
  }
  out->clear();
  for (int i = 0; i < count; ++i) {
    const StreamId sid = new_stream(opts, cntl);
    if (sid == 0) {
      for (StreamId created : *out) {
        StreamClose(created);
      }
      out->clear();
      return ENOMEM;
    }
    out->push_back(sid);
  }
  cntl->call().offered_stream = (*out)[0];
  cntl->call().extra_offered.assign(out->begin() + 1, out->end());
  return 0;
}

int StreamAcceptBatch(std::vector<StreamId>* out, Controller* cntl,
                      const StreamOptions& opts) {
  if (cntl->call().peer_stream == 0) {
    return EINVAL;
  }
  out->clear();
  const StreamId first = accept_one(cntl, opts, cntl->call().peer_stream,
                                    cntl->call().peer_stream_window);
  if (first == 0) {
    return ENOMEM;
  }
  out->push_back(first);
  for (const auto& [peer_sid, peer_window] : cntl->call().extra_peer) {
    const StreamId sid = accept_one(cntl, opts, peer_sid, peer_window);
    if (sid == 0) {
      for (StreamId created : *out) {
        StreamClose(created);
      }
      out->clear();
      return ENOMEM;
    }
    out->push_back(sid);
  }
  cntl->call().accepted_stream = (*out)[0];
  cntl->call().extra_accepted.assign(out->begin() + 1, out->end());
  return 0;
}

int StreamWrite(StreamId id, IOBuf&& data) {
  StreamMeta* m = stream_of(id);
  if (m == nullptr) {
    return EINVAL;
  }
  // Wait for establishment (client side: response not yet back).
  while (m->established_ev.value.load(std::memory_order_acquire) == 0) {
    if (m->closed.load(std::memory_order_acquire)) {
      return EPIPE;
    }
    m->established_ev.wait(0, monotonic_time_us() + 10 * 1000 * 1000);
    if (stream_of(id) != m) {
      return EINVAL;
    }
  }
  const int64_t bytes = static_cast<int64_t>(data.size());
  // Credit gate: park while the window is exhausted.  A chunk is admitted
  // as soon as any of the window is open, whatever its size (upstream's
  // AppendIfNotFull), and takes the credit below zero by what it overran:
  // what is written and not given back stays under window + one chunk,
  // and a chunk wider than the window still goes.  Each wakeup also
  // probes the connection so a dead peer (no CLOSE ever arriving) unparks
  // the writer within one probe interval.
  int64_t window = m->send_window.load(std::memory_order_acquire);
  int64_t parked_at = 0;
  while (true) {
    if (m->closed.load(std::memory_order_acquire) || stream_of(id) != m) {
      return EPIPE;
    }
    {
      SocketRef s(Socket::Address(m->sock));
      if (!s || s->Failed()) {
        mark_closed(m);
        return EPIPE;
      }
    }
    if (window > 0 || bytes == 0) {
      if (m->send_window.compare_exchange_weak(window, window - bytes,
                                               std::memory_order_acq_rel)) {
        break;
      }
      continue;  // `window` reloaded by the failed CAS
    }
    const uint32_t snap = m->window_ev.value.load(std::memory_order_acquire);
    window = m->send_window.load(std::memory_order_acquire);
    if (window > 0) {
      continue;  // refilled between checks
    }
    if (parked_at == 0) {
      parked_at = monotonic_time_us();
    }
    m->window_ev.wait(snap, monotonic_time_us() + 1000 * 1000);
    window = m->send_window.load(std::memory_order_acquire);
  }
  if (parked_at != 0) {
    g_vars.credit_wait_us << (monotonic_time_us() - parked_at);
  }
  RpcMeta meta;
  meta.type = RpcMeta::kStreamFrame;
  meta.stream_flags = RpcMeta::kStreamData;
  meta.stream_id = m->peer_sid.load(std::memory_order_acquire);
  meta.correlation_id = transfer_token(
      meta.stream_id, m->tx_seq.fetch_add(1, std::memory_order_relaxed));
  meta.has_checksum = m->checksum;
  // A chunk over the large-message threshold, on a connection with a
  // one-sided session, is put into the peer's receive window and only its
  // descriptor is framed (net/rma.h: the path a unary body takes, chosen
  // by the same rule).  The control frame is written when the put is
  // done, where the in-band frame would have been written, so the
  // stream's DATA, ACK and CLOSE frames keep the socket's order whichever
  // way each body went.  1: not this time (no session, under the
  // threshold, window full): the chunk is framed whole, as a unary body
  // is.
  int one_sided = rma_try_send(m->sock, &meta, &data, 0, 0);
  if (one_sided > 0 &&
      stripe_frame_send(m->sock, std::move(meta), std::move(data)) != 0) {
    one_sided = -1;
  }
  if (one_sided < 0) {
    mark_closed(m);
    return EPIPE;
  }
  g_vars.chunks_written << 1;
  g_vars.bytes_written << bytes;
  if (one_sided == 0) {
    g_vars.one_sided_bytes << bytes;
  }
  return 0;
}

int StreamConsumed(StreamId id, size_t bytes) {
  StreamMeta* m = stream_of(id);
  if (m == nullptr) {
    return EINVAL;
  }
  give_back(m, static_cast<int64_t>(bytes));
  return 0;
}

int StreamClose(StreamId id) {
  StreamMeta* m = stream_of(id);
  if (m == nullptr) {
    return EINVAL;
  }
  if (!m->closed.load(std::memory_order_acquire)) {
    send_close(m);
  }
  mark_closed(m);
  // Destroy the local id under the meta lock: frame submission validates
  // the version under the same lock, so no frame can enter the queue after
  // the bump; the queue itself is persistent (stopped, reused on next
  // incarnation after it drains).
  const uint64_t sock = m->sock;
  const uint32_t ver = static_cast<uint32_t>(id >> 32);
  m->lock();
  uint32_t expect = ver;
  if (!m->version.compare_exchange_strong(expect, ver + 1,
                                          std::memory_order_acq_rel)) {
    m->unlock();
    return 0;  // someone else destroyed concurrently
  }
  m->consume_q->stop();
  m->unlock();
  unbind_socket(sock, id);
  StreamPool::instance()->release(m->slot);
  return 0;
}

int StreamWait(StreamId id, int64_t deadline_us) {
  StreamMeta* m = stream_of(id);
  if (m == nullptr) {
    return 0;  // already gone == closed
  }
  while (!m->closed.load(std::memory_order_acquire)) {
    if (stream_of(id) != m) {
      return 0;
    }
    const int rc = m->close_ev.wait(0, deadline_us);
    if (rc == ETIMEDOUT) {
      return rc;
    }
  }
  return 0;
}

bool StreamExists(StreamId id) { return stream_of(id) != nullptr; }

// ---- wiring ---------------------------------------------------------------

void stream_on_frame(InputMessage&& msg) {
  StreamMeta* m = stream_of(msg.meta.stream_id);
  if (m == nullptr) {
    return;  // stale frame after close: harmless (versioned id armor)
  }
  switch (msg.meta.stream_flags) {
    case RpcMeta::kStreamData: {
      auto* chunk = new IOBuf(std::move(msg.payload));
      const int64_t bytes = static_cast<int64_t>(chunk->size());
      // Submit under the meta lock so a concurrent StreamClose (version
      // bump + queue stop under the same lock) can't recycle the slot
      // between our validation and the enqueue.  The bytes are counted
      // unread before the consumer can see the chunk, so the count never
      // reads low.
      m->lock();
      bool ok = m->version.load(std::memory_order_relaxed) ==
                    static_cast<uint32_t>(msg.meta.stream_id >> 32) &&
                m->consume_q != nullptr;
      if (ok) {
        const int64_t unread =
            m->unread.fetch_add(bytes, std::memory_order_acq_rel) + bytes;
        if (unread > m->unread_high_water.load(std::memory_order_relaxed)) {
          m->unread_high_water.store(unread, std::memory_order_relaxed);
          g_vars.unread_high_water << unread;
        }
        ok = m->consume_q->execute(chunk) == 0;
        if (!ok) {
          m->unread.fetch_sub(bytes, std::memory_order_acq_rel);
        }
      }
      m->unlock();
      if (!ok) {
        delete chunk;
      }
      break;
    }
    case RpcMeta::kStreamAck:
      m->send_window.fetch_add(static_cast<int64_t>(msg.meta.ack_bytes),
                               std::memory_order_acq_rel);
      m->window_ev.value.fetch_add(1, std::memory_order_release);
      m->window_ev.wake_all();
      break;
    case RpcMeta::kStreamClose:
      // Ordered close: deliver queued data first via the sentinel.
      close_in_order(m, msg.meta.stream_id);
      break;
    default:
      break;
  }
}

void stream_on_chunk_lost(uint64_t stream_id) {
  StreamMeta* m = stream_of(stream_id);
  if (m == nullptr) {
    return;
  }
  // The writer is told (its next write fails with EPIPE), and this end
  // closes behind the chunks that did arrive: its reader drains them and
  // then reads the close, never the chunk after the lost one.
  send_close(m);
  close_in_order(m, stream_id);
}

void stream_on_accept_response(uint64_t local_sid, uint64_t peer_sid,
                               uint64_t socket_id, uint64_t peer_window) {
  StreamMeta* m = stream_of(local_sid);
  if (m == nullptr) {
    return;
  }
  m->sock = socket_id;
  m->peer_sid.store(peer_sid, std::memory_order_release);
  m->send_window.store(static_cast<int64_t>(peer_window),
                       std::memory_order_release);
  m->established_ev.value.store(1, std::memory_order_release);
  m->established_ev.wake_all();
  bind_socket(socket_id, local_sid);
}

uint64_t stream_recv_window(StreamId id) {
  StreamMeta* m = stream_of(id);
  return m != nullptr ? static_cast<uint64_t>(m->opts.window_bytes) : 0;
}

uint64_t stream_unread_high_water(StreamId id) {
  StreamMeta* m = stream_of(id);
  return m != nullptr ? static_cast<uint64_t>(m->unread_high_water.load(
                            std::memory_order_acquire))
                      : 0;
}

uint64_t stream_send_window(StreamId id) {
  StreamMeta* m = stream_of(id);
  if (m == nullptr) {
    return 0;
  }
  const int64_t w = m->send_window.load(std::memory_order_acquire);
  return w > 0 ? static_cast<uint64_t>(w) : 0;
}

void stream_on_connection_failed(uint64_t socket_id) {
  // Snapshot-then-close: mark_closed runs user on_closed callbacks, which
  // may call StreamClose (unbind takes the same mutex) — never hold the
  // registry lock across them.
  std::vector<StreamId> victims;
  {
    std::lock_guard<std::mutex> g(by_socket_mu());
    auto range = by_socket().equal_range(socket_id);
    for (auto it = range.first; it != range.second; ++it) {
      victims.push_back(it->second);
    }
    by_socket().erase(socket_id);
  }
  for (StreamId sid : victims) {
    StreamMeta* m = stream_of(sid);
    if (m == nullptr) {
      continue;
    }
    close_in_order(m, sid);
  }
}

}  // namespace trpc
