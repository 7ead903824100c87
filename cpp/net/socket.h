// Socket — fd + lifecycle + wait-free write queue behind a versioned handle.
//
// Parity: brpc::Socket (/root/reference/src/brpc/socket.h:498-509 SetFailed/
// Address wait-free strong refs; socket.cpp:1624-1890 the MPSC write path
// with the KeepWrite continuation; socket.cpp:2254 input-event dedup).
// Re-designed: version+refcount packed in one atomic64; the write queue is a
// Treiber/flag MPSC (ExecutionQueue-style) instead of the reference's
// exchanged linked list; the first write is attempted inline, leftovers
// continue in a KeepWrite fiber parked on the writable-edge Event.
#pragma once

#include <atomic>
#include <functional>
#include <memory>

#include "base/endpoint.h"
#include "base/iobuf.h"
#include "base/resource_pool.h"
#include "fiber/event.h"
#include "net/transport.h"

namespace trpc {

using SocketId = uint64_t;  // version<<32 | pool slot

class Socket {
 public:
  struct Options {
    int fd = -1;                     // accepted/listen fd, or -1 to connect
    EndPoint remote;
    SocketMode mode = SocketMode::kTcp;
    // Fiber-spawned on each readable edge (versioned id passed through).
    void (*on_readable)(SocketId id, void* ctx) = nullptr;
    void* ctx = nullptr;
    // Owner context (Server*/Channel*); set BEFORE the fd is registered
    // with the dispatcher so the first event can never observe null.
    void* user_data = nullptr;
    // Non-TCP transports (shm rings, ICI): the transport instance and its
    // per-connection context.  The holder keeps the context (e.g. a mapped
    // segment) alive exactly as long as the socket generation.
    Transport* transport = nullptr;
    std::shared_ptr<void> transport_ctx_holder;
    // Worker group for this connection's fibers (read fiber, and via
    // inheritance the handler/KeepWrite fibers).  Server.h:280 bthread_tag
    // parity: a server pins its connections to its tag's worker group.
    uint8_t worker_tag = 0;
  };

  // Creates a socket with one owner reference; registers with the
  // dispatcher when fd >= 0.  Returns 0 and the versioned id.
  static int Create(const Options& opts, SocketId* out);
  // Wait-free strong ref; nullptr if the id is stale or failed.
  static Socket* Address(SocketId id);
  void Dereference();
  // True while a failed socket of this id's generation still has strong
  // references draining (holders may still be inside request entry paths).
  static bool Draining(SocketId id);

  // Marks failed: future Address() fails, fd closed once refs drain, the
  // owner reference is dropped, waiters woken.
  void SetFailed(int err);
  // Single-slot observer invoked once per socket failure (from whatever
  // thread called SetFailed), with the PRE-failure id — the generation
  // holders stored before the version bump invalidated it.  The stream
  // plane registers here so logical streams bound to a dead connection
  // close promptly instead of waiting out a write probe (net/stream.cc).
  // The callback must not park and must tolerate ids it never saw.
  static void set_failure_observer(void (*cb)(SocketId id));
  // Acquire on both state bits: an observer acting on failed/connected
  // (e.g. skipping ensure_connected) must also see the writes SetFailed
  // or the connect path published before flipping them.
  bool Failed() const {
    return failed_.load(std::memory_order_acquire);
  }
  bool connected() const {
    // Acquire: see Failed() — same publication pairing.
    return connected_.load(std::memory_order_acquire);
  }

  // Appends data to the wait-free write queue; the queue guarantees FIFO
  // per socket and writes happen in a KeepWrite fiber (first try inline).
  // Returns 0 if queued/sent, -1 if the socket is failed.
  // close_after (Connection: close semantics) rides the write NODE — the
  // socket fails itself only after this write (and everything queued with
  // it) has flushed, so a racing drain of earlier responses can never
  // close before this one leaves.
  int Write(IOBuf&& data, bool close_after = false);

  // Text table of every live socket (/sockets builtin; reference:
  // builtin/sockets_service.cpp printing Socket::DebugString).
  static std::string DumpAll(size_t max_rows);
  // One line per live socket of hot-path state (queued-write flag, writer
  // role, pending input events) — wedge forensics, atomics only.
  static std::string DumpHotState();

  int fd() const { return fd_; }
  SocketMode mode() const { return mode_; }
  SocketId id() const;
  const EndPoint& remote() const { return remote_; }
  Transport* transport() const { return transport_; }
  // True where the two ends of this connection read one CLOCK_MONOTONIC,
  // from what the connection itself shows: it is the shm ring (one host
  // by construction), a unix socket, or a tcp connection whose peer
  // address is this host's own (loopback, or the address this end is
  // bound to).  Decided once per connected generation.  What lets the
  // caller cut a call's wire time into its two legs (net/wire_split.h).
  bool peer_shares_clock();
  IOBuf& read_buf() { return read_buf_; }
  // Protocol index pinned after first successful parse (-1 = unknown).
  int pinned_protocol = -1;
  // Set once the server verified this connection's kAuth credential
  // (auth.h); requests on unverified sockets are rejected when the
  // server has an authenticator installed.
  std::atomic<bool> auth_ok{false};
  void* user_data = nullptr;  // Server*/Channel* context, set by owner
  void* transport_ctx = nullptr;  // per-connection transport state
  uint8_t worker_tag = 0;  // worker group for this connection's fibers
  // Protocol-probe memo: buffer length at the last inconclusive probe
  // sweep (every protocol said NotEnoughData/TryOther).  The messenger
  // skips re-probing until more bytes than this have arrived — a partial
  // prefix no longer pays a full multi-protocol probe per read event.
  // 0 = no stalled probe.  Read-fiber-owned; reset with the socket.
  size_t probe_stall_len = 0;
  // Bulk-read hint: bytes the current (partially buffered) frame still
  // needs, published by the parser on NotEnoughData.  The messenger and
  // transport size their next reads/blocks from it, turning a 64MB body
  // into a few large-iovec readvs instead of thousands of 8KB ones.
  // 0 = no known remainder.  Read-fiber-owned; reset with the socket.
  size_t read_block_hint = 0;
  // Incremental parser state for protocols that need it (HTTP chunked
  // bodies resume scanning; h2 connection state).  Owned by the read
  // fiber; cleared on socket reuse.  `parse_state_owner` tags WHICH
  // protocol the state belongs to (a unique static address per protocol):
  // during protocol probing several parsers see the same socket, and one
  // that consumed a prefix (h2's preface) must reclaim its state on the
  // next round instead of misreading another protocol's.
  std::shared_ptr<void> parse_state;
  const void* parse_state_owner = nullptr;

  // -- dispatcher integration (internal) -------------------------------
  static void destroy_write_node_opaque(void* n);  // TLS cache teardown
  void on_input_event();    // readable edge (any thread)
  void on_output_event();   // writable edge (any thread)
  int wait_writable(uint32_t snap, int64_t deadline_us);
  uint32_t writable_snap() const {
    return const_cast<Event&>(wr_ev_).value.load(std::memory_order_acquire);
  }
  int ensure_connected();   // lazy non-blocking connect (parks fiber)

 private:
  friend class ResourcePool<Socket>;
  struct WriteNode {
    IOBuf data;
    bool close_after = false;
    WriteNode* next = nullptr;
  };

  static void read_fiber_thunk(void* arg);
  static void keep_write_thunk(void* arg);
  void keep_write();
  // Inline fast path: called by Write with the writer role held.  Returns
  // true when the queue fully flushed (or the socket failed) and the role
  // is done with; false when bytes remain and a KeepWrite fiber must take
  // over (role stays held).
  bool try_inline_write();
  // Moves the whole MPSC chain (reversed to FIFO) into pending_; returns
  // the node count absorbed.  Writer-role holder only.
  size_t drain_queue_into_pending();
  // Releases the writer role with the seq_cst handoff that closes the
  // producer/exit Dekker race; returns false when new nodes arrived and
  // the role was re-acquired (caller must keep draining).
  bool release_writer_role();
  // Failure/teardown of an active writer: fail the socket, purge pending_
  // and the queue.  The writer role is intentionally left held — the
  // socket is dead, reset_for_reuse re-arms the flag.
  void abort_writer(int err);
  void reset_for_reuse(const Options& opts);
  void drop_write_queue();
  // TLS-cached WriteNode alloc/free (one node per Write on the hot path;
  // pooling also retains the inner IOBuf's refs vector capacity).
  static WriteNode* alloc_write_node(IOBuf&& data, bool close_after);
  static void free_write_node(WriteNode* n);

  std::atomic<uint64_t> ref_ver_{0};  // version<<32 | refcount
  std::atomic<uint32_t> slot_{0};
  int fd_ = -1;
  SocketMode mode_ = SocketMode::kTcp;
  EndPoint remote_;
  Transport* transport_ = nullptr;
  std::atomic<bool> failed_{false};
  std::atomic<bool> connected_{false};
  // peer_shares_clock's memo: -1 undecided, else the answer.  Relaxed:
  // every reader that finds -1 computes the same value.
  std::atomic<int8_t> peer_clock_{-1};
  std::atomic<int> nevent_{0};
  void (*on_readable_)(SocketId, void*) = nullptr;
  void* ctx_ = nullptr;
  IOBuf read_buf_;
  std::shared_ptr<void> transport_ctx_holder_;
  Event wr_ev_;  // writable-edge counter
  // MPSC write queue.
  std::atomic<WriteNode*> wq_head_{nullptr};
  std::atomic<bool> writing_{false};
  // Coalesced unwritten bytes + deferred close flag, owned by whoever
  // holds the writer role (writing_): the inline fast path hands both to
  // the KeepWrite fiber through here on EAGAIN.
  IOBuf pending_;
  bool pending_close_ = false;
};

void make_nonblocking(int fd);

// RAII strong reference.
class SocketRef {
 public:
  SocketRef() = default;
  explicit SocketRef(Socket* s) : s_(s) {}
  SocketRef(SocketRef&& o) noexcept : s_(o.s_) { o.s_ = nullptr; }
  ~SocketRef() {
    if (s_ != nullptr) {
      s_->Dereference();
    }
  }
  Socket* operator->() const { return s_; }
  Socket* get() const { return s_; }
  explicit operator bool() const { return s_ != nullptr; }

 private:
  Socket* s_ = nullptr;
};

}  // namespace trpc
