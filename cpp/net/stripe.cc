#include "net/stripe.h"

#include <errno.h>
#include <unistd.h>

#include <algorithm>
#include <atomic>
#include <memory>
#include <mutex>
#include <unordered_map>
#include <utility>

#include "base/compress.h"
#include "base/flags.h"
#include "base/logging.h"
#include "base/rand.h"
#include "base/time.h"
#include "fiber/fiber.h"
#include "net/hotpath_stats.h"
#include "net/socket.h"

namespace trpc {

namespace {

// Landing buffers are single contiguous blocks, so a stripe total must
// fit a Block's 32-bit length; bodies at/above this fall back to the
// single-frame path (still correct, just unstriped).
constexpr uint64_t kMaxStripeTotal = 3ull << 30;
// Global bound on bytes parked in incomplete reassemblies: a flood of
// heads with huge totals must exhaust the map, not the heap.
constexpr uint64_t kPendingCapBytes = 8ull << 30;

int64_t flag_value(Flag* f, int64_t dflt) {
  return f != nullptr ? f->int64_value() : dflt;
}

Flag* int_flag(const char* name, int64_t dflt, const char* desc,
               int64_t lo, int64_t hi) {
  Flag* f = Flag::define_int64(name, dflt, desc);
  if (f != nullptr) {
    // Range validator + introspectable bounds in one declaration (the
    // tuner and /flags?format=json read them back).
    f->set_int_range(lo, hi);
  }
  return f;
}

Flag* threshold_flag() {
  static Flag* f = int_flag(
      "trpc_stripe_threshold", 2ll << 20,
      "payloads above this many bytes are striped into concurrent chunk "
      "frames (0 disables striping)",
      0, static_cast<int64_t>(kMaxStripeTotal));
  return f;
}

Flag* chunk_flag() {
  static Flag* f = int_flag(
      "trpc_stripe_chunk_bytes", 2ll << 20,
      "stripe chunk size in bytes (per-frame unit of the multi-rail "
      "large-message path)",
      64 << 10, 64 << 20);
  return f;
}

Flag* rails_flag() {
  static Flag* f = int_flag(
      "trpc_stripe_rails", 4,
      "connections a striped message spreads over (pooled channels; "
      "includes the primary)",
      1, 16);
  return f;
}

Flag* reassembly_timeout_flag() {
  static Flag* f = int_flag(
      "trpc_stripe_reassembly_timeout_ms", 30000,
      "incomplete stripe reassemblies older than this are dropped "
      "(whole-call failure surfaces via the RPC timeout)",
      100, 3600 * 1000);
  return f;
}

// ---- reassembly map ------------------------------------------------------

struct StripeEntry {
  uint64_t id = 0;
  uint64_t total = 0;
  char* dest = nullptr;   // landing base (block->data or caller buffer)
  Block* block = nullptr;  // arena landing block (null: caller-registered)
  bool caller_buf = false;
  SocketId head_socket = 0;
  int64_t created_us = 0;
  std::mutex mu;  // head/rails/dispatch bookkeeping (chunk-rate, not hot)
  bool have_head = false;
  bool dispatched = false;
  RpcMeta head_meta;
  std::vector<SocketId> rails;
  // Admitted chunk spans, kept sorted and verified DISJOINT: chunks are
  // admitted only if they overlap nothing already accepted, so admitted
  // spans summing to `total` within [0, total) is a proof of exact
  // cover — landed == total can then never dispatch a payload with
  // unwritten gaps (duplicate offsets from a buggy/hostile peer are
  // dropped instead of double-counted).  Guarded by mu.
  std::vector<std::pair<uint64_t, uint64_t>> spans;  // (offset, end)
  std::atomic<uint64_t> landed{0};
  // Landers currently able to touch `dest`; incremented under the map
  // mutex BEFORE the landing fiber is spawned, so an unregistering
  // caller that removed the entry and then observed landers == 0 knows
  // no copy into its buffer can ever start again.
  std::atomic<int> landers{0};
  std::atomic<bool> abandoned{false};

  ~StripeEntry() {
    if (block != nullptr) {
      block->release();
    }
  }
};

struct LandingReg {
  void* buf = nullptr;
  size_t cap = 0;
  std::shared_ptr<StripeEntry> entry;  // bound when chunks start landing
};

std::mutex& map_mu() {
  static std::mutex* mu = new std::mutex();
  return *mu;
}
std::unordered_map<uint64_t, std::shared_ptr<StripeEntry>>& entries() {
  static auto* m =
      new std::unordered_map<uint64_t, std::shared_ptr<StripeEntry>>();
  return *m;
}
std::unordered_map<uint64_t, LandingReg>& landings() {
  static auto* m = new std::unordered_map<uint64_t, LandingReg>();
  return *m;
}
std::atomic<uint64_t> g_pending_bytes{0};
std::atomic<int64_t> g_last_gc_us{0};

// Eager flag definitions: settable via /flags (and trpc_flag_set) before
// the first striped message would lazily create them.
[[maybe_unused]] Flag* const g_stripe_flags_eager[] = {
    threshold_flag(), chunk_flag(), rails_flag(), reassembly_timeout_flag()};

void maybe_gc() {
  const int64_t now = monotonic_time_us();
  // Relaxed load + CAS: the stamp only rate-limits GC claims; the map
  // itself is read under map_mu(), so no data rides this word.
  int64_t last = g_last_gc_us.load(std::memory_order_relaxed);
  if (now - last < 1000 * 1000 ||
      !g_last_gc_us.compare_exchange_strong(last, now,
                                            std::memory_order_relaxed)) {
    return;
  }
  stripe_gc(now);
}

// Finds-or-creates the entry for id and ADMITS one chunk: validates
// bounds, records the arrival rail, and counts the lander in — all in
// ONE map-mutex critical section.  The lander count must rise under the
// same lock that stripe_unregister_landing abandons entries under, or an
// unregistering caller could observe zero landers (buffer "quiescent"),
// recycle the buffer, and then have this chunk's copy land in it.
// nullptr when the chunk is unacceptable (over caps, total mismatch,
// bad bounds) — it is dropped and the call times out whole.
std::shared_ptr<StripeEntry> admit_chunk(uint64_t id, uint64_t total,
                                         uint64_t offset, uint64_t len,
                                         SocketId from) {
  if (id == 0 || total == 0 || total >= kMaxStripeTotal || len == 0 ||
      offset + len > total || offset + len < offset) {
    return nullptr;
  }
  std::lock_guard<std::mutex> g(map_mu());
  std::shared_ptr<StripeEntry> e;
  auto it = entries().find(id);
  if (it != entries().end()) {
    if (it->second->total != total) {
      return nullptr;  // id collision / corrupted peer: drop
    }
    e = it->second;
  } else {
    if (g_pending_bytes.load(std::memory_order_relaxed) + total >
        kPendingCapBytes) {
      return nullptr;  // reassembly arena over budget: shed, don't OOM
    }
    e = std::make_shared<StripeEntry>();
    e->id = id;
    e->total = total;
    e->created_us = monotonic_time_us();
    auto reg = landings().find(id);
    if (reg != landings().end() && reg->second.cap >= total) {
      // Caller-registered landing (batch plane): chunks memcpy straight
      // into the caller's buffer — no arena bounce, no boundary copy.
      e->dest = static_cast<char*>(reg->second.buf);
      e->caller_buf = true;
      reg->second.entry = e;
    } else {
      e->block = HostArena::instance()->allocate(
          static_cast<uint32_t>(total));
      e->block->size = static_cast<uint32_t>(total);
      e->dest = e->block->data;
    }
    // Relaxed: pure accounting var (stripe_pending_bytes) — readers
    // tolerate transient skew, no ordering needed.
    g_pending_bytes.fetch_add(total, std::memory_order_relaxed);
    entries().emplace(id, e);
  }
  {
    std::lock_guard<std::mutex> eg(e->mu);
    // Disjointness check: sorted insert, reject any overlap with an
    // already-admitted span (see the `spans` member comment).
    auto pos = std::lower_bound(
        e->spans.begin(), e->spans.end(),
        std::make_pair(offset, offset + len));
    if ((pos != e->spans.end() && pos->first < offset + len) ||
        (pos != e->spans.begin() && std::prev(pos)->second > offset)) {
      return nullptr;  // duplicate/overlapping chunk: drop it
    }
    e->spans.insert(pos, {offset, offset + len});
    bool seen = false;
    for (SocketId r : e->rails) {
      if (r == from) {
        seen = true;
        break;
      }
    }
    if (!seen) {
      e->rails.push_back(from);
    }
  }
  e->landers.fetch_add(1, std::memory_order_acq_rel);
  return e;
}

void drop_entry_locked(const std::shared_ptr<StripeEntry>& e) {
  // Relaxed: accounting only (see the fetch_add at entry creation).
  g_pending_bytes.fetch_sub(e->total, std::memory_order_relaxed);
  entries().erase(e->id);
}

void noop_deleter(void*, void*) {}

// Dispatches the fully landed message through the tstd protocol hooks
// (runs on the finishing lander's worker fiber — the same place a
// per-message dispatch fiber would have run).
void dispatch_entry(const std::shared_ptr<StripeEntry>& e) {
  hotpath_vars().stripe_reassembled << 1;
  InputMessage m;
  {
    std::lock_guard<std::mutex> g(e->mu);
    m.meta = std::move(e->head_meta);
    if (m.meta.type == RpcMeta::kRequest) {
      auto arrival = std::make_shared<StripeArrival>();
      arrival->rails = e->rails;
      m.ctx = std::move(arrival);
      // A striped request is whole when its last chunk has landed
      // (InputMessage::arrival_us).
      m.arrival_us = monotonic_time_us();
    }
  }
  // Per-chunk CRCs were verified frame-by-frame at parse; the head's CRC
  // covered only chunk 0, so it must not masquerade as a whole-body one.
  m.meta.checksum = 0;
  m.socket = e->head_socket;
  if (e->caller_buf) {
    m.payload.append_user_data(e->dest, e->total, &noop_deleter);
  } else {
    m.payload.append_block(e->block, 0, static_cast<uint32_t>(e->total));
    e->block = nullptr;  // reference consumed by the payload
  }
  const Protocol& p = tstd_protocol();
  if (m.meta.type == RpcMeta::kResponse) {
    p.process_response(std::move(m));
  } else {
    p.process_request(std::move(m));
  }
}

// Checks completion and dispatches exactly once.
void maybe_finalize(const std::shared_ptr<StripeEntry>& e) {
  if (e->landed.load(std::memory_order_acquire) != e->total) {
    return;
  }
  {
    std::lock_guard<std::mutex> g(e->mu);
    // Acquire on abandoned: pairs with the GC's release store so a
    // dispatch racing expiry never delivers a half-reclaimed entry.
    if (!e->have_head || e->dispatched ||
        e->abandoned.load(std::memory_order_acquire)) {
      return;
    }
    e->dispatched = true;
  }
  {
    std::lock_guard<std::mutex> g(map_mu());
    drop_entry_locked(e);
  }
  dispatch_entry(e);
}

struct LandJob {
  std::shared_ptr<StripeEntry> entry;
  IOBuf data;
  uint64_t offset = 0;
};

void land_job_run(LandJob* j) {
  const std::shared_ptr<StripeEntry>& e = j->entry;
  const uint64_t n = j->data.size();
  // Acquire: a lander observing the GC's abandoned release-store must
  // also see the entry's landing block already detached — copying into
  // e->dest after reclaim would scribble freed arena memory.
  if (!e->abandoned.load(std::memory_order_acquire)) {
    j->data.copy_to(e->dest + j->offset, n);
  }
  j->data.clear();  // release parse-buffer blocks before the dispatch
  const uint64_t landed =
      e->landed.fetch_add(n, std::memory_order_acq_rel) + n;
  // The lander count gates buffer reuse (stripe_unregister_landing):
  // drop it BEFORE finalize, whose dispatch path may park this fiber in
  // a fid lock held by a concurrent timeout completion that is itself
  // waiting for landers to drain.
  e->landers.fetch_sub(1, std::memory_order_release);
  if (landed == e->total) {
    maybe_finalize(e);
  }
}

void land_job_fiber(void* arg) {
  auto* j = static_cast<LandJob*>(arg);
  land_job_run(j);
  delete j;
}

// Queues one chunk's landing memcpy on a worker fiber (inline fallback
// when the pool is exhausted).  Caller must have incremented
// entry->landers under the map mutex.
void enqueue_land(std::shared_ptr<StripeEntry> e, IOBuf&& data,
                  uint64_t offset) {
  auto* j = new LandJob{std::move(e), std::move(data), offset};
  if (fiber_start(nullptr, land_job_fiber, j, 0) != 0) {
    land_job_run(j);
    delete j;
  }
}

}  // namespace

bool stripe_eligible(uint64_t n) {
  const int64_t thr = flag_value(threshold_flag(), 0);
  return thr > 0 && n > static_cast<uint64_t>(thr) && n < kMaxStripeTotal;
}

uint64_t stripe_chunk_bytes() {
  return static_cast<uint64_t>(flag_value(chunk_flag(), 2 << 20));
}

int stripe_rails() {
  return static_cast<int>(flag_value(rails_flag(), 4));
}

uint64_t stripe_make_id() {
  uint64_t id;
  do {
    id = fast_rand();
  } while (id == 0);
  return id;
}

bool stripe_should(SocketId primary, uint64_t stream_id,
                   uint64_t body_bytes) {
  // Stripes ride several rails and reassemble in whatever order they
  // land: nothing of a stream, whose frames keep the connection's order,
  // is ever striped (a wide stream chunk goes one-sided, net/rma.h, or in
  // band).
  if (stream_id != 0 || !stripe_eligible(body_bytes)) {
    return false;
  }
  SocketRef s(Socket::Address(primary));
  return s && s->mode() != SocketMode::kIci;
}

int stripe_frame_send(SocketId primary, RpcMeta&& meta, IOBuf&& body) {
  if (meta.has_checksum) {
    meta.checksum = crc32c(body);
  }
  IOBuf frame;
  tstd_pack(&frame, meta, body);
  SocketRef s(Socket::Address(primary));
  return s && s->Write(std::move(frame)) == 0 ? 0 : -1;
}

int stripe_send(SocketId primary, const std::vector<SocketId>& rails,
                RpcMeta&& meta, IOBuf&& body, uint64_t stripe_id,
                const DeadlineToken& tok) {
  const uint64_t total = body.size();
  const uint64_t chunk =
      std::max<uint64_t>(64 << 10, stripe_chunk_bytes());
  meta.stripe_id = stripe_id;
  meta.stripe_offset = 0;
  meta.stripe_total = total;
  IOBuf first;
  body.cutn(&first, chunk);
  if (meta.has_checksum) {
    meta.checksum = crc32c(first);  // head CRC covers chunk 0 only
  }
  uint64_t nchunks = 1;
  {
    // Head rides the primary so the call's own connection sees it in
    // the position a single-frame message would have held.
    IOBuf frame;
    tstd_pack(&frame, meta, first);
    SocketRef p(Socket::Address(primary));
    if (!p || p->Write(std::move(frame)) != 0) {
      return -1;
    }
  }
  uint64_t off = chunk;
  size_t rail_i = 0;
  while (!body.empty()) {
    if (tok.aborted()) {
      // Cascading cancel / expired budget: stop cutting within one
      // chunk.  The receiver's partial reassembly never dispatches and
      // expires whole after trpc_stripe_reassembly_timeout_ms.
      deadline_vars().cancel_saved_bytes
          << static_cast<int64_t>(body.size());
      return -1;
    }
    IOBuf piece;
    body.cutn(&piece, chunk);
    RpcMeta cm;
    cm.type = RpcMeta::kStripe;
    cm.stripe_id = stripe_id;
    cm.stripe_offset = off;
    cm.stripe_total = total;
    off += piece.size();
    if (meta.has_checksum) {
      cm.has_checksum = true;
      cm.checksum = crc32c(piece);
    }
    ++nchunks;
    const SocketId rid =
        rails.empty() ? primary : rails[rail_i++ % rails.size()];
    bool sent = false;
    if (rid != 0) {
      // tstd_pack shares `piece`'s blocks by reference, so a failed rail
      // write leaves the chunk intact for the primary retry below.
      IOBuf frame;
      tstd_pack(&frame, cm, piece);
      SocketRef r(Socket::Address(rid));
      sent = r && r->Write(std::move(frame)) == 0;
    }
    if (!sent) {
      if (rid == primary) {
        return -1;
      }
      IOBuf frame;
      tstd_pack(&frame, cm, piece);
      SocketRef p(Socket::Address(primary));
      if (!p || p->Write(std::move(frame)) != 0) {
        return -1;  // primary gone: the whole call fails, cleanly
      }
    }
  }
  hotpath_vars().stripe_tx_chunks << static_cast<int64_t>(nchunks);
  hotpath_vars().stripe_tx_bytes << static_cast<int64_t>(total);
  return 0;
}

void stripe_on_head(InputMessage&& msg) {
  maybe_gc();
  hotpath_vars().stripe_rx_chunks << 1;
  hotpath_vars().stripe_rx_bytes
      << static_cast<int64_t>(msg.payload.size());
  const uint64_t id = msg.meta.stripe_id;
  const uint64_t total = msg.meta.stripe_total;
  const uint64_t off = msg.meta.stripe_offset;
  const uint64_t len = msg.payload.size();
  std::shared_ptr<StripeEntry> e =
      admit_chunk(id, total, off, len, msg.socket);
  if (e == nullptr) {
    LOG(Warning) << "stripe head dropped (id=" << id << " total=" << total
                 << " len=" << len << ")";
    return;
  }
  {
    std::lock_guard<std::mutex> g(e->mu);
    e->have_head = true;
    e->head_meta = std::move(msg.meta);
    e->head_socket = msg.socket;
  }
  enqueue_land(std::move(e), std::move(msg.payload), off);
}

void stripe_on_chunk(InputMessage&& msg) {
  maybe_gc();
  hotpath_vars().stripe_rx_chunks << 1;
  hotpath_vars().stripe_rx_bytes
      << static_cast<int64_t>(msg.payload.size());
  const uint64_t off = msg.meta.stripe_offset;
  std::shared_ptr<StripeEntry> e =
      admit_chunk(msg.meta.stripe_id, msg.meta.stripe_total, off,
                  msg.payload.size(), msg.socket);
  if (e == nullptr) {
    return;  // expired/foreign stripe: drop; the call times out whole
  }
  enqueue_land(std::move(e), std::move(msg.payload), off);
}

void stripe_register_landing(uint64_t cid, void* buf, size_t cap) {
  {
    std::lock_guard<std::mutex> g(map_mu());
    landings()[cid] = LandingReg{buf, cap, nullptr};
  }
}

void stripe_unregister_landing(uint64_t cid) {
  std::shared_ptr<StripeEntry> e;
  {
    std::lock_guard<std::mutex> g(map_mu());
    auto it = landings().find(cid);
    if (it == landings().end()) {
      return;
    }
    e = std::move(it->second.entry);
    landings().erase(it);
    if (e != nullptr && entries().count(e->id) != 0) {
      // Incomplete reassembly into the caller's buffer: orphan it so a
      // late chunk re-creates an arena-backed entry instead.
      e->abandoned.store(true, std::memory_order_release);
      drop_entry_locked(e);
    }
  }
  if (e == nullptr || !e->caller_buf) {
    return;
  }
  // The buffer may be recycled the moment we return: wait out any lander
  // already counted in (bounded by one chunk memcpy each).
  while (e->landers.load(std::memory_order_acquire) != 0) {
    if (in_fiber()) {
      fiber_sleep_us(50);
    } else {
      usleep(50);
    }
  }
}

void stripe_gc(int64_t now_us) {
  const int64_t timeout_us =
      flag_value(reassembly_timeout_flag(), 30000) * 1000;
  std::vector<std::shared_ptr<StripeEntry>> dead;
  {
    std::lock_guard<std::mutex> g(map_mu());
    auto& m = entries();
    for (auto it = m.begin(); it != m.end();) {
      StripeEntry& e = *it->second;
      // Acquire/release on abandoned: the release store publishes the
      // expiry decision to landers (land_job_run's acquire); relaxed on
      // the byte counter — accounting only.
      if (e.abandoned.load(std::memory_order_acquire) ||
          now_us - e.created_us > timeout_us) {
        e.abandoned.store(true, std::memory_order_release);
        g_pending_bytes.fetch_sub(e.total, std::memory_order_relaxed);
        dead.push_back(it->second);
        it = m.erase(it);
      } else {
        ++it;
      }
    }
  }
  if (!dead.empty()) {
    hotpath_vars().stripe_expired << static_cast<int64_t>(dead.size());
  }
}

size_t stripe_pending_reassemblies() {
  std::lock_guard<std::mutex> g(map_mu());
  return entries().size();
}

}  // namespace trpc
