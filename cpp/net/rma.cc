#include "net/rma.h"

#include <errno.h>
#include <fcntl.h>
#include <string.h>
#include <sys/mman.h>
#include <sys/stat.h>
#include <unistd.h>

#include <algorithm>
#include <functional>
#include <memory>
#include <unordered_map>
#include <vector>

#include "base/compress.h"
#include "base/flags.h"
#include "base/logging.h"
#include "base/time.h"
#include "fiber/event.h"
#include "fiber/fiber.h"
#include "net/fault.h"
#include "net/hotpath_stats.h"
#include "net/ici_transport.h"
#include "net/socket.h"
#include "net/stripe.h"
#include "stat/reducer.h"
#include "stat/timeline.h"

namespace trpc {

namespace {

constexpr uint64_t kRmaMagic = 0x545250524d413154ull;  // "TRPRMA1T"
// Region layout: [RmaSegHdr, padded to kRmaDataOffset][data area].
// Window spans reserve kRmaSpanHdr at their start for the transfer
// header; direct (caller-buffer) transfers use the RmaSegHdr's embedded
// header so the payload can land at data offset 0.
constexpr uint32_t kRmaDataOffset = 8192;
constexpr uint32_t kRmaSpanHdr = 8192;
constexpr uint32_t kRmaMaxChunks = 1024;
constexpr uint32_t kRmaBitWords = kRmaMaxChunks / 64;
// Window slots fit ONE bitmap word: span allocation is a single CAS and
// a span is always a contiguous run of ≤ 64 slots.
constexpr uint32_t kRmaWindowSlots = 64;
constexpr uint32_t kXferCrcPresent = 1u << 0;

// One transfer's completion state, shared memory.  The sender writes
// the scalar fields before any chunk, sets chunk_bits with release as
// each chunk's bytes land, and the receiver admits the payload only
// when every bit reads set (acquire) — the control frame alone never
// proves the bytes arrived (a faulted chunk leaves its bit clear).
struct RmaXfer {
  // Release/acquire: `total` is the sender's first store and doubles as
  // the header-initialized marker for the direct path.
  std::atomic<uint64_t> total;
  // The transfer's correlation id, stamped at init and matched at
  // resolve: a LATE put from a timed-out call that re-initializes a
  // reused direct landing region after the live call's init makes the
  // live resolve reject (clean whole-call failure) instead of admitting
  // interleaved bytes.  (A stale writer racing mid-flight is inherent to
  // shared memory — see the reuse contract in rma.h/RmaBuffer.)
  uint64_t token;
  uint32_t chunk_bytes;
  uint32_t nchunks;
  uint32_t flags;  // kXferCrcPresent: chunk_crc[] carries per-chunk crc32c
  uint32_t pad;
  // Release per set bit (pairs with the receiver's acquire scan): a set
  // bit publishes that chunk's payload bytes.
  std::atomic<uint64_t> chunk_bits[kRmaBitWords];
  uint32_t chunk_crc[kRmaMaxChunks];
};
static_assert(sizeof(RmaXfer) <= kRmaSpanHdr, "span header overflow");

struct RmaSegHdr {
  uint64_t magic;
  uint32_t data_off;
  uint32_t nslots;  // 0: plain region (no window allocator)
  uint64_t data_len;
  uint32_t slot_bytes;
  uint32_t reserved;
  // Window slot bitmap, shared: the PEER allocates spans (CAS set,
  // acquire — a freed slot's payload reads must not be reordered before
  // the claim), the owner frees them (fetch_and clear, release — the
  // consumer finished reading before the slot recycles).
  std::atomic<uint64_t> slot_map;
  // Direct-to-region transfers (caller landing buffers) complete here.
  RmaXfer direct;
};
static_assert(sizeof(RmaSegHdr) <= kRmaDataOffset, "region header overflow");

int64_t flag_value(Flag* f, int64_t dflt) {
  return f != nullptr ? f->int64_value() : dflt;
}

Flag* int_flag(const char* name, int64_t dflt, const char* desc, int64_t lo,
               int64_t hi) {
  Flag* f = Flag::define_int64(name, dflt, desc);
  if (f != nullptr) {
    // Range validator + introspectable bounds in one declaration (the
    // tuner and /flags?format=json read them back).
    f->set_int_range(lo, hi);
  }
  return f;
}

Flag* window_flag() {
  static Flag* f = [] {
    Flag* flag = Flag::define_int64(
        "trpc_rma_window_bytes", 256ll << 20,
        "per-connection one-sided receive window for NEW shm/ici "
        "connections (bytes, 0 disables the rma plane, else a power of "
        "two in [16MB, 4GB]; the largest one-sided transfer is the "
        "window minus one 4MB-granularity slot)");
    if (flag != nullptr) {
      flag->set_validator([](const std::string& v) {
        char* end = nullptr;
        const long long n = strtoll(v.c_str(), &end, 10);
        return end != v.c_str() && *end == '\0' &&
               (n == 0 || (n >= (16ll << 20) && n <= (4ll << 30) &&
                           (n & (n - 1)) == 0));
      });
      // Bounds hint only: the validator additionally requires 0 or a
      // power of two, so set_int_range would be too permissive.  The
      // tuner's window rule doubles within these bounds (preserving
      // power-of-two) and never touches a 0 (= disabled) window.
      flag->set_bounds_hint(16ll << 20, 4ll << 30);
    }
    return flag;
  }();
  return f;
}

Flag* shm_rails_flag() {
  static Flag* f = int_flag(
      "trpc_shm_rails", 4,
      "concurrent one-sided writer lanes for rma transfers over shm "
      "connections (parallel rail fibers, each owning a contiguous "
      "chunk range)",
      1, 16);
  return f;
}

Flag* ici_rails_flag() {
  static Flag* f = int_flag(
      "trpc_ici_rails", 4,
      "concurrent one-sided writer lanes for rma transfers over ici "
      "connections (parallel rail fibers, each owning a contiguous "
      "chunk range)",
      1, 16);
  return f;
}

Flag* scavenge_flag() {
  static Flag* f = int_flag(
      "trpc_rma_span_scavenge_ms", 10000,
      "age after which an allocated-but-never-admitted receive-window "
      "span is reclaimed (ms, [50, 600000]) — a dropped control frame "
      "(chaos, dying sender) otherwise leaks the slots until connection "
      "teardown, and group-transfer schedules hammer the window hard "
      "enough that the leak stops being theoretical; must exceed the "
      "slowest legitimate write+control latency",
      50, 600000);
  return f;
}

[[maybe_unused]] Flag* const g_rma_flags_eager[] = {
    window_flag(), shm_rails_flag(), ici_rails_flag(), scavenge_flag()};

// ---- registry ------------------------------------------------------------

// TRUSTED geometry snapshot of a region.  The live header lives in
// peer-writable shared memory, so every consumer works from a snapshot
// taken when WE created the region (registry) or validated the mapping
// (peer windows) — a peer scribbling its header afterwards can corrupt
// its own data plane but can never push our arithmetic out of bounds
// (slot_bytes=0 division, data_off past the mapping, ...).
struct RmaGeom {
  uint64_t data_len = 0;
  uint32_t slot_bytes = 0;
  uint32_t nslots = 0;  // 0: plain region
};

// Scavenger state for one receive window (owner side).  `admitted`
// marks slots whose span rma_resolve admitted and whose payload is
// still referenced — exempt from scavenging however old; per-slot
// first-seen stamps (guarded by reg_mu — only the scavenger pass reads
// or writes them) age everything else.
struct WindowScav {
  // Release on set (admit) / clear (last payload ref dropped) pairs
  // with the scavenger's acquire read: an admitted span is never aged.
  std::atomic<uint64_t> admitted{0};
  int64_t first_seen_us[kRmaWindowSlots] = {};
};

struct RegionRec {
  uint64_t rkey = 0;
  std::shared_ptr<RmaMapping> map;  // null for local pins (rma_reg)
  std::string name;                 // shm name for exportable regions
  const char* pin_base = nullptr;   // local pins: the pinned range
  size_t pin_len = 0;
  bool window = false;
  std::shared_ptr<WindowScav> scav;  // windows only
  // rma_free arrived while a landing bind (an in-flight call's resp_buf)
  // still referenced this region: the striped copy-path fallback holds
  // the raw data pointer, so the unmap defers until the last bind drops
  // (rma_landing_unbind) instead of pulling pages out from under a late
  // landing memcpy.
  bool free_pending = false;
  RmaGeom geom;
};

struct LandingBind {
  uint64_t rkey = 0;
  uint64_t cap = 0;
  uint64_t off = 0;  // landing offset inside the region's data area
};

std::mutex& reg_mu() {
  static std::mutex* mu = new std::mutex();
  return *mu;
}
std::vector<RegionRec>& regions() {
  static auto* v = new std::vector<RegionRec>();
  return *v;
}
std::unordered_map<uint64_t, LandingBind>& landing_binds() {
  static auto* m = new std::unordered_map<uint64_t, LandingBind>();
  return *m;
}
// Relaxed: ordinal mint only needs uniqueness, no ordering.
std::atomic<uint32_t> g_next_ordinal{1};

std::string rma_shm_name(int32_t pid, uint32_t ordinal) {
  char name[64];
  snprintf(name, sizeof(name), "/trpc_rma_%d_%u", pid, ordinal);
  return name;
}

uint64_t make_rkey(uint32_t ordinal) {
  return (static_cast<uint64_t>(static_cast<uint32_t>(getpid())) << 32) |
         ordinal;
}

RmaSegHdr* hdr_of(const std::shared_ptr<RmaMapping>& m) {
  return reinterpret_cast<RmaSegHdr*>(m->base);
}

// Creates + registers one exportable region.  window: initialize the
// slot allocator over the data area.
void* region_create(size_t data_len, bool window, uint64_t* rkey_out) {
  if (data_len == 0 || data_len > (4ull << 30)) {
    return nullptr;
  }
  // Relaxed: ordinal mint needs uniqueness only, no ordering.
  const uint32_t ord =
      g_next_ordinal.fetch_add(1, std::memory_order_relaxed);
  const std::string name = rma_shm_name(getpid(), ord);
  const size_t bytes = kRmaDataOffset + data_len;
  int fd = shm_open(name.c_str(), O_CREAT | O_EXCL | O_RDWR, 0600);
  if (fd < 0 && errno == EEXIST) {
    // The name holds this pid and an ordinal minted once in this process:
    // an object already under it is the orphan of a dead process whose
    // pid was recycled (killed before it could unlink).  Nobody maps it
    // under this name any more; take the name.
    shm_unlink(name.c_str());
    fd = shm_open(name.c_str(), O_CREAT | O_EXCL | O_RDWR, 0600);
  }
  if (fd < 0) {
    return nullptr;
  }
  if (ftruncate(fd, static_cast<off_t>(bytes)) != 0) {
    close(fd);
    shm_unlink(name.c_str());
    return nullptr;
  }
  void* mem =
      mmap(nullptr, bytes, PROT_READ | PROT_WRITE, MAP_SHARED, fd, 0);
  close(fd);
  if (mem == MAP_FAILED) {
    shm_unlink(name.c_str());
    return nullptr;
  }
  auto* h = static_cast<RmaSegHdr*>(mem);
  memset(static_cast<void*>(h), 0, sizeof(RmaSegHdr));
  h->data_off = kRmaDataOffset;
  h->data_len = data_len;
  if (window) {
    h->nslots = kRmaWindowSlots;
    h->slot_bytes = static_cast<uint32_t>(data_len / kRmaWindowSlots);
  }
  // Release via the magic store position: peers validate magic before
  // trusting any other field (plain store is fine — the name is only
  // shipped to peers after this returns).
  h->magic = kRmaMagic;
  auto mapping = std::make_shared<RmaMapping>();
  mapping->base = static_cast<char*>(mem);
  mapping->len = bytes;
  mapping->owned = true;
  RegionRec rec;
  rec.rkey = make_rkey(ord);
  rec.map = mapping;
  rec.name = name;
  rec.window = window;
  if (window) {
    rec.scav = std::make_shared<WindowScav>();
  }
  rec.geom.data_len = data_len;
  rec.geom.slot_bytes = h->slot_bytes;
  rec.geom.nslots = h->nslots;
  {
    std::lock_guard<std::mutex> g(reg_mu());
    regions().push_back(std::move(rec));
  }
  if (rkey_out != nullptr) {
    *rkey_out = make_rkey(ord);
  }
  return static_cast<char*>(mem) + kRmaDataOffset;
}

// Local-registry lookup (receiver side; loopback peer resolution) with
// the TRUSTED creation-time geometry.
std::shared_ptr<RmaMapping> local_region(
    uint64_t rkey, bool* window, RmaGeom* geom,
    std::shared_ptr<WindowScav>* scav = nullptr) {
  std::lock_guard<std::mutex> g(reg_mu());
  for (const RegionRec& r : regions()) {
    if (r.rkey == rkey && r.map != nullptr) {
      if (window != nullptr) {
        *window = r.window;
      }
      if (geom != nullptr) {
        *geom = r.geom;
      }
      if (scav != nullptr) {
        *scav = r.scav;
      }
      return r.map;
    }
  }
  return nullptr;
}

// Slot-run mask of a span [off, off+need) under geometry g.
uint64_t span_slot_mask(const RmaGeom& g, uint64_t off, uint64_t need) {
  const uint32_t k =
      static_cast<uint32_t>((need + g.slot_bytes - 1) / g.slot_bytes);
  const uint32_t start = static_cast<uint32_t>(off / g.slot_bytes);
  const uint64_t run = k >= 64 ? ~0ull : ((1ull << k) - 1);
  return run << start;
}

// Cross-pid peer mappings cached by rkey (bounded, FIFO-evicted): the
// direct-landing path puts into the SAME caller regions over and over
// (a decode node cycling a handful of landing buffers), and paying
// shm_open+mmap+munmap plus cold soft-faults per transfer capped the
// cross-process KV pull at ~1.2 GB/s where the in-process path ran 6+.
// A hit is revalidated against the shm object's CURRENT inode (one
// shm_open+fstat, no mmap, pages stay warm): rkeys embed pid+ordinal,
// and pid RECYCLING can re-mint an old rkey for a brand-new region — an
// identity check is what makes the cache safe, not the mint alone.  A
// peer that merely freed its region is harmless either way: the
// receiver's resolve rejects the transfer whole.
struct PeerMapEntry {
  std::shared_ptr<RmaMapping> map;
  RmaGeom geom;
  dev_t dev = 0;  // shm object identity at map time
  ino_t ino = 0;
};
struct PeerMapCache {
  std::mutex mu;
  std::unordered_map<uint64_t, PeerMapEntry> map;
  std::vector<uint64_t> order;  // insertion order for eviction
};
PeerMapCache& peer_map_cache() {
  static auto* c = new PeerMapCache();
  return *c;
}
constexpr size_t kPeerMapCacheCap = 64;

// Maps a PEER's exportable region by rkey, snapshotting its geometry
// from the header ONCE under validation (all later arithmetic uses the
// snapshot).  Loopback (peer pid == ours) shares the registry's own
// mapping — same virtual address, and the shared refcount defers
// rma_free's munmap past this user.  Cross-pid mappings come from the
// bounded cache above.
std::shared_ptr<RmaMapping> map_peer_region(uint64_t rkey, RmaGeom* geom) {
  const int32_t pid = static_cast<int32_t>(rkey >> 32);
  const uint32_t ord = static_cast<uint32_t>(rkey);
  if (pid == getpid()) {
    return local_region(rkey, nullptr, geom);
  }
  const std::string name = rma_shm_name(pid, ord);
  {
    PeerMapCache& c = peer_map_cache();
    std::lock_guard<std::mutex> g(c.mu);
    auto it = c.map.find(rkey);
    if (it != c.map.end()) {
      // Revalidate identity: the same rkey naming a DIFFERENT shm
      // object (pid recycled, ordinal re-minted) must not serve the
      // dead peer's orphaned pages.
      struct stat st;
      const int vfd = shm_open(name.c_str(), O_RDONLY, 0600);
      const bool same = vfd >= 0 && fstat(vfd, &st) == 0 &&
                        st.st_dev == it->second.dev &&
                        st.st_ino == it->second.ino;
      if (vfd >= 0) {
        close(vfd);
      }
      if (same) {
        if (geom != nullptr) {
          *geom = it->second.geom;
        }
        return it->second.map;
      }
      c.map.erase(it);  // stale identity: fall through to a fresh map
      for (auto oit = c.order.begin(); oit != c.order.end(); ++oit) {
        if (*oit == rkey) {
          c.order.erase(oit);
          break;
        }
      }
    }
  }
  const int fd = shm_open(name.c_str(), O_RDWR, 0600);
  if (fd < 0) {
    return nullptr;
  }
  struct stat st;
  if (fstat(fd, &st) != 0 ||
      st.st_size < static_cast<off_t>(kRmaDataOffset)) {
    close(fd);
    return nullptr;
  }
  void* mem = mmap(nullptr, static_cast<size_t>(st.st_size),
                   PROT_READ | PROT_WRITE, MAP_SHARED, fd, 0);
  close(fd);
  if (mem == MAP_FAILED) {
    return nullptr;
  }
  auto m = std::make_shared<RmaMapping>();
  m->base = static_cast<char*>(mem);
  m->len = static_cast<size_t>(st.st_size);
  m->owned = true;
  const RmaSegHdr* h = hdr_of(m);
  // Copy-then-validate: each field is read ONCE into the snapshot; the
  // live header may be scribbled by its owner afterwards.
  RmaGeom snap;
  snap.data_len = h->data_len;
  snap.slot_bytes = h->slot_bytes;
  snap.nslots = h->nslots;
  if (h->magic != kRmaMagic || h->data_off != kRmaDataOffset ||
      snap.data_len == 0 || snap.data_len > m->len - kRmaDataOffset) {
    return nullptr;  // mapping dtor unmaps
  }
  if (snap.nslots != 0 &&
      (snap.nslots != kRmaWindowSlots || snap.slot_bytes < kRmaSpanHdr ||
       static_cast<uint64_t>(snap.slot_bytes) * snap.nslots >
           snap.data_len)) {
    return nullptr;
  }
  if (geom != nullptr) {
    *geom = snap;
  }
  {
    PeerMapCache& c = peer_map_cache();
    std::lock_guard<std::mutex> g(c.mu);
    if (c.map.size() >= kPeerMapCacheCap && !c.order.empty()) {
      c.map.erase(c.order.front());  // shared_ptr defers the munmap
      c.order.erase(c.order.begin());
    }
    PeerMapEntry e;
    e.map = m;
    e.geom = snap;
    e.dev = st.st_dev;  // identity captured at map time (fstat above)
    e.ino = st.st_ino;
    if (c.map.emplace(rkey, std::move(e)).second) {
      c.order.push_back(rkey);
    }
  }
  return m;
}

// ---- window span allocator ----------------------------------------------

// Claims a contiguous run of slots covering `need` bytes (trusted
// geometry only — never the live header's).  Single-word CAS: ≤ 64
// slots per window by construction.  -1 when no run fits (window full —
// the caller falls back to the copy path).
int span_alloc(RmaSegHdr* h, const RmaGeom& g, uint64_t need,
               uint64_t* off_out) {
  const uint32_t k =
      static_cast<uint32_t>((need + g.slot_bytes - 1) / g.slot_bytes);
  if (k == 0 || k > g.nslots) {
    return -1;
  }
  const uint64_t run = k == 64 ? ~0ull : ((1ull << k) - 1);
  // Acquire on the claim: the payload bytes we are about to write into
  // a recycled slot must not be ordered before the observation that the
  // receiver freed it.
  uint64_t cur = h->slot_map.load(std::memory_order_acquire);
  while (true) {
    int start = -1;
    for (uint32_t s = 0; s + k <= g.nslots; ++s) {
      if ((cur & (run << s)) == 0) {
        start = static_cast<int>(s);
        break;
      }
    }
    if (start < 0) {
      return -1;
    }
    // Acquire on both CAS orders: claiming (or re-reading) the bitmap
    // must happen-before our writes into possibly-recycled slots — pairs
    // with span_free's release clear after the consumer's last read.
    if (h->slot_map.compare_exchange_weak(cur, cur | (run << start),
                                          std::memory_order_acquire,
                                          std::memory_order_acquire)) {
      *off_out = static_cast<uint64_t>(start) * g.slot_bytes;
      return 0;
    }
  }
}

void span_free(RmaSegHdr* h, const RmaGeom& g, uint64_t off,
               uint64_t need) {
  const uint32_t k =
      static_cast<uint32_t>((need + g.slot_bytes - 1) / g.slot_bytes);
  const uint32_t start = static_cast<uint32_t>(off / g.slot_bytes);
  const uint64_t run = k == 64 ? ~0ull : ((1ull << k) - 1);
  // Release: every read of the span's payload happened before the slots
  // recycle to the allocating peer.
  h->slot_map.fetch_and(~(run << start), std::memory_order_release);
}

// ---- send path -----------------------------------------------------------

// Effective chunk size: the configured stripe chunk, grown until the
// count fits the bitmap.
uint64_t effective_chunk(uint64_t total) {
  uint64_t chunk = std::max<uint64_t>(64 << 10, stripe_chunk_bytes());
  while ((total + chunk - 1) / chunk > kRmaMaxChunks) {
    chunk *= 2;
  }
  return chunk;
}

void xfer_init(RmaXfer* x, uint64_t total, uint64_t chunk, bool crc,
               uint64_t token) {
  const uint32_t nchunks =
      static_cast<uint32_t>((total + chunk - 1) / chunk);
  x->token = token;
  x->chunk_bytes = static_cast<uint32_t>(chunk);
  x->nchunks = nchunks;
  x->flags = crc ? kXferCrcPresent : 0;
  for (uint32_t i = 0; i < kRmaBitWords; ++i) {
    // Relaxed: bits are re-published per chunk with release below; the
    // zeroing itself is ordered by the `total` release store that marks
    // the header live.
    x->chunk_bits[i].store(0, std::memory_order_relaxed);
  }
  // Release: publishes the scalar header fields (and the cleared bitmap)
  // before any chunk bit can be observed set.
  x->total.store(total, std::memory_order_release);
}

struct RailJob {
  RmaXfer* x = nullptr;
  char* dst_base = nullptr;  // payload base in the peer region
  IOBuf data;                // this rail's contiguous byte range
  uint32_t first_chunk = 0;
  uint64_t chunk = 0;
  uint64_t cid = 0;     // timeline correlation
  uint32_t rail = 0;
  bool crc = false;
  EndPoint peer;
  // Deadline plane (net/deadline.h): polled between chunks; a triggered
  // token stops this rail (skipped bytes counted into *aborted — the
  // cancel_saved_bytes accounting and the caller's no-control-frame
  // decision).  The token's scope is kept alive by the rma_try_send
  // caller across put_body's bounded join.
  DeadlineToken tok;
  std::atomic<uint64_t>* aborted = nullptr;
};

// Writes one rail's chunk range: memcpy into the peer region, then a
// release-fenced bit per chunk.  Fault points compose with the global
// transport actor (kTx): drop skips write+bit, trunc writes a prefix and
// skips the bit (whole-call failure either way), delay parks first.
void rail_run(RailJob* j) {
  FaultActor& fa = FaultActor::global();
  const bool tl = timeline::enabled();
  uint32_t ci = j->first_chunk;
  uint64_t off = static_cast<uint64_t>(ci) * j->chunk;
  while (!j->data.empty()) {
    if (j->aborted != nullptr && j->tok.aborted()) {
      // Cascading cancel / expired budget: stop within one chunk.  The
      // remaining chunks' bits stay clear, so the receiver (if the
      // control frame raced out at all) drops the transfer whole.
      j->aborted->fetch_add(j->data.size(), std::memory_order_acq_rel);
      break;
    }
    IOBuf piece;
    j->data.cutn(&piece, j->chunk);
    const uint64_t n = piece.size();
    bool write_bytes = true;
    bool set_bit = true;
    uint64_t trunc_to = n;
    bool corrupt = false;
    if (fa.active()) {
      // Same kTx decision stream as the byte plane (FaultTransport), so
      // chunk faults replay by seed alongside everything else.  delay
      // faults compose via the control frame's rx path instead — a
      // delayed ring read stalls the whole transfer's completion.
      const FaultDecision d = fa.decide(FaultPoint::kTx, j->peer);
      switch (d.kind) {
        case FaultKind::kDrop:
        case FaultKind::kReset:
          write_bytes = false;
          set_bit = false;
          break;
        case FaultKind::kTrunc:
        case FaultKind::kPartial:
          trunc_to = n > 1 ? d.rand % n : 0;
          set_bit = false;
          break;
        case FaultKind::kCorrupt:
          corrupt = true;  // flip one byte AFTER the copy
          break;
        default:
          break;
      }
    }
    if (write_bytes) {
      piece.copy_to(j->dst_base + off, trunc_to);
      if (corrupt && trunc_to > 0) {
        // One flipped byte in the landed chunk: the per-chunk CRC (when
        // the call checksums) rejects the whole transfer at resolve.
        j->dst_base[off] ^= 0x20;
      }
    }
    if (set_bit) {
      if (j->crc) {
        j->x->chunk_crc[ci] = crc32c(piece);
      }
      // Release: publishes this chunk's payload bytes (and its CRC slot)
      // to the receiver's acquire bitmap scan.
      j->x->chunk_bits[ci / 64].fetch_or(1ull << (ci % 64),
                                         std::memory_order_release);
    }
    if (tl) {
      // Rail index carries the rma marker bit so Perfetto's rail tracks
      // show one-sided puts distinctly from ring-copied stripe sends.
      timeline::record(timeline::kStripeSend, j->cid,
                       ((timeline::kStripeRmaRailBit |
                         static_cast<uint64_t>(j->rail))
                        << 48) |
                           off);
    }
    ci += 1;
    off += n;
  }
}

// ---- the fan-out (both directions: put_body writes a body into the
// peer's region with it, rma_land copies a window span out with it) ------

// How a transfer of `total` bytes in chunks of `chunk` is cut over rails:
// `per` consecutive chunks (`rail_bytes`) a rail, `rails` of them, the
// last holding what is left.
struct RailCut {
  uint32_t per = 0;
  uint32_t rails = 0;
  uint64_t rail_bytes = 0;
};

RailCut cut_rails(uint64_t total, uint64_t chunk, int rails) {
  const uint32_t nchunks =
      static_cast<uint32_t>((total + chunk - 1) / chunk);
  const uint32_t want =
      std::max(1u, std::min<uint32_t>(static_cast<uint32_t>(rails),
                                      nchunks));
  RailCut cut;
  cut.per = (nchunks + want - 1) / want;
  // Rails actually used: ceil(nchunks/per) — may be fewer than `want`
  // when the rounding above packs the chunks tighter (the join counts
  // REAL rails, or it would wait forever on lanes that never ran).
  cut.rails = (nchunks + cut.per - 1) / cut.per;
  cut.rail_bytes = static_cast<uint64_t>(cut.per) * chunk;
  return cut;
}

struct RailStart {
  const std::function<void(uint32_t)>* run = nullptr;
  uint32_t rail = 0;
  std::atomic<uint32_t>* remaining = nullptr;
};

void rail_fiber(void* arg) {
  std::unique_ptr<RailStart> s(static_cast<RailStart*>(arg));
  (*s->run)(s->rail);
  // Release on the countdown: the joining caller must observe every
  // byte this rail wrote before it acts on the whole (the control frame,
  // the landed stamp).
  s->remaining->fetch_sub(1, std::memory_order_release);
}

// Runs run(0) .. run(rails - 1) concurrently and returns when all have
// finished: all but the last on their own fibers, the last on the
// caller.  `run` and whatever it captures only have to outlive the call.
void run_rails(uint32_t rails, const std::function<void(uint32_t)>& run) {
  std::atomic<uint32_t> remaining{rails - 1};
  for (uint32_t i = 0; i + 1 < rails; ++i) {
    auto* s = new RailStart{&run, i, &remaining};
    if (fiber_start(nullptr, rail_fiber, s, 0) != 0) {
      rail_fiber(s);  // no fiber to be had: this rail runs here too
    }
  }
  run(rails - 1);
  // Bounded join: each rail is a finite range memcpy.  Acquire pairs
  // with the rails' release countdown.
  while (remaining.load(std::memory_order_acquire) != 0) {
    if (in_fiber()) {
      fiber_sleep_us(20);
    } else {
      usleep(20);
    }
  }
}

// Cuts body into rail ranges and writes them concurrently; returns when
// every rail finished.  payload_dst points at the transfer's payload
// base in the peer region.
// Returns the bytes SKIPPED by a mid-transfer cancel (0 = fully put).
uint64_t put_body(RmaXfer* x, char* payload_dst, IOBuf&& body,
                  uint64_t chunk, int rails, uint64_t cid, bool crc,
                  const EndPoint& peer, const DeadlineToken& tok) {
  const RailCut cut = cut_rails(body.size(), chunk, rails);
  std::atomic<uint64_t> aborted_bytes{0};
  std::vector<RailJob> jobs(cut.rails);
  for (uint32_t i = 0; i < cut.rails; ++i) {
    RailJob& j = jobs[i];
    j.x = x;
    j.dst_base = payload_dst;
    j.first_chunk = i * cut.per;
    j.chunk = chunk;
    j.cid = cid;
    j.rail = i;
    j.crc = crc;
    j.peer = peer;
    j.tok = tok;
    j.aborted = &aborted_bytes;
    body.cutn(&j.data, cut.rail_bytes);  // the last takes what is left
  }
  // Every chunk write happens-before the control frame the caller sends
  // after this returns (run_rails' join).
  run_rails(cut.rails, [&jobs](uint32_t i) { rail_run(&jobs[i]); });
  // Acquire pairs with the rails' abort accounting above.
  return aborted_bytes.load(std::memory_order_acquire);
}

// Queues the zero-payload control frame.  0 on success.
int send_control(SocketId primary, RpcMeta&& meta) {
  IOBuf frame;
  tstd_pack(&frame, meta, IOBuf());
  SocketRef s(Socket::Address(primary));
  return s && s->Write(std::move(frame)) == 0 ? 0 : -1;
}

// Resolves (and caches) the peer's window for a session.
std::shared_ptr<RmaMapping> resolve_peer_window(RmaSession* rs,
                                                uint64_t* rkey_out,
                                                RmaGeom* geom_out) {
  std::lock_guard<std::mutex> g(rs->mu);
  // Acquire: the peer published its window rkey into the shared segment
  // after fully creating the region.
  const uint64_t prk =
      rs->peer_rkey_slot != nullptr
          ? rs->peer_rkey_slot->load(std::memory_order_acquire)
          : 0;
  if (prk == 0) {
    return nullptr;
  }
  if (rs->peer_map == nullptr || rs->peer_rkey != prk) {
    RmaGeom snap;
    std::shared_ptr<RmaMapping> m = map_peer_region(prk, &snap);
    if (m == nullptr || snap.nslots == 0) {
      return nullptr;
    }
    rs->peer_map = std::move(m);
    rs->peer_rkey = prk;
    rs->peer_data_len = snap.data_len;
    rs->peer_slot_bytes = snap.slot_bytes;
    rs->peer_nslots = snap.nslots;
  }
  *rkey_out = rs->peer_rkey;
  geom_out->data_len = rs->peer_data_len;
  geom_out->slot_bytes = rs->peer_slot_bytes;
  geom_out->nslots = rs->peer_nslots;
  return rs->peer_map;
}

// Deleter context for a window-span payload: frees the span's slots in
// OUR OWN window when the consumer's last reference drops, holding the
// mapping alive meanwhile.  Carries the trusted geometry — the deleter
// may run long after a hostile peer scribbled the live header.
struct SpanCtx {
  std::shared_ptr<RmaMapping> map;
  std::shared_ptr<WindowScav> scav;  // null when scav state is gone
  RmaGeom geom;
  uint64_t off = 0;
  uint64_t need = 0;
  int mode = 0;  // SocketMode of the connection: whose rails (rma_land)
};

// Forgets the scavenger's first-seen stamps for a span's slots: called
// whenever the OWNER knows the span's identity ended (payload freed, or
// a faulted transfer rejected) so a successor span allocated into the
// same slots ages from ITS OWN birth — without this, a busy slot
// recycled between scavenger ticks would inherit its predecessor's age
// and a healthy in-flight span could be reclaimed early.
void scav_forget_span(WindowScav* scav, const RmaGeom& g, uint64_t off,
                      uint64_t need) {
  if (scav == nullptr) {
    return;
  }
  const uint64_t mask = span_slot_mask(g, off, need);
  std::lock_guard<std::mutex> lk(reg_mu());
  for (uint32_t i = 0; i < kRmaWindowSlots; ++i) {
    if ((mask & (1ull << i)) != 0) {
      scav->first_seen_us[i] = 0;
    }
  }
}

void span_deleter(void*, void* vctx) {
  auto* ctx = static_cast<SpanCtx*>(vctx);
  if (ctx->scav != nullptr) {
    // Clear the admitted marks BEFORE the slots recycle: a slot that
    // reads set-but-not-admitted merely starts aging fresh (harmless);
    // the reverse order could shield a brand-new span with stale marks.
    // Release pairs with the scavenger's acquire read.
    ctx->scav->admitted.fetch_and(
        ~span_slot_mask(ctx->geom, ctx->off, ctx->need),
        std::memory_order_release);
    scav_forget_span(ctx->scav.get(), ctx->geom, ctx->off, ctx->need);
  }
  span_free(hdr_of(ctx->map), ctx->geom, ctx->off, ctx->need);
  delete ctx;
}

// Deleter context for a direct (caller-region) payload: the caller owns
// the bytes; only the mapping refcount is held (so rma_free defers).
struct DirectCtx {
  std::shared_ptr<RmaMapping> map;
};

void direct_deleter(void*, void* vctx) {
  delete static_cast<DirectCtx*>(vctx);
}

// Verifies a transfer header + bitmap + optional CRCs against the data
// area.  All header fields are copied locally FIRST: the header lives in
// shared memory and a hostile peer can mutate it between check and use.
bool xfer_verify(const RmaXfer* x, uint64_t want_token, const char* payload,
                 uint64_t want_len, uint64_t avail) {
  // Acquire: pairs with the sender's header-publishing release store.
  const uint64_t total = x->total.load(std::memory_order_acquire);
  const uint64_t token = x->token;
  const uint64_t chunk = x->chunk_bytes;
  const uint32_t nchunks = x->nchunks;
  const uint32_t flags = x->flags;
  if (token != want_token || total == 0 || total != want_len ||
      total > avail || chunk < 1024 ||
      nchunks == 0 || nchunks > kRmaMaxChunks ||
      static_cast<uint64_t>(nchunks - 1) * chunk >= total ||
      static_cast<uint64_t>(nchunks) * chunk < total) {
    return false;
  }
  for (uint32_t i = 0; i < nchunks; i += 64) {
    const uint32_t in_word = std::min(64u, nchunks - i);
    const uint64_t want =
        in_word == 64 ? ~0ull : ((1ull << in_word) - 1);
    // Acquire: a set bit publishes that chunk's payload bytes.
    if ((x->chunk_bits[i / 64].load(std::memory_order_acquire) & want) !=
        want) {
      return false;  // incomplete transfer: faulted chunk — drop whole
    }
  }
  if (flags & kXferCrcPresent) {
    for (uint32_t i = 0; i < nchunks; ++i) {
      const uint64_t off = static_cast<uint64_t>(i) * chunk;
      const uint64_t n = std::min(chunk, total - off);
      if (crc32c(payload + off, n) != x->chunk_crc[i]) {
        return false;
      }
    }
  }
  return true;
}

}  // namespace

RmaMapping::~RmaMapping() {
  if (base != nullptr && owned) {
    munmap(base, len);
  }
}

RmaSession::~RmaSession() {
  if (local_rkey != 0) {
    // Release the window region: unlink + drop the registry ref; the
    // munmap defers past any still-wrapped payload.
    std::lock_guard<std::mutex> g(reg_mu());
    auto& v = regions();
    for (auto it = v.begin(); it != v.end(); ++it) {
      if (it->rkey == local_rkey) {
        if (!it->name.empty()) {
          shm_unlink(it->name.c_str());
        }
        v.erase(it);
        break;
      }
    }
  }
}

std::shared_ptr<RmaSession> rma_session_create() {
  const int64_t bytes = flag_value(window_flag(), 0);
  if (bytes <= 0) {
    return nullptr;
  }
  uint64_t rkey = 0;
  if (region_create(static_cast<size_t>(bytes), /*window=*/true, &rkey) ==
      nullptr) {
    return nullptr;
  }
  auto s = std::make_shared<RmaSession>();
  s->local_rkey = rkey;
  return s;
}

void* rma_alloc(size_t len, uint64_t* rkey_out) {
  return region_create(len, /*window=*/false, rkey_out);
}

void rma_free(void* data) {
  if (data == nullptr) {
    return;
  }
  const char* base = static_cast<const char*>(data) - kRmaDataOffset;
  std::lock_guard<std::mutex> g(reg_mu());
  auto& v = regions();
  for (auto it = v.begin(); it != v.end(); ++it) {
    if (it->map != nullptr && it->map->base == base) {
      if (!it->name.empty()) {
        shm_unlink(it->name.c_str());  // no NEW peer maps either way
      }
      for (const auto& [cid, bind] : landing_binds()) {
        if (bind.rkey == it->rkey) {
          // An in-flight call still lands here (possibly via the striped
          // copy path, which holds the raw pointer): defer the erase —
          // and with it the munmap — to the last unbind.
          it->free_pending = true;
          return;
        }
      }
      v.erase(it);  // mapping refcount defers the munmap
      return;
    }
  }
}

uint64_t rma_reg(const void* buf, size_t len) {
  if (buf == nullptr || len == 0) {
    return 0;
  }
  // Relaxed: ordinal mint needs uniqueness only, no ordering.
  const uint32_t ord =
      g_next_ordinal.fetch_add(1, std::memory_order_relaxed);
  const uint64_t rkey = make_rkey(ord);
  RegionRec rec;
  rec.rkey = rkey;
  rec.pin_base = static_cast<const char*>(buf);
  rec.pin_len = len;
  std::lock_guard<std::mutex> g(reg_mu());
  regions().push_back(std::move(rec));
  return rkey;
}

int rma_unreg(uint64_t rkey) {
  std::lock_guard<std::mutex> g(reg_mu());
  auto& v = regions();
  for (auto it = v.begin(); it != v.end(); ++it) {
    if (it->rkey == rkey && it->map == nullptr) {
      v.erase(it);
      return 0;
    }
  }
  return -1;
}

bool rma_exportable(const void* buf, size_t len, uint64_t* rkey,
                    uint64_t* off) {
  return rma_pin_exportable(buf, len, rkey, off) != nullptr;
}

size_t rma_region_count() {
  std::lock_guard<std::mutex> g(reg_mu());
  return regions().size();
}

namespace {

Adder& span_scavenged_var() {
  static Adder* a = [] {
    auto* v = new Adder();
    v->expose("rma_span_scavenged",
              "receive-window slots reclaimed by the span scavenger "
              "(allocated by a peer but never admitted — the control "
              "frame was dropped or the sender died mid-put; bounded by "
              "trpc_rma_span_scavenge_ms)");
    return v;
  }();
  return *a;
}

[[maybe_unused]] Adder& g_scavenged_eager = span_scavenged_var();

}  // namespace

size_t rma_scavenge(int64_t now_us) {
  if (now_us == 0) {
    now_us = monotonic_time_us();
  }
  const int64_t age_us = flag_value(scavenge_flag(), 10000) * 1000;
  size_t reclaimed = 0;
  std::lock_guard<std::mutex> g(reg_mu());
  for (RegionRec& r : regions()) {
    if (!r.window || r.map == nullptr || r.scav == nullptr) {
      continue;
    }
    RmaSegHdr* h = hdr_of(r.map);
    // Acquire pairs with the peer's CAS claim (span_alloc) — a slot
    // counted here was fully published before this scan.
    const uint64_t cur = h->slot_map.load(std::memory_order_acquire);
    // Acquire pairs with rma_resolve's admit / span_deleter's clear.
    const uint64_t admitted =
        r.scav->admitted.load(std::memory_order_acquire);
    uint64_t reclaim = 0;
    for (uint32_t i = 0; i < kRmaWindowSlots; ++i) {
      const uint64_t bit = 1ull << i;
      if ((cur & bit) == 0 || (admitted & bit) != 0) {
        r.scav->first_seen_us[i] = 0;  // free, or a live admitted span
        continue;
      }
      if (r.scav->first_seen_us[i] == 0) {
        r.scav->first_seen_us[i] = now_us;  // start aging
      } else if (now_us - r.scav->first_seen_us[i] > age_us) {
        reclaim |= bit;
        r.scav->first_seen_us[i] = 0;
      }
    }
    if (reclaim != 0) {
      // Release mirrors span_free: nothing of ours reads the span, but
      // the allocating peer's next claim must not fold into stale state.
      h->slot_map.fetch_and(~reclaim, std::memory_order_release);
      reclaimed += static_cast<size_t>(__builtin_popcountll(reclaim));
    }
  }
  if (reclaimed != 0) {
    span_scavenged_var() << static_cast<int64_t>(reclaimed);
  }
  return reclaimed;
}

size_t rma_spans_in_use() {
  // The drain quiesce poll doubles as the scavenger's lazy tick: a
  // leaked span must not hold a draining server hostage.
  rma_scavenge();
  std::lock_guard<std::mutex> g(reg_mu());
  size_t n = 0;
  for (const RegionRec& r : regions()) {
    if (!r.window || r.map == nullptr) {
      continue;
    }
    // Acquire: pairs with the peer's CAS claim so a span counted here
    // was fully published before we read the bitmap.
    n += static_cast<size_t>(__builtin_popcountll(
        hdr_of(r.map)->slot_map.load(std::memory_order_acquire)));
  }
  return n;
}

// The one authoritative exportable-region scan: rma_exportable is a
// thin boolean wrapper over it.
std::shared_ptr<RmaMapping> rma_pin_exportable(const void* buf, size_t len,
                                               uint64_t* rkey,
                                               uint64_t* off) {
  const char* p = static_cast<const char*>(buf);
  std::lock_guard<std::mutex> g(reg_mu());
  for (const RegionRec& r : regions()) {
    if (r.map == nullptr || r.window || r.free_pending) {
      continue;  // windows are connection-owned, not caller landings;
                 // free_pending regions accept no NEW registrations
    }
    const char* data = r.map->base + kRmaDataOffset;
    if (p >= data && len <= r.geom.data_len &&
        p + len <= data + r.geom.data_len) {
      if (rkey != nullptr) {
        *rkey = r.rkey;
      }
      if (off != nullptr) {
        *off = static_cast<uint64_t>(p - data);
      }
      return r.map;
    }
  }
  return nullptr;
}

void rma_landing_bind(uint64_t cid, void* buf, size_t cap) {
  uint64_t rkey = 0;
  uint64_t off = 0;
  if (!rma_exportable(buf, cap, &rkey, &off)) {
    return;  // copy-path landing only (arbitrary caller memory)
  }
  std::lock_guard<std::mutex> g(reg_mu());
  for (const auto& [other_cid, bind] : landing_binds()) {
    if (bind.rkey == rkey && other_cid != cid) {
      // One direct transfer per region at a time: the region header
      // holds a single completion descriptor.  This call still lands
      // via the striped copy path — correct, just not zero-copy.
      return;
    }
  }
  landing_binds()[cid] = LandingBind{rkey, cap, off};
}

void rma_landing_unbind(uint64_t cid) {
  std::lock_guard<std::mutex> g(reg_mu());
  auto it = landing_binds().find(cid);
  if (it == landing_binds().end()) {
    return;
  }
  const uint64_t rkey = it->second.rkey;
  landing_binds().erase(it);
  for (const auto& [other_cid, bind] : landing_binds()) {
    if (bind.rkey == rkey) {
      return;  // another in-flight call still lands in the region
    }
  }
  auto& v = regions();
  for (auto rit = v.begin(); rit != v.end(); ++rit) {
    if (rit->rkey == rkey && rit->free_pending) {
      v.erase(rit);  // the deferred rma_free completes here
      return;
    }
  }
}

uint64_t rma_landing_rkey(uint64_t cid, uint64_t* max_out,
                          uint64_t* off_out) {
  std::lock_guard<std::mutex> g(reg_mu());
  auto it = landing_binds().find(cid);
  if (it == landing_binds().end()) {
    return 0;
  }
  if (max_out != nullptr) {
    *max_out = it->second.cap;
  }
  if (off_out != nullptr) {
    *off_out = it->second.off;
  }
  return it->second.rkey;
}

int rma_rails_for(int socket_mode) {
  return static_cast<int>(
      socket_mode == static_cast<int>(SocketMode::kIci)
          ? flag_value(ici_rails_flag(), 4)
          : flag_value(shm_rails_flag(), 4));
}

uint32_t rma_land(const IOBuf& resp, void* dst, size_t n) {
  const IOBuf::BlockRef* ref =
      resp.block_count() == 1 ? &resp.ref_at(0) : nullptr;
  if (ref != nullptr && ref->block->user_deleter == &span_deleter &&
      n <= ref->length) {
    const auto* ctx = static_cast<const SpanCtx*>(ref->block->user_ctx);
    const RailCut cut =
        cut_rails(n, effective_chunk(n), rma_rails_for(ctx->mode));
    if (cut.rails > 1) {
      const char* src = ref->block->data + ref->offset;
      char* out = static_cast<char*>(dst);
      run_rails(cut.rails, [&](uint32_t i) {
        const uint64_t off = i * cut.rail_bytes;
        memcpy(out + off, src + off,
               std::min<uint64_t>(cut.rail_bytes, n - off));
      });
      return cut.rails;
    }
  }
  resp.copy_to(dst, n);
  return 1;
}

void rma_advertise_response(SocketId sid, uint64_t cid, RpcMeta* meta) {
  uint64_t max = 0;
  uint64_t off = 0;
  const uint64_t rkey = rma_landing_rkey(cid, &max, &off);
  if (rkey == 0) {
    return;
  }
  SocketRef s(Socket::Address(sid));
  if (!s || s->transport() == nullptr ||
      s->transport()->rma(s.get()) == nullptr) {
    return;  // no one-sided plane on this connection
  }
  meta->rma_resp_rkey = rkey;
  meta->rma_resp_max = max;
  meta->rma_resp_off = off;
}

int rma_try_send(SocketId primary, RpcMeta* meta, IOBuf* body,
                 uint64_t target_rkey, uint64_t target_max,
                 uint64_t target_off, const DeadlineToken& tok) {
  const uint64_t total = body->size();
  // A stream's DATA frame is a large body like any other: StreamWrite
  // writes the control frame where the in-band frame would have gone, so
  // the stream's order holds, and names the transfer in correlation_id
  // (net/stream.cc).  A request or response that offers or accepts a
  // stream stays in band.
  const bool stream_data = meta->type == RpcMeta::kStreamFrame &&
                           meta->stream_flags == RpcMeta::kStreamData;
  if ((meta->stream_id != 0 && !stream_data) || !stripe_eligible(total)) {
    return 1;
  }
  SocketRef s(Socket::Address(primary));
  if (!s || s->transport() == nullptr) {
    return 1;
  }
  RmaSession* rs = s->transport()->rma(s.get());
  if (rs == nullptr) {
    return 1;
  }
  if (s->mode() == SocketMode::kIci &&
      ici_payload_prefers_descriptors(*body)) {
    return 1;  // staging-backed bodies ride sender-owned descriptors
  }
  const uint64_t chunk = effective_chunk(total);
  const bool crc = meta->has_checksum;
  const int rails = rma_rails_for(static_cast<int>(s->mode()));
  const uint64_t cid = meta->correlation_id;
  const EndPoint peer = s->remote();

  // Direct-to-region: the peer advertised a registered caller buffer for
  // this payload (response landing) — write at data offset 0, completion
  // bitmap in the region header.
  if (target_rkey != 0 && total <= target_max) {
    RmaGeom tg;
    std::shared_ptr<RmaMapping> m = map_peer_region(target_rkey, &tg);
    if (m != nullptr) {
      RmaSegHdr* h = hdr_of(m);
      if (tg.nslots == 0 && target_off <= tg.data_len &&
          total <= tg.data_len - target_off) {
        if (timeline::enabled()) {
          timeline::record(timeline::kStripeCut, cid, total);
        }
        xfer_init(&h->direct, total, chunk, crc, cid);
        const uint32_t nchunks =
            static_cast<uint32_t>((total + chunk - 1) / chunk);
        const uint64_t skipped =
            put_body(&h->direct, m->base + kRmaDataOffset + target_off,
                     std::move(*body), chunk, rails, cid, crc, peer, tok);
        if (skipped != 0) {
          // Cancelled mid-transfer: no control frame — the receiver
          // never admits the partial put; the caller's fid is already
          // dying (the cancel reached it first).
          deadline_vars().cancel_saved_bytes
              << static_cast<int64_t>(skipped);
          return -1;
        }
        meta->rma_rkey = target_rkey;
        meta->rma_off = kRmaDirectOff;
        meta->rma_len = total;
        meta->rma_chunk = chunk;
        // The control frame's payload is empty, so a checksummed call's
        // frame carries crc32c("") == 0 — has_checksum stays SET (the
        // server derives response-checksum intent from it; the real
        // integrity rides the per-chunk CRCs in the transfer header).
        meta->checksum = 0;
        hotpath_vars().rma_tx_msgs << 1;
        hotpath_vars().rma_tx_chunks << nchunks;
        hotpath_vars().rma_tx_bytes << static_cast<int64_t>(total);
        return send_control(primary, std::move(*meta)) == 0 ? 0 : -1;
      }
    }
    // Advertised region unusable: fall through to the window path.
  }

  uint64_t peer_rkey = 0;
  RmaGeom wg;
  std::shared_ptr<RmaMapping> m = resolve_peer_window(rs, &peer_rkey, &wg);
  if (m == nullptr) {
    return 1;  // peer window not published (old peer / disabled)
  }
  RmaSegHdr* h = hdr_of(m);
  uint64_t off = 0;
  const uint64_t need = kRmaSpanHdr + total;
  if (span_alloc(h, wg, need, &off) != 0) {
    hotpath_vars().rma_window_full << 1;
    return 1;  // window full: copy path carries this one
  }
  auto* x = reinterpret_cast<RmaXfer*>(m->base + kRmaDataOffset + off);
  if (timeline::enabled()) {
    timeline::record(timeline::kStripeCut, cid, total);
  }
  xfer_init(x, total, chunk, crc, cid);
  const uint32_t nchunks =
      static_cast<uint32_t>((total + chunk - 1) / chunk);
  const uint64_t skipped =
      put_body(x, reinterpret_cast<char*>(x) + kRmaSpanHdr,
               std::move(*body), chunk, rails, cid, crc, peer, tok);
  if (skipped != 0) {
    // Cancelled mid-transfer: reclaim the span now (no control frame
    // will ever admit it) and fail the call whole.
    deadline_vars().cancel_saved_bytes << static_cast<int64_t>(skipped);
    span_free(h, wg, off, need);
    return -1;
  }
  meta->rma_rkey = peer_rkey;
  meta->rma_off = off;
  meta->rma_len = total;
  meta->rma_chunk = chunk;
  // Empty control payload: crc32c("") == 0; has_checksum stays SET so
  // the server still derives response-checksum intent from the request.
  meta->checksum = 0;
  hotpath_vars().rma_tx_msgs << 1;
  hotpath_vars().rma_tx_chunks << nchunks;
  hotpath_vars().rma_tx_bytes << static_cast<int64_t>(total);
  if (send_control(primary, std::move(*meta)) != 0) {
    span_free(h, wg, off, need);  // control never queued: reclaim now
    return -1;
  }
  return 0;
}

bool rma_resolve(InputMessage* msg, Socket* sock) {
  {
    // Lazy scavenger tick, rate-limited to ~4/s: while one-sided
    // traffic flows, leaked spans (dropped control frames) reclaim
    // without any dedicated thread; the drain poll covers idle windows.
    static std::atomic<int64_t> last_scan{0};
    const int64_t now = monotonic_time_us();
    // Relaxed: the limiter only needs an approximate winner; the
    // scavenger itself synchronizes through reg_mu and the bitmaps.
    int64_t prev = last_scan.load(std::memory_order_relaxed);
    if (now - prev > 250 * 1000 &&
        last_scan.compare_exchange_strong(prev, now,
                                          std::memory_order_relaxed)) {
      rma_scavenge(now);
    }
  }
  RpcMeta& m = msg->meta;
  const uint64_t rkey = m.rma_rkey;
  const uint64_t total = m.rma_len;
  const bool direct = m.rma_off == kRmaDirectOff;
  auto reject = [&](const char* why) {
    hotpath_vars().rma_rejected << 1;
    LOG(Warning) << "rma control rejected (" << why << ", rkey=" << rkey
                 << " off=" << m.rma_off << " len=" << total << ")";
    return false;
  };
  if (total == 0 || !msg->payload.empty()) {
    return reject("bad control frame");
  }
  if (direct) {
    // Response into the caller's registered buffer: the rkey must be the
    // one THIS process advertised for this cid — a control frame naming
    // anything else (freed region, another caller's buffer) drops whole.
    if (m.type != RpcMeta::kResponse) {
      return reject("direct put on a non-response");
    }
    uint64_t cap = 0;
    uint64_t land_off = 0;
    if (rma_landing_rkey(m.correlation_id, &cap, &land_off) != rkey ||
        total > cap) {
      return reject("not the advertised landing");
    }
    bool window = false;
    RmaGeom geom;  // trusted creation-time geometry, never the header's
    std::shared_ptr<RmaMapping> map = local_region(rkey, &window, &geom);
    if (map == nullptr || window) {
      return reject("unknown region");
    }
    RmaSegHdr* h = hdr_of(map);
    // The landing offset comes from the LOCAL bind (what this process
    // registered), never the frame — a control frame cannot steer the
    // payload pointer anywhere the caller didn't bind.
    if (land_off > geom.data_len || total > geom.data_len - land_off) {
      return reject("landing out of bounds");
    }
    char* payload = map->base + kRmaDataOffset + land_off;
    if (!xfer_verify(&h->direct, m.correlation_id, payload, total,
                     geom.data_len - land_off)) {
      return reject("incomplete or corrupt transfer");
    }
    auto* ctx = new DirectCtx{std::move(map)};
    msg->payload.append_user_data(payload, total, &direct_deleter, ctx);
  } else {
    // Window span: only the window bound to THIS connection's session is
    // addressable — the control frame cannot name other local regions.
    RmaSession* rs = sock != nullptr && sock->transport() != nullptr
                         ? sock->transport()->rma(sock)
                         : nullptr;
    if (rs == nullptr || rs->local_rkey != rkey) {
      return reject("not this connection's window");
    }
    bool window = false;
    RmaGeom geom;  // trusted creation-time geometry, never the header's
    std::shared_ptr<WindowScav> scav;
    std::shared_ptr<RmaMapping> map =
        local_region(rkey, &window, &geom, &scav);
    if (map == nullptr || !window) {
      return reject("unknown window");
    }
    RmaSegHdr* h = hdr_of(map);
    const uint64_t need = kRmaSpanHdr + total;
    if (m.rma_off % geom.slot_bytes != 0 || m.rma_off >= geom.data_len ||
        need > geom.data_len - m.rma_off) {
      return reject("span out of bounds");
    }
    // A span is addressable only while its slots are ALLOCATED: clear
    // bits mean the scavenger reclaimed it (its control frame was
    // presumed lost — this is that frame, arriving late).  Neither
    // admit nor free: a successor span may already own the memory.
    // Acquire pairs with the peer's claim CAS.
    const uint64_t slot_mask = span_slot_mask(geom, m.rma_off, need);
    if ((h->slot_map.load(std::memory_order_acquire) & slot_mask) !=
        slot_mask) {
      return reject("span was scavenged");
    }
    auto* x = reinterpret_cast<RmaXfer*>(map->base + kRmaDataOffset +
                                         m.rma_off);
    char* payload = reinterpret_cast<char*>(x) + kRmaSpanHdr;
    // Token gate on RECLAMATION: only a frame whose correlation id owns
    // the span header may free the slots on verification failure — a
    // scavenged-and-reused span (successor's token) or a hostile frame
    // must reject WITHOUT freeing someone else's live span.  Acquire
    // pairs with the sender's header-publishing release store.
    const bool owns =
        x->total.load(std::memory_order_acquire) != 0 &&
        x->token == m.correlation_id;
    if (!xfer_verify(x, m.correlation_id, payload, total,
                     geom.data_len - m.rma_off - kRmaSpanHdr)) {
      if (owns) {
        scav_forget_span(scav.get(), geom, m.rma_off, need);
        span_free(h, geom, m.rma_off, need);  // reclaim the faulted span
      }
      return reject("incomplete or corrupt transfer");
    }
    if (scav != nullptr) {
      // Admit marks: the span is live for as long as the payload holds
      // a reference — the scavenger must never age it.  Release pairs
      // with the scavenger's acquire read.
      scav->admitted.fetch_or(span_slot_mask(geom, m.rma_off, need),
                              std::memory_order_release);
    }
    auto* ctx = new SpanCtx{std::move(map), std::move(scav), geom,
                            m.rma_off, need,
                            static_cast<int>(sock->mode())};
    msg->payload.append_user_data(payload, total, &span_deleter, ctx);
  }
  if (timeline::enabled()) {
    timeline::record(timeline::kStripeDone, m.correlation_id, total);
  }
  hotpath_vars().rma_rx_msgs << 1;
  // The payload is in place: clear the transfer fields (the response
  // advertisement, if any, stays — it belongs to the request's reply
  // path) and let the messenger dispatch the message normally.
  m.rma_rkey = 0;
  m.rma_off = 0;
  m.rma_len = 0;
  m.rma_chunk = 0;
  // Chunk CRCs were verified out-of-band; the zeroed checksum must not
  // masquerade as a whole-body one, but has_checksum stays as parsed —
  // the server derives response-checksum intent from it (the same
  // contract as stripe.cc's dispatch_entry).
  m.checksum = 0;
  return true;
}

// -- readiness maps --------------------------------------------------------
//
// Producer-stamped chunk-ready bitmaps with the RmaXfer fence
// discipline: stamp = release fetch_or after the producer's writes,
// test = acquire scan so a true answer publishes those writes.  Maps
// are process-local; waiters park on a fiber Event so both fibers and
// pthreads (ctypes callers) can block.

namespace {

struct ReadyMap {
  const char* base = nullptr;
  uint64_t len = 0;
  uint64_t granularity = 0;
  uint64_t nchunks = 0;
  std::vector<std::atomic<uint64_t>> bits;
  // Monotonic count of bytes stamped (first-time bits only).
  // relaxed: stats only, read with no ordering requirement.
  std::atomic<uint64_t> ready_bytes{0};
  // Bumped (and woken) on every stamp so range waiters re-scan.
  Event changed;

  ReadyMap(const void* b, uint64_t l, uint64_t g)
      : base(static_cast<const char*>(b)),
        len(l),
        granularity(g),
        nchunks((l + g - 1) / g),
        bits((nchunks + 63) / 64) {}
};

std::mutex& ready_mu() {
  static std::mutex* mu = new std::mutex();
  return *mu;
}

std::unordered_map<uint64_t, std::shared_ptr<ReadyMap>>& ready_reg() {
  static auto* reg =
      new std::unordered_map<uint64_t, std::shared_ptr<ReadyMap>>();
  return *reg;
}

uint64_t& ready_next_handle() {
  static uint64_t next = 1;
  return next;
}

std::shared_ptr<ReadyMap> ready_find(uint64_t handle) {
  std::lock_guard<std::mutex> g(ready_mu());
  auto it = ready_reg().find(handle);
  return it == ready_reg().end() ? nullptr : it->second;
}

// Chunk index range [first, last] covering [off, off+len); false when
// the span falls outside the map.
bool ready_span(const ReadyMap& m, uint64_t off, uint64_t len,
                uint64_t* first, uint64_t* last) {
  if (len == 0 || off > m.len || m.len - off < len) return false;
  *first = off / m.granularity;
  *last = (off + len - 1) / m.granularity;
  return true;
}

// Acquire scan: 1 when every chunk in [first, last] is stamped.
bool ready_all_set(const ReadyMap& m, uint64_t first, uint64_t last) {
  for (uint64_t c = first; c <= last; ++c) {
    // acquire: pairs with the stamp's release fetch_or — observing the
    // bit set publishes the producer's buffer writes up to the stamp.
    const uint64_t w = m.bits[c / 64].load(std::memory_order_acquire);
    if (!(w & (1ull << (c % 64)))) return false;
  }
  return true;
}

}  // namespace

uint64_t rma_ready_create(const void* base, uint64_t len,
                          uint64_t granularity) {
  if (base == nullptr || len == 0 || granularity == 0) return 0;
  auto map = std::make_shared<ReadyMap>(base, len, granularity);
  std::lock_guard<std::mutex> g(ready_mu());
  const uint64_t h = ready_next_handle()++;
  ready_reg().emplace(h, std::move(map));
  return h;
}

int rma_ready_stamp(uint64_t handle, uint64_t off, uint64_t len) {
  auto m = ready_find(handle);
  if (!m) return -1;
  uint64_t first, last;
  if (!ready_span(*m, off, len, &first, &last)) return -1;
  // Alignment contract: stamps cover whole chunks so a later test of
  // any sub-range is never half-true.
  if (off % m->granularity != 0) return -1;
  if (len % m->granularity != 0 && off + len != m->len) return -1;
  uint64_t fresh_bytes = 0;
  for (uint64_t c = first; c <= last; ++c) {
    const uint64_t bit = 1ull << (c % 64);
    // release: publishes the producer's preceding buffer writes to any
    // consumer whose acquire scan observes this bit (RmaXfer pattern).
    const uint64_t prev =
        m->bits[c / 64].fetch_or(bit, std::memory_order_release);
    if (!(prev & bit)) {
      fresh_bytes += std::min(m->granularity, m->len - c * m->granularity);
    }
  }
  if (fresh_bytes != 0) {
    // relaxed: stats counter, no ordering needed beyond the bit fence.
    m->ready_bytes.fetch_add(fresh_bytes, std::memory_order_relaxed);
  }
  // relaxed: the Event word is only a wakeup ticket — waiters re-scan
  // the bitmap (acquire) after every wake, so no ordering rides on it.
  m->changed.value.fetch_add(1, std::memory_order_relaxed);
  m->changed.wake_all();
  return 0;
}

int rma_ready_test(uint64_t handle, uint64_t off, uint64_t len) {
  auto m = ready_find(handle);
  if (!m) return -1;
  uint64_t first, last;
  if (!ready_span(*m, off, len, &first, &last)) return -1;
  return ready_all_set(*m, first, last) ? 1 : 0;
}

int rma_ready_wait(uint64_t handle, uint64_t off, uint64_t len,
                   int64_t deadline_us) {
  for (;;) {
    auto m = ready_find(handle);
    if (!m) return EINVAL;  // destroyed under a parked waiter
    uint64_t first, last;
    if (!ready_span(*m, off, len, &first, &last)) return EINVAL;
    // relaxed: ticket read only; the authoritative answer is the
    // acquire bitmap scan below, re-run after every wake.
    const uint32_t v = m->changed.value.load(std::memory_order_relaxed);
    if (ready_all_set(*m, first, last)) return 0;
    if (deadline_us >= 0 && monotonic_time_us() >= deadline_us) {
      return ETIMEDOUT;
    }
    m->changed.wait(v, deadline_us);
  }
}

uint64_t rma_ready_bytes(uint64_t handle) {
  auto m = ready_find(handle);
  // relaxed: stats read, no ordering requirement.
  return m ? m->ready_bytes.load(std::memory_order_relaxed) : 0;
}

void rma_ready_destroy(uint64_t handle) {
  std::shared_ptr<ReadyMap> m;
  {
    std::lock_guard<std::mutex> g(ready_mu());
    auto it = ready_reg().find(handle);
    if (it == ready_reg().end()) return;
    m = std::move(it->second);
    ready_reg().erase(it);
  }
  // Wake parked waiters; they re-resolve the handle and see EINVAL.
  // relaxed: wakeup ticket only (see rma_ready_stamp).
  m->changed.value.fetch_add(1, std::memory_order_relaxed);
  m->changed.wake_all();
}

size_t rma_ready_maps() {
  std::lock_guard<std::mutex> g(ready_mu());
  return ready_reg().size();
}

}  // namespace trpc
