// Paged KV-block registry + node-local block store — block-addressed
// one-sided KV-cache transfer over the RMA fabric (ISSUE 11 tentpole).
//
// No direct brpc parity: the reference stops at connection-addressed
// RPC.  This is fabric-lib's (arXiv 2510.27656) central abstraction made
// concrete on our transport stack: a KV-cache block is addressed by
// BLOCK ID, not by connection — the registry maps
//   block_id → {node, rkey, offset, len, generation}
// and any client holding that record can fetch the bytes from the
// owning node, landing them ONE-SIDED in its own registered pages (the
// PR 10 direct-landing path: the fetch response is PUT straight into
// the caller's RmaBuffer, zero receiver-side copies).  T3-style overlap
// (arXiv 2401.16677) falls out of the existing planes: MB-scale block
// fetches ride the striped/RMA rails while the small token-RPC decode
// stream keeps dispatching through the messenger cut budget and QoS
// lanes — the disaggregated prefill/decode workload composes instead of
// head-of-line blocking.
//
// Roles:
//  - KvStore (one per process, `kv_store()`): the PREFILL side.  Blocks
//    are published out of exportable (rma_alloc'd) regions; the store
//    pins the region mapping so fetches serve the bytes zero-copy (an
//    IOBuf wrap of the registered pages) and rma_free can never unmap
//    them under an in-flight response.  Publishing mints the block's
//    GENERATION (monotonic per block id, tombstones survive eviction);
//    a byte budget (trpc_kv_store_bytes) evicts expired-then-LRU blocks
//    under pressure.  `kv_attach_store(Server*)` serves "Kv.Fetch".
//    Its second tier is the content-addressed PREFIX store (ISSUE 17;
//    at a deployment's block width since PR 37): blocks named by chain
//    key and content hash, hot in registered pages or cold on the heap.
//    A publish takes the bytes where they lie (a landing block of the
//    host pool, co-owned through its mapping until the block is demoted
//    or dropped) or copies them once; a demote MOVES the bytes to the
//    heap and lets the region go; a promote copies them back.  The hash,
//    every copy and every release of a block's memory run OUTSIDE the
//    store's one lock (a `moving` mark under it, the result installed
//    only if the block is still the one that was left, and a demote's
//    only if nothing touched the block meanwhile), room in the hot
//    tier is reserved before it is written, and a victim is the front
//    of a touch-ordered list, never a walk.  Served by
//    "Kv.FetchPrefix"; counted by KvPrefixCounters' registry Adders.
//  - KvRegistry (`kv_registry()`): the directory.  Lease-based
//    ownership: every record carries a deadline; expired records answer
//    kEKvMiss and are pruned lazily.  Double-register of a live block
//    is rejected (kEKvExists) unless the incoming generation is newer
//    (the publisher re-published after a local evict).
//    `kv_attach_registry(Server*)` serves "KvReg.{Register,Lookup,
//    Evict,Renew}" and the batch forms "KvReg.{Register,Lookup,
//    Evict}Many" (a block's records in one RPC) — the registry can run
//    on any node, including a third party.  Prefix replicas are recorded
//    by "KvReg.PutPrefix" / "KvReg.PutPrefixMany" (a turn's new blocks
//    in one RPC) and found by "KvReg.Match" (longest cached prefix).
//  - KvCache: the DECODE-side lookup cache.  Lookups are cached until
//    proven stale: a fetch answered kEKvStale/kEKvMiss (generation
//    bumped, lease expired, block evicted) invalidates the cached
//    record, re-looks-up once, and retries — the generation check is
//    what makes caching safe, never a freshness timer.
//
// Fault semantics (the whole-or-nothing contract, inherited from the
// RMA/stripe planes and extended by generations):
//  - A chunk fault (drop/trunc/corrupt) during a block fetch fails the
//    CALL whole — the landing buffer is never observable as complete
//    with partial bytes (rma_resolve / stripe reassembly drop whole).
//  - Generation and lease are validated AT SERVE TIME, so a lease that
//    expires while the fetch is queued (svr_delay, chaos) answers
//    kEKvStale and the client admits nothing stale — there is no
//    admit-then-invalidate window.
#pragma once

#include <atomic>
#include <cstdint>
#include <list>
#include <map>
#include <memory>
#include <mutex>
#include <string>
#include <unordered_map>
#include <vector>

#include "base/iobuf.h"
#include "stat/reducer.h"

namespace trpc {

class Channel;
class Server;
struct RmaMapping;

// Error codes, continuing the 2004/2005 (kELimit/kEOverloaded) family.
// kEKvMiss: the block is unknown (never registered here, or expired and
// pruned) — look it up again or re-publish.  kEKvStale: the caller's
// record is outdated (generation bumped, lease lapsed, block evicted) —
// a cached lookup MUST be invalidated.  kEKvExists: double-register of
// a live block (ownership is exclusive while the lease holds).
constexpr int kEKvMiss = 2101;
constexpr int kEKvStale = 2102;
constexpr int kEKvExists = 2103;

// Addressing record: where one block's bytes live.  `node` is the
// owning node's RPC endpoint ("host:port") — any connection to it can
// serve the block; the block is NOT bound to a connection.
struct KvBlockMeta {
  uint64_t block_id = 0;
  uint64_t generation = 0;
  uint64_t rkey = 0;  // exportable region holding the bytes
  uint64_t off = 0;   // byte offset inside the region's data area
  uint64_t len = 0;
  char node[64] = {};
};

// Wire form shared by every Kv RPC (fixed little-endian, 112 bytes;
// mirrored by brpc_tpu/rpc/kv.py _WIRE — kv-wire marker for review):
// Register sends all fields; Lookup/Evict send block_id only; Fetch
// sends block_id + generation; Renew sends block_id + lease_ms.
// Lookup's RESPONSE is the same struct with lease_ms = remaining ms;
// Register/Evict/Renew respond with one u64 generation.
struct KvWire {
  uint64_t block_id;
  uint64_t generation;
  uint64_t rkey;
  uint64_t off;
  uint64_t len;
  int64_t lease_ms;
  char node[64];
};
static_assert(sizeof(KvWire) == 112, "KvWire is wire format — fixed");

// Batch forms of the registry calls (kv-wire marker; mirrored by
// brpc_tpu/rpc/kv.py _MANY_GEN / _MANY_RECORD): a block's records cross
// in ONE RPC.  Request: a u64 count (1..kKvManyMax, as KvReg.Match's)
// then that many KvWire, each filled as its single call fills it.
// Answer: the same u64 count, then one entry per request entry, in
// order.  A miss, a live duplicate or a stale generation is that
// ENTRY's status (0 or a kEKv* code); only a malformed request fails
// the call.  RegisterMany/EvictMany answer KvManyGen, LookupMany answers
// KvManyRecord (rec as Lookup's response; zeroed where status != 0).
constexpr uint64_t kKvManyMax = 4096;
struct KvManyGen {
  int64_t status;
  uint64_t generation;
};
static_assert(sizeof(KvManyGen) == 16, "KvManyGen is wire format — fixed");
struct KvManyRecord {
  int64_t status;
  KvWire rec;
};
static_assert(sizeof(KvManyRecord) == 120,
              "KvManyRecord is wire format — fixed");

// Method names (tstd, served by the attach functions below).
inline constexpr const char* kKvFetchMethod = "Kv.Fetch";
inline constexpr const char* kKvRegisterMethod = "KvReg.Register";
inline constexpr const char* kKvLookupMethod = "KvReg.Lookup";
inline constexpr const char* kKvEvictMethod = "KvReg.Evict";
inline constexpr const char* kKvRenewMethod = "KvReg.Renew";
inline constexpr const char* kKvRegisterManyMethod = "KvReg.RegisterMany";
inline constexpr const char* kKvLookupManyMethod = "KvReg.LookupMany";
inline constexpr const char* kKvEvictManyMethod = "KvReg.EvictMany";
inline constexpr const char* kKvPrefixPutMethod = "KvReg.PutPrefix";
inline constexpr const char* kKvPrefixPutManyMethod = "KvReg.PutPrefixMany";
inline constexpr const char* kKvPrefixMatchMethod = "KvReg.Match";
inline constexpr const char* kKvPrefixFetchMethod = "Kv.FetchPrefix";

// ---- content addressing (prefix cache, ISSUE 17) -------------------------

// 128-bit content key.  crc32c is taken by the transport checksum
// plane, so prefix blocks use a two-lane 64-bit mix over the block
// bytes AND the token-id span: identical (bytes, tokens) pairs hash
// identically on every node — the fleet-wide dedup key.
struct Key128 {
  uint64_t hi = 0;
  uint64_t lo = 0;
  bool operator==(const Key128& o) const {
    return hi == o.hi && lo == o.lo;
  }
  bool operator!=(const Key128& o) const { return !(*this == o); }
  bool zero() const { return hi == 0 && lo == 0; }
};
struct Key128Hash {
  size_t operator()(const Key128& k) const {
    return static_cast<size_t>(k.hi ^ (k.lo * 0x9e3779b97f4a7c15ull));
  }
};

// Content hash of one prefix block: the block bytes plus the token-id
// span they were computed from (two prompts that collide on bytes but
// diverge on tokens must NOT dedup).  Deterministic across processes.
void kv_content_hash(const void* data, size_t len, const uint64_t* tokens,
                     size_t ntokens, Key128* out);

// Pages a run's content hashes walk side by side: 1.8 ms a 9 MB page at
// four against 5.2 serial, 2.2 at six, 2.0 at eight (probe, ISSUE 38).
constexpr size_t kKvHashLanes = 4;

// kv_content_hash of n blocks of one length, kKvHashLanes at a time:
// out[j] is bit for bit kv_content_hash(data[j], len, tokens[j],
// ntokens[j]).  One loop advances a group's chains together, so the
// multiplier works on one block while another's mix is in flight.
void kv_content_hash_lanes(const void* const* data, size_t len,
                           const uint64_t* const* tokens,
                           const size_t* ntokens, size_t n, Key128* out);

// Chain keys for a token-id sequence: key_i folds key_{i-1} with the
// i-th block_tokens-sized token chunk, so key_i names the WHOLE prefix
// through block i — the registry's "trie" is a flat map over chain
// keys, and longest-prefix match is a walk until first miss.  Computed
// from token ids alone: the decode side derives them without holding
// any bytes.  block_tokens <= 0 uses trpc_kv_prefix_block_tokens.
// Returns the number of FULL blocks written (partial tail ignored).
size_t kv_prefix_chain(const uint64_t* tokens, size_t ntokens,
                       int64_t block_tokens, Key128* keys, size_t max_keys);

// The kind bit of a prefix record (KvPrefixWire.flags, KvPrefixMeta.flags):
// set, the record is the recurrent STATE of a hybrid cache's
// linear-attention layers taken at a boundary, not a page.  Its key is
// the chain key of the boundary's last page and its depth the boundary
// in pages; the registry files it apart from the pages, so that no walk
// over page keys finds it and a state of another boundary or prompt is
// another key.  The store moves it as it moves a page: only its size
// differs.
constexpr uint32_t kKvPrefixState = 2;

// Addressing record for one prefix-block replica: chain key (where in
// the trie), content hash (what bytes), and where THIS replica lives.
struct KvPrefixMeta {
  Key128 key;         // chain key (token-derived)
  Key128 hash;        // content hash (bytes + token span)
  uint64_t generation = 0;
  uint64_t rkey = 0;  // valid while the replica is hot
  uint64_t off = 0;
  uint64_t len = 0;
  uint32_t depth = 0;  // 0-based block index in the prefix chain; a
                       // state's boundary in pages
  uint32_t flags = 0;  // kKvPrefixState for a state
  char node[64] = {};
};

// Wire form of every prefix-cache RPC (fixed little-endian, 144 bytes;
// mirrored by brpc_tpu/rpc/kv.py _PREFIX_WIRE — kv-wire marker).
// PutPrefix sends all fields; FetchPrefix sends hash + generation;
// Match sends a u64 count + count x 16-byte chain keys and answers a
// u64 record count + that many KvPrefixWire records (one per live
// replica, grouped in chain order — lease_ms = remaining ms).  Where a
// live state lies at a boundary of the matched run, the pages end at the
// deepest such boundary and that state's replicas follow them, flagged
// kKvPrefixState (KvRegistry::match).
// PutPrefixMany is RegisterMany's shape over this record: a u64 count
// (1..kKvManyMax) then that many KvPrefixWire in, the count then one
// KvManyGen per record out, in order (status 0, kEKvExists for the
// idempotent re-offer, or kEKvStale; the accepted generation): a turn's
// new blocks are recorded in ONE round trip.
struct KvPrefixWire {
  uint64_t key_hi;
  uint64_t key_lo;
  uint64_t hash_hi;
  uint64_t hash_lo;
  uint64_t generation;
  uint64_t rkey;
  uint64_t off;
  uint64_t len;
  int64_t lease_ms;
  uint32_t depth;
  uint32_t flags;  // bit 0: replica currently cold (tier telemetry);
                   // kKvPrefixState: the record is a state
  char node[64];
};
static_assert(sizeof(KvPrefixWire) == 144,
              "KvPrefixWire is wire format — fixed");

// Process-wide prefix-plane counters: ONE set, always-on Adders of the
// native registry (exposed as `kv_prefix_<member>` when the kv vars
// register), read as window deltas by benchmark/counters.py, by
// observe.Vars.dump() and /vars, and by trpc_kv_prefix_counters.
struct KvPrefixCounters {
  Adder publish_total;   // fresh blocks admitted by publish_prefix
  Adder publish_bytes;   // their bytes
  Adder publish_copy_bytes;      // ... copied once into store-owned pages
  Adder publish_in_place_bytes;  // ... taken where they lay (no copy)
  Adder publish_renewed;  // publishes of live identical content (lease
                          // renewed, nothing admitted: the cache hit)
  Adder renew_promote;    // ... that found the block in the heap tier
                          // and made the publisher's bytes its hot pages
  Adder hash_us;         // time publishes spent hashing (a group once)
  Adder hash_lanes;      // pages hashed in a group of two or more
  Adder fetch_total;     // prefix fetches served (hot + cold)
  Adder hot_hits;        // ... from registered pages
  Adder cold_hits;       // ... that found the block in the heap tier
  Adder promote;         // cold blocks copied back into registered pages
  Adder demote;          // hot blocks moved to the heap tier
  Adder dropped;         // blocks dropped (budget, lapsed lease, withdraw)
  Adder fetch_stale;     // prefix fetches answered kv-stale
  Adder lock_wait_us;    // time prefix fetches and publishes waited for
                         // the store's lock
  Adder put_total;       // replica registrations accepted by the registry
  Adder put_many_total;  // KvReg.PutPrefixMany calls answered
  Adder put_many_records;  // records they carried
  Adder dedup;           // registry replica folds (same chain key + hash)
  Adder match_total;     // KvReg.Match queries answered
  Adder match_keys;      // chain keys they asked
  Adder match_blocks;    // blocks they matched (sum of matched depths)
  // The state kind (kKvPrefixState), counted besides the totals above,
  // which hold both kinds:
  Adder state_publish_total;   // states admitted fresh (in publish_total)
  Adder state_publish_bytes;   // their bytes
  Adder state_fetch_total;     // states served (in fetch_total)
  Adder state_fetch_bytes;     // their bytes
  Adder match_states;          // Match answers that carried a state
  Adder match_states_shallow;  // ... at a boundary short of the matched run
  KvPrefixCounters();    // exposes each under its name
  static uint64_t read(const Adder& c) {
    return static_cast<uint64_t>(c.get_value());
  }
};
KvPrefixCounters& kv_prefix_counters();

class KvHeapBlock;  // one block's bytes in the heap tier (kvstore.cc)

// ---- node-local block store (prefill side) -------------------------------

class KvStore {
 public:
  // Publishes [data, data+len) as block_id under a lease (lease_ms <= 0
  // uses trpc_kv_lease_ms).  `data` MUST lie inside an exportable
  // (rma_alloc'd) region — the store pins the region mapping and serves
  // fetches zero-copy from it.  Mints the generation (monotonic per
  // block id across evictions) and fills *out (node left empty — the
  // publisher stamps its own endpoint when registering).  Evicts
  // expired-then-LRU blocks to fit the trpc_kv_store_bytes budget.
  // Returns 0, kEKvExists when the block is live (withdraw first),
  // or -1 (not exportable memory / larger than the whole budget).
  // min_generation floors the minted generation: a hot-restart
  // successor (fresh pid, empty tombstones) passes the predecessor's
  // last registry generation + 1 so its takeover re-publish outranks
  // every cached record (net/naming.h drain flow).
  int publish(uint64_t block_id, const void* data, size_t len,
              int64_t lease_ms, KvBlockMeta* out,
              uint64_t min_generation = 0);
  // Explicit eviction.  The generation survives as a tombstone so a
  // re-publish mints a NEWER generation and stale fetches stay
  // detectable.  Returns 0, or kEKvMiss.
  int withdraw(uint64_t block_id);
  // Drain support (Server::Drain hook, net/naming.h): withdraws EVERY
  // live block, tombstoning each generation — a decode cache that still
  // holds this node's records gets kv-stale (invalidate + re-resolve),
  // never bytes from a process that is about to die.  Returns the count.
  size_t withdraw_all();
  // Extends the lease (lease_ms <= 0: the flag default).  0 or kEKvMiss.
  int renew(uint64_t block_id, int64_t lease_ms);
  // Serves one block: validates generation AND lease at serve time,
  // then appends the bytes zero-copy (the region mapping rides the
  // IOBuf deleter).  Returns 0, kEKvStale (generation mismatch, lease
  // lapsed, or evicted-but-tombstoned) or kEKvMiss (never seen).
  int fetch(uint64_t block_id, uint64_t expected_gen, IOBuf* out);
  // In-process zero-copy access for group-transfer machinery
  // (net/collective.h Reshard.Execute): pins the block's region mapping
  // and hands out the raw bytes.  expected_gen 0 accepts any live
  // generation.  Validity is decided now, like fetch; the returned
  // mapping reference keeps the pages alive past rma_free.  Returns 0,
  // kEKvStale, or kEKvMiss.
  int pin(uint64_t block_id, uint64_t expected_gen, const char** data,
          uint64_t* len, std::shared_ptr<RmaMapping>* map,
          uint64_t* gen_out);

  // ---- content-addressed prefix tier (two-tier store, ISSUE 17) ----
  //
  // Publishes one prefix block under its CONTENT hash.  The content
  // hash is computed here, over the bytes where they lie (bytes + token
  // span), outside the store's lock, and echoed in *out with the minted
  // generation.  Where the bytes go: with `in_place` and a source inside
  // an exportable (rma_alloc'd) region the block is TAKEN where it lies
  // and co-owns the region's mapping until it is demoted or dropped,
  // nothing copied (the caller's promise: nobody writes those bytes
  // while anybody owns the mapping — the host pool's landing blocks keep
  // it, cpp/capi/hostpool_capi.cc; kv.py decides by
  // trpc_host_pool_holds); any other source is copied ONCE into a
  // store-owned registered region (callers need no RmaBuffer), or into
  // the heap tier when the hot tier cannot take it.  Re-publishing a
  // LIVE block with the same content hash is the cache-hit path: the
  // lease renews and *out fills, but the return is kEKvExists so
  // callers can count bytes-NOT-recomputed; a block found in the heap
  // tier comes back hot on the publisher's bytes (taken in place or
  // copied once, as above; its generation stays).  Budget: hot bytes
  // under trpc_kv_prefix_hot_bytes (LRU hot blocks DEMOTE to the cold
  // heap tier, never drop); total store bytes (blocks + hot + cold)
  // under trpc_kv_store_bytes (expired, then LRU cold, then LRU hot
  // blocks drop with generation tombstones).  Every touch, a publish's
  // as a fetch's, leaves its block hot, so the hot tier is the blocks
  // touched last and the two tiers are ONE order by last touch: a
  // block is never dropped before one touched earlier (but for the
  // order in which fetches in flight together are served, and a heap
  // block that found no registered memory).  Every copy of a block's
  // bytes (a demote, the one copy of a publish) runs OUTSIDE the lock.
  // Returns 0, kEKvExists, or -1.
  int publish_prefix(const Key128& key, uint32_t depth, const void* data,
                     size_t len, const uint64_t* tokens, size_t ntokens,
                     int64_t lease_ms, KvPrefixMeta* out,
                     uint64_t min_generation = 0, bool in_place = false);
  // One page of a run: its chain key, its bytes, its token span, and
  // whether the store may take the bytes where they lie (`in_place`).
  // A state (flags kKvPrefixState) is admitted as a page is; the flag is
  // kept in its record and counted, nothing else reads it.
  struct PrefixPage {
    Key128 key;
    const void* data = nullptr;
    const uint64_t* tokens = nullptr;
    size_t ntokens = 0;
    bool in_place = false;
    uint32_t flags = 0;
  };
  // Publishes the n pages of a run, all `len` bytes, page j at depth
  // first_depth + j: hashed kKvHashLanes at a time
  // (kv_content_hash_lanes), then admitted one by one as
  // publish_prefix admits, rcs[j] and outs[j] its return and record.
  // Stops after the first page that returns -1; returns the pages
  // handled (n when none failed).
  size_t publish_prefix_run(const PrefixPage* pages, size_t n, size_t len,
                            uint32_t first_depth, int64_t lease_ms,
                            int* rcs, KvPrefixMeta* outs,
                            uint64_t min_generation = 0);
  // Serves one prefix block by content hash: generation AND lease
  // validated at serve time (same stale rules as fetch()).  A hot hit
  // serves zero-copy from the registered pages; a cold hit PROMOTES the
  // block back into a registered region first, the copy (and the demote
  // of whatever it displaces) outside the lock, so fetches of other
  // blocks are served meanwhile; a cold block that cannot be promoted
  // (registered memory exhausted, another fetch already promoting it) is
  // served zero-copy from the heap.  0, kEKvStale, kEKvMiss.
  int fetch_prefix(const Key128& hash, uint64_t expected_gen, IOBuf* out);
  // Explicit eviction by content hash (generation tombstones).
  int withdraw_prefix(const Key128& hash);

  size_t prefix_count();
  uint64_t prefix_hot_bytes();
  uint64_t prefix_cold_bytes();

  size_t count();
  uint64_t bytes_used();
  void clear();  // tests: drop every block AND tombstone

 private:
  struct Block {
    KvBlockMeta meta;
    const char* data = nullptr;
    std::shared_ptr<RmaMapping> map;
    int64_t deadline_us = 0;
    uint64_t touch_seq = 0;  // LRU clock (publish/fetch bumps)
  };
  using PrefixLru = std::list<Key128>;
  using PrefixLeases = std::multimap<int64_t, Key128>;
  struct PrefixBlock {
    KvPrefixMeta meta;        // rkey/off valid only while hot
    const char* hot_data = nullptr;   // registered pages (hot tier)
    std::shared_ptr<RmaMapping> map;  // pins hot pages across serves
    bool owned = false;  // hot pages are a store-owned rma_alloc region;
                         // else a region taken in place, co-owned by map
    std::shared_ptr<const KvHeapBlock> cold;  // the bytes while demoted
    bool hot = false;
    bool moving = false;  // a demote or promote is copying the bytes
                          // outside mu_ (no second move starts meanwhile)
    uint64_t touches = 0;  // fetches and renewals so far: a demote that
                           // finds one more after its copy leaves it hot
    int64_t deadline_us = 0;
    PrefixLru::iterator lru_at;       // in lru_hot_ or lru_cold_
    PrefixLeases::iterator lease_at;  // in prefix_leases_
  };
  // What a drop or a move let go of: released after mu_ is (an rma_free
  // or the free of a heap block is a munmap of megabytes).
  struct PrefixTrash;
  // Evicts one block under mu_ (iterator-safe helper).
  void evict_locked(uint64_t block_id, bool count_var);
  // publish_prefix's admission of a block whose content hash is `hash`.
  int admit_prefix(const PrefixPage& page, uint32_t depth, size_t len,
                   const Key128& hash, int64_t lease_ms, KvPrefixMeta* out,
                   uint64_t min_generation);
  // Prefix-tier helpers, all entered and left with mu_ held.
  void touch_prefix_locked(PrefixBlock* b);
  void set_prefix_lease_locked(PrefixBlock* b, const Key128& hash,
                               int64_t deadline_us);
  void evict_prefix_locked(const Key128& hash, PrefixTrash* trash);
  // Drops expired, then LRU cold, then LRU hot prefix blocks until
  // `incoming` more bytes fit trpc_kv_store_bytes; false if they cannot.
  bool fit_total_locked(uint64_t incoming, uint64_t total_budget,
                        int64_t now, PrefixTrash* trash);
  // Moves the LRU hot block's bytes to the heap tier; mu_ is RELEASED
  // for the copy and held again on return.  False: no hot block to move.
  bool demote_one(std::unique_lock<std::mutex>* lk, PrefixTrash* trash);
  // Reserves `incoming` bytes of the hot budget (prefix_hot_reserved_),
  // demoting LRU hot blocks while they do not fit; may release mu_.
  bool reserve_hot(std::unique_lock<std::mutex>* lk, uint64_t incoming,
                   PrefixTrash* trash);
  // Brings heap-tier block `hash`, which the caller has just marked
  // `moving`, to the hot tier: room reserved, then `map` taken (registered
  // memory at `src` the store may co-own: no copy) or, without one, `src`
  // copied into a region of the store's with mu_ released.  Returns the
  // block as it is afterwards, still in the heap tier where there was no
  // room or no registered memory; nullptr if it was dropped meanwhile.
  PrefixBlock* promote_marked(std::unique_lock<std::mutex>* lk,
                              const Key128& hash, const void* src,
                              std::shared_ptr<RmaMapping> map, uint64_t rkey,
                              uint64_t off, PrefixTrash* trash);
  std::mutex mu_;
  std::unordered_map<uint64_t, Block> blocks_;
  std::unordered_map<Key128, PrefixBlock, Key128Hash> prefix_blocks_;
  // Last generation minted per block id, surviving eviction: a
  // re-published block continues the sequence, and a fetch for an
  // evicted block answers kEKvStale (record invalid) instead of
  // kEKvMiss (record unknown).
  std::unordered_map<uint64_t, uint64_t> tombstones_;
  std::unordered_map<Key128, uint64_t, Key128Hash> prefix_tombstones_;
  uint64_t bytes_ = 0;
  // Tier order, least recently touched first; a demote moves the front
  // of lru_hot_ to the back of lru_cold_, a promote or a publish to the
  // back of lru_hot_: cold then hot is the whole tier in touch order,
  // and a victim is a list's front, never a walk over the blocks.
  PrefixLru lru_hot_;
  PrefixLru lru_cold_;
  PrefixLeases prefix_leases_;  // by deadline: the lapsed lie in front
  uint64_t prefix_hot_bytes_ = 0;
  uint64_t prefix_hot_reserved_ = 0;  // room held for copies under way
  uint64_t prefix_cold_bytes_ = 0;
  uint64_t touch_counter_ = 0;
};
KvStore& kv_store();

// ---- registry (directory) ------------------------------------------------

class KvRegistry {
 public:
  // Records meta under a lease.  Rejects kEKvExists while a live record
  // holds the block with generation >= meta.generation; a NEWER
  // generation replaces (re-publish).  A generation at or below the
  // last seen for this id is rejected kEKvStale (zombie publisher).
  // Returns 0 and echoes the accepted generation.
  int do_register(const KvBlockMeta& meta, int64_t lease_ms,
                  uint64_t* gen_out);
  // Fills *out (+ remaining lease ms).  Expired records prune here and
  // answer kEKvMiss.
  int lookup(uint64_t block_id, KvBlockMeta* out,
             int64_t* lease_left_ms = nullptr);
  int evict(uint64_t block_id, uint64_t* gen_out = nullptr);
  // Extends a live record's lease; echoes the current generation.
  int renew(uint64_t block_id, int64_t lease_ms,
            uint64_t* gen_out = nullptr);

  // ---- content-addressed prefix records (replica sets, ISSUE 17) ----
  //
  // Records one replica of a prefix block.  N publishers of the SAME
  // chain key + content hash fold into ONE record with a replica set
  // (fleet-wide dedup); each replica keeps its own lease deadline and
  // generation, with the PR 12 zombie fence applied PER NODE (a
  // publisher re-offering a generation at or below its last accepted
  // one answers kEKvStale).  A chain key re-offered with a DIFFERENT
  // content hash is rejected kEKvStale — token/content divergence must
  // never silently alias.  Returns 0 and echoes the accepted
  // generation; kEKvExists on an exact same-node same-generation
  // double-register (the lease still renews — content-addressed
  // registration is idempotent).
  int put_prefix(const KvPrefixMeta& meta, int64_t lease_ms,
                 uint64_t* gen_out);
  // A record flagged kKvPrefixState is a state: it is filed under its
  // chain key apart from the pages, with the same rules.
  //
  // Longest cached prefix: walks keys[0..n) in order, stopping at the
  // first key with no live replica.  Appends one KvPrefixMeta per LIVE
  // replica of every matched block (grouped in chain order; expired
  // replicas prune here) plus its remaining lease into the parallel
  // lease_out (ms).  Where a state with a live replica lies at a
  // boundary b of the matched run (the state filed under keys[b - 1]),
  // the deepest such b ends the answer: the pages under it, then every
  // live replica of that state; a hybrid cache is restored as the pages
  // up to a state and the state, never one without the other.  Returns
  // the number of BLOCKS answered (b where a state was, else the run).
  size_t match(const Key128* keys, size_t n,
               std::vector<KvPrefixMeta>* out,
               std::vector<int64_t>* lease_out = nullptr);
  // Drops one node's replica of one chain key (drain support).
  int evict_prefix(const Key128& key, const char* node);
  size_t prefix_count();   // live prefix records (chain keys)
  size_t prefix_replicas();  // live replicas across all records

  size_t count();
  void clear();  // tests

 private:
  struct Entry {
    KvBlockMeta meta;
    int64_t deadline_us = 0;
  };
  struct PrefixReplica {
    KvPrefixMeta meta;
    int64_t deadline_us = 0;
  };
  struct PrefixEntry {
    Key128 hash;        // the content hash every replica must agree on
    uint32_t depth = 0;
    uint64_t len = 0;
    std::vector<PrefixReplica> replicas;
    // Per-node zombie fence, surviving replica pruning: highest
    // generation ever accepted from each node for this chain key.
    std::unordered_map<std::string, uint64_t> last_gen;
  };
  std::mutex mu_;
  std::unordered_map<uint64_t, Entry> entries_;
  std::unordered_map<uint64_t, uint64_t> last_gen_;
  std::unordered_map<Key128, PrefixEntry, Key128Hash> prefix_;
  // States, by the chain key of their boundary's last page.
  std::unordered_map<Key128, PrefixEntry, Key128Hash> prefix_states_;
};
KvRegistry& kv_registry();

// Attach the native handlers (call before Server::Start).  Both may be
// attached to the same server; the registry may also run on a node that
// stores nothing.  Return 0, or -1 when any registration was refused
// (server already running).
int kv_attach_store(Server* s);
int kv_attach_registry(Server* s);

// ---- client-side lookup cache (decode side) ------------------------------

// Caches registry lookups with generation-checked invalidation.  NOT a
// freshness timer: a cached record is used until a fetch proves it
// stale (kEKvStale/kEKvMiss), then invalidated and re-resolved once.
class KvCache {
 public:
  // `registry_ch` (not owned) must outlive the cache.
  explicit KvCache(Channel* registry_ch) : reg_(registry_ch) {}

  // Cached lookup (refresh forces a registry round-trip).  0 or error.
  int lookup(uint64_t block_id, KvBlockMeta* out, bool refresh = false);
  void invalidate(uint64_t block_id);

  // Fetches block_id's bytes from `node_ch` (a channel to meta.node,
  // caller-routed) using the cached record; on a stale answer
  // invalidates, re-looks-up, and retries ONCE with the fresh
  // generation.  0 on success (bytes in *out), else the final error.
  int fetch(Channel* node_ch, uint64_t block_id, IOBuf* out);

  uint64_t hits() const {
    // Relaxed: monotonic test/stat counters — no ordering carried.
    return hits_.load(std::memory_order_relaxed);
  }
  uint64_t misses() const {
    // Relaxed: monotonic test/stat counters — no ordering carried.
    return misses_.load(std::memory_order_relaxed);
  }

 private:
  Channel* reg_;
  std::mutex mu_;
  std::unordered_map<uint64_t, KvBlockMeta> cache_;
  // Relaxed counters: diagnostics only, no synchronization piggybacks.
  std::atomic<uint64_t> hits_{0};
  std::atomic<uint64_t> misses_{0};
};

// Flag registration (idempotent; attach functions and the capi call it
// so /flags sees the kv knobs before first traffic).
void kv_ensure_registered();

// Counts one client-side multi-record fetch (kv.py KvClient.fetch_many)
// of `records` records in kv_fetch_many_total / kv_fetch_many_records.
void kv_note_fetch_many(uint64_t records);

// Counts one client-side sequence hand-over (kv.py
// KvClient.fetch_sequence): handed over, its records and bytes of each
// kind in kv_seq_total / kv_seq_{page,snapshot}_{records,bytes};
// refused whole, one in kv_seq_refused and nothing else.
void kv_note_sequence(uint64_t page_records, uint64_t page_bytes,
                      uint64_t snapshot_records, uint64_t snapshot_bytes,
                      bool handed_over);

// Counts one publish of a page or a sequence (kv.py _publish_records):
// the bytes published where their transfer landed them in
// kv_publish_in_place_bytes, the bytes copied into the caller's slab
// first in kv_publish_copy_bytes.
void kv_note_publish(uint64_t in_place_bytes, uint64_t copy_bytes);

}  // namespace trpc
