#include "net/kvstore.h"

#include <errno.h>
#include <string.h>

#include <algorithm>
#include <limits>

#include "base/flags.h"
#include "base/logging.h"
#include "base/time.h"
#include "net/channel.h"
#include "net/controller.h"
#include "net/rma.h"
#include "net/server.h"
#include "stat/latency_recorder.h"
#include "stat/reducer.h"
#include "stat/timeline.h"

namespace trpc {

namespace {

Flag* lease_flag() {
  static Flag* f = [] {
    Flag* flag = Flag::define_int64(
        "trpc_kv_lease_ms", 30000,
        "default KV-block lease for publishes/registrations that pass "
        "lease_ms <= 0 (ms, [50, 86400000]); an expired lease "
        "invalidates the block everywhere — lookups answer kv-miss, "
        "fetches answer kv-stale");
    if (flag != nullptr) {
      flag->set_validator([](const std::string& v) {
        char* end = nullptr;
        const long long n = strtoll(v.c_str(), &end, 10);
        return end != v.c_str() && *end == '\0' && n >= 50 &&
               n <= 86400000;
      });
    }
    return flag;
  }();
  return f;
}

Flag* store_bytes_flag() {
  static Flag* f = [] {
    Flag* flag = Flag::define_int64(
        "trpc_kv_store_bytes", 1ll << 30,
        "node-local KV-block store byte budget ([1MB, 64GB]); a publish "
        "that would exceed it evicts expired-then-LRU blocks (their "
        "generation tombstones survive, so evicted fetches answer "
        "kv-stale, never partial bytes)");
    if (flag != nullptr) {
      flag->set_validator([](const std::string& v) {
        char* end = nullptr;
        const long long n = strtoll(v.c_str(), &end, 10);
        return end != v.c_str() && *end == '\0' && n >= (1ll << 20) &&
               n <= (64ll << 30);
      });
    }
    return flag;
  }();
  return f;
}

Flag* prefix_hot_bytes_flag() {
  static Flag* f = [] {
    Flag* flag = Flag::define_int64(
        "trpc_kv_prefix_hot_bytes", 256ll << 20,
        "hot-tier byte budget for content-addressed prefix blocks "
        "([1MB, 64GB]); hot blocks live in registered-RMA pages and "
        "serve zero-copy — exceeding the budget DEMOTES LRU blocks to "
        "the unregistered cold tier (never drops them)");
    if (flag != nullptr) {
      flag->set_validator([](const std::string& v) {
        char* end = nullptr;
        const long long n = strtoll(v.c_str(), &end, 10);
        return end != v.c_str() && *end == '\0' && n >= (1ll << 20) &&
               n <= (64ll << 30);
      });
    }
    return flag;
  }();
  return f;
}

Flag* prefix_block_tokens_flag() {
  static Flag* f = [] {
    Flag* flag = Flag::define_int64(
        "trpc_kv_prefix_block_tokens", 128,
        "token span per prefix-cache block ([1, 65536]); chain keys fold "
        "one block_tokens-sized chunk at a time, so every node in the "
        "fleet MUST agree on this value for content hashes to dedup");
    if (flag != nullptr) {
      flag->set_validator([](const std::string& v) {
        char* end = nullptr;
        const long long n = strtoll(v.c_str(), &end, 10);
        return end != v.c_str() && *end == '\0' && n >= 1 && n <= 65536;
      });
    }
    return flag;
  }();
  return f;
}

int64_t effective_lease_us(int64_t lease_ms) {
  if (lease_ms <= 0) {
    lease_ms = lease_flag() != nullptr ? lease_flag()->int64_value() : 30000;
  }
  return monotonic_time_us() + lease_ms * 1000;
}

// ---- vars ----------------------------------------------------------------

struct KvVars {
  Adder publish_total;
  Adder evict_total;
  Adder fetch_total;
  Adder fetch_bytes;
  Adder stale_total;
  Adder register_total;
  Adder lookup_total;
  Adder lookup_miss_total;
  Adder reg_many_total;
  Adder reg_many_records;
  Adder fetch_many_total;
  Adder fetch_many_records;
  Adder seq_total;
  Adder seq_page_records;
  Adder seq_snapshot_records;
  Adder seq_page_bytes;
  Adder seq_snapshot_bytes;
  Adder seq_refused;
  Adder publish_in_place_bytes;
  Adder publish_copy_bytes;
  std::unique_ptr<PassiveStatus<long>> store_blocks;
  std::unique_ptr<PassiveStatus<long>> store_bytes;
  std::unique_ptr<PassiveStatus<long>> registry_blocks;
  KvVars() {
    publish_total.expose(
        "kv_publish_total",
        "KV blocks published into this node's block store");
    evict_total.expose(
        "kv_evict_total",
        "KV blocks evicted from this node's store (budget pressure, "
        "lease expiry, or explicit withdraw)");
    fetch_total.expose("kv_fetch_total",
                       "KV block fetches served by this node");
    fetch_bytes.expose("kv_fetch_bytes",
                       "payload bytes served by KV block fetches");
    stale_total.expose(
        "kv_stale_total",
        "KV fetches rejected with kv-stale (generation mismatch, lease "
        "lapsed, or evicted block) — each one invalidates a client's "
        "cached lookup");
    register_total.expose("kv_register_total",
                          "KV-block registrations accepted by the "
                          "registry on this node");
    lookup_total.expose("kv_lookup_total",
                        "KV-block lookups answered by the registry on "
                        "this node");
    lookup_miss_total.expose(
        "kv_lookup_miss_total",
        "registry lookups answering kv-miss (unknown block or expired "
        "lease)");
    reg_many_total.expose(
        "kv_reg_many_total",
        "batch registry RPCs (KvReg.RegisterMany/LookupMany/EvictMany) "
        "answered by the registry on this node");
    reg_many_records.expose(
        "kv_reg_many_records",
        "records those batch registry RPCs carried — divide by "
        "kv_reg_many_total for the records per RPC");
    fetch_many_total.expose(
        "kv_fetch_many_total",
        "multi-record fetches (KvClient.fetch_many) this process made: "
        "each keeps its records' Kv.Fetch calls in flight together");
    fetch_many_records.expose(
        "kv_fetch_many_records",
        "records those multi-record fetches asked for");
    seq_total.expose(
        "kv_seq_total",
        "sequence hand-overs (KvClient.fetch_sequence) this process took "
        "in: every record of both kinds landed, the snapshots of the "
        "pages' boundary");
    seq_page_records.expose(
        "kv_seq_page_records",
        "paged-layer records those hand-overs landed");
    seq_snapshot_records.expose(
        "kv_seq_snapshot_records",
        "snapshot-layer records those hand-overs landed");
    seq_page_bytes.expose("kv_seq_page_bytes",
                          "bytes of those paged-layer records");
    seq_snapshot_bytes.expose("kv_seq_snapshot_bytes",
                              "bytes of those snapshot-layer records");
    seq_refused.expose(
        "kv_seq_refused",
        "sequence hand-overs refused whole: a record missing, short, or "
        "a snapshot of another boundary");
    publish_in_place_bytes.expose(
        "kv_publish_in_place_bytes",
        "bytes of pages and sequences published from the block their "
        "device-to-host transfer landed in (kv.publish_page / "
        "publish_sequence): nothing copied on the host");
    publish_copy_bytes.expose(
        "kv_publish_copy_bytes",
        "bytes of pages and sequences copied into the caller's slab to be "
        "published: their source was not memory the store can serve");
    store_blocks = std::make_unique<PassiveStatus<long>>(
        [] { return static_cast<long>(kv_store().count()); });
    store_blocks->expose("kv_store_blocks",
                         "KV blocks currently live in this node's store");
    store_bytes = std::make_unique<PassiveStatus<long>>(
        [] { return static_cast<long>(kv_store().bytes_used()); });
    store_bytes->expose(
        "kv_store_bytes",
        "payload bytes currently held by this node's KV store (bounded "
        "by trpc_kv_store_bytes)");
    registry_blocks = std::make_unique<PassiveStatus<long>>(
        [] { return static_cast<long>(kv_registry().count()); });
    registry_blocks->expose(
        "kv_registry_blocks",
        "KV-block records currently live in the registry on this node");
  }
};

KvVars& kv_vars() {
  static KvVars* v = new KvVars();
  return *v;
}

struct KvPrefixVars {
  Adder publish_total;
  Adder fetch_total;
  Adder put_total;
  Adder match_total;
  Adder match_blocks;
  std::unique_ptr<PassiveStatus<long>> dedup_total;
  std::unique_ptr<PassiveStatus<long>> promote_total;
  std::unique_ptr<PassiveStatus<long>> demote_total;
  std::unique_ptr<PassiveStatus<long>> hot_hit_total;
  std::unique_ptr<PassiveStatus<long>> cold_hit_total;
  std::unique_ptr<PassiveStatus<long>> store_blocks;
  std::unique_ptr<PassiveStatus<long>> store_hot_bytes;
  std::unique_ptr<PassiveStatus<long>> store_cold_bytes;
  std::unique_ptr<PassiveStatus<long>> registry_records;
  KvPrefixVars() {
    publish_total.expose(
        "kv_prefix_publish_total",
        "content-addressed prefix blocks published (fresh bytes copied "
        "into this node's two-tier prefix store)");
    fetch_total.expose("kv_prefix_fetch_total",
                       "prefix-block fetches served by this node (hot "
                       "zero-copy + cold/promoted)");
    put_total.expose(
        "kv_prefix_put_total",
        "prefix-replica registrations accepted by the registry on this "
        "node (one chain key folds N publishers into a replica set)");
    match_total.expose(
        "kv_prefix_match_total",
        "longest-cached-prefix queries answered by the registry on this "
        "node (KvReg.Match walks chain keys until first miss)");
    match_blocks.expose(
        "kv_prefix_match_blocks",
        "prefix blocks matched across all KvReg.Match answers (sum of "
        "matched depths — divide by kv_prefix_match_total for the mean "
        "cached-prefix length)");
    dedup_total = std::make_unique<PassiveStatus<long>>([] {
      return static_cast<long>(
          KvPrefixCounters::read(kv_prefix_counters().dedup));
    });
    dedup_total->expose(
        "kv_prefix_dedup_total",
        "publishes that folded into an existing replica set instead of "
        "minting a new record (fleet-wide content dedup events)");
    promote_total = std::make_unique<PassiveStatus<long>>([] {
      return static_cast<long>(
          KvPrefixCounters::read(kv_prefix_counters().promote));
    });
    promote_total->expose(
        "kv_prefix_promote_total",
        "cold prefix blocks promoted back into registered-RMA pages on "
        "fetch (promotion-on-hit)");
    demote_total = std::make_unique<PassiveStatus<long>>([] {
      return static_cast<long>(
          KvPrefixCounters::read(kv_prefix_counters().demote));
    });
    demote_total->expose(
        "kv_prefix_demote_total",
        "hot prefix blocks spilled to the unregistered cold tier under "
        "trpc_kv_prefix_hot_bytes pressure (demoted, not dropped)");
    hot_hit_total = std::make_unique<PassiveStatus<long>>([] {
      return static_cast<long>(
          KvPrefixCounters::read(kv_prefix_counters().hot_hits));
    });
    hot_hit_total->expose(
        "kv_prefix_hot_hit_total",
        "prefix fetches served zero-copy from hot registered pages");
    cold_hit_total = std::make_unique<PassiveStatus<long>>([] {
      return static_cast<long>(
          KvPrefixCounters::read(kv_prefix_counters().cold_hits));
    });
    cold_hit_total->expose(
        "kv_prefix_cold_hit_total",
        "prefix fetches that found the block demoted in the cold tier "
        "(each one attempts promotion back to hot)");
    store_blocks = std::make_unique<PassiveStatus<long>>(
        [] { return static_cast<long>(kv_store().prefix_count()); });
    store_blocks->expose(
        "kv_prefix_store_blocks",
        "prefix blocks currently live in this node's two-tier store");
    store_hot_bytes = std::make_unique<PassiveStatus<long>>(
        [] { return static_cast<long>(kv_store().prefix_hot_bytes()); });
    store_hot_bytes->expose(
        "kv_prefix_store_hot_bytes",
        "prefix bytes currently pinned in registered-RMA pages (bounded "
        "by trpc_kv_prefix_hot_bytes)");
    store_cold_bytes = std::make_unique<PassiveStatus<long>>(
        [] { return static_cast<long>(kv_store().prefix_cold_bytes()); });
    store_cold_bytes->expose(
        "kv_prefix_store_cold_bytes",
        "prefix bytes currently demoted to the unregistered cold tier "
        "(counted against trpc_kv_store_bytes)");
    registry_records = std::make_unique<PassiveStatus<long>>(
        [] { return static_cast<long>(kv_registry().prefix_count()); });
    registry_records->expose(
        "kv_prefix_registry_records",
        "chain keys with at least one live replica in the registry on "
        "this node");
  }
};

KvPrefixVars& kv_prefix_vars() {
  static KvPrefixVars* v = new KvPrefixVars();
  return *v;
}

void record_kv(uint64_t block_id, uint64_t op, uint64_t len) {
  if (timeline::enabled()) {
    timeline::record(timeline::kKvBlock, block_id,
                     (op << 56) | (len & ((1ull << 56) - 1)));
  }
}

}  // namespace

void kv_ensure_registered() {
  lease_flag();
  store_bytes_flag();
  prefix_hot_bytes_flag();
  prefix_block_tokens_flag();
  kv_vars();
  kv_prefix_vars();
}

void kv_note_fetch_many(uint64_t records) {
  kv_vars().fetch_many_total << 1;
  kv_vars().fetch_many_records << static_cast<int64_t>(records);
}

void kv_note_sequence(uint64_t page_records, uint64_t page_bytes,
                      uint64_t snapshot_records, uint64_t snapshot_bytes,
                      bool handed_over) {
  if (!handed_over) {
    kv_vars().seq_refused << 1;
    return;
  }
  kv_vars().seq_total << 1;
  kv_vars().seq_page_records << static_cast<int64_t>(page_records);
  kv_vars().seq_page_bytes << static_cast<int64_t>(page_bytes);
  kv_vars().seq_snapshot_records << static_cast<int64_t>(snapshot_records);
  kv_vars().seq_snapshot_bytes << static_cast<int64_t>(snapshot_bytes);
}

void kv_note_publish(uint64_t in_place_bytes, uint64_t copy_bytes) {
  kv_vars().publish_in_place_bytes << static_cast<int64_t>(in_place_bytes);
  kv_vars().publish_copy_bytes << static_cast<int64_t>(copy_bytes);
}

KvPrefixCounters& kv_prefix_counters() {
  static KvPrefixCounters* c = new KvPrefixCounters();
  return *c;
}

// ---- content addressing --------------------------------------------------

namespace {

// splitmix64 finalizer: full-avalanche 64-bit mix, deterministic across
// processes and architectures (the dedup contract).
inline uint64_t kv_mix64(uint64_t x) {
  x ^= x >> 33;
  x *= 0xff51afd7ed558ccdull;
  x ^= x >> 33;
  x *= 0xc4ceb9fe1a85ec53ull;
  x ^= x >> 33;
  return x;
}

}  // namespace

void kv_content_hash(const void* data, size_t len, const uint64_t* tokens,
                     size_t ntokens, Key128* out) {
  // Two lanes with distinct seeds and distinct fold ops (xor-mix vs
  // add-mix) so hi/lo fail independently — 128 bits of key space from
  // two 64-bit walks.  Length and token count seed the lanes: a prefix
  // of the bytes can never alias the whole.
  uint64_t h1 = 0x9e3779b97f4a7c15ull ^ kv_mix64(len);
  uint64_t h2 = 0xc2b2ae3d27d4eb4full ^ kv_mix64(ntokens + 0x100);
  const unsigned char* p = static_cast<const unsigned char*>(data);
  size_t i = 0;
  for (; i + 8 <= len; i += 8) {
    uint64_t w;
    memcpy(&w, p + i, 8);
    h1 = kv_mix64(h1 ^ w);
    h2 = kv_mix64(h2 + w);
  }
  if (i < len) {
    uint64_t tail = 0;
    memcpy(&tail, p + i, len - i);
    h1 = kv_mix64(h1 ^ tail);
    h2 = kv_mix64(h2 + tail);
  }
  for (size_t t = 0; t < ntokens; ++t) {
    h1 = kv_mix64(h1 ^ tokens[t]);
    h2 = kv_mix64(h2 + kv_mix64(tokens[t]));
  }
  out->hi = h1;
  out->lo = h2;
}

size_t kv_prefix_chain(const uint64_t* tokens, size_t ntokens,
                       int64_t block_tokens, Key128* keys,
                       size_t max_keys) {
  kv_ensure_registered();
  if (block_tokens <= 0) {
    block_tokens = prefix_block_tokens_flag() != nullptr
                       ? prefix_block_tokens_flag()->int64_value()
                       : 128;
  }
  const size_t bt = static_cast<size_t>(std::max<int64_t>(block_tokens, 1));
  const size_t nblocks = ntokens / bt;
  // Chain seed folds the block size: the same token stream chunked at a
  // different granularity must never alias the same chain keys.
  Key128 prev;
  prev.hi = 0x27d4eb2f165667c5ull ^ kv_mix64(bt);
  prev.lo = 0x85ebca77c2b2ae63ull + kv_mix64(bt);
  size_t written = 0;
  for (size_t b = 0; b < nblocks && written < max_keys; ++b) {
    uint64_t h1 = prev.hi;
    uint64_t h2 = prev.lo;
    for (size_t t = b * bt; t < (b + 1) * bt; ++t) {
      h1 = kv_mix64(h1 ^ tokens[t]);
      h2 = kv_mix64(h2 + kv_mix64(tokens[t] ^ 0x94d049bb133111ebull));
    }
    keys[written].hi = h1;
    keys[written].lo = h2;
    prev = keys[written];
    ++written;
  }
  return written;
}

// ---- KvStore -------------------------------------------------------------

KvStore& kv_store() {
  static KvStore* s = new KvStore();
  return *s;
}

void KvStore::evict_locked(uint64_t block_id, bool count_var) {
  auto it = blocks_.find(block_id);
  if (it == blocks_.end()) {
    return;
  }
  tombstones_[block_id] = it->second.meta.generation;
  bytes_ -= it->second.meta.len;
  record_kv(block_id, kKvOpEvict, it->second.meta.len);
  blocks_.erase(it);
  if (count_var) {
    kv_vars().evict_total << 1;
  }
}

int KvStore::publish(uint64_t block_id, const void* data, size_t len,
                     int64_t lease_ms, KvBlockMeta* out,
                     uint64_t min_generation) {
  kv_ensure_registered();
  if (data == nullptr || len == 0) {
    return -1;
  }
  uint64_t rkey = 0;
  uint64_t off = 0;
  std::shared_ptr<RmaMapping> map =
      rma_pin_exportable(data, len, &rkey, &off);
  if (map == nullptr) {
    return -1;  // not registered memory: the store serves zero-copy only
  }
  const uint64_t budget = static_cast<uint64_t>(std::max<int64_t>(
      store_bytes_flag() != nullptr ? store_bytes_flag()->int64_value()
                                    : (1ll << 30),
      1));
  if (len > budget) {
    return -1;  // cannot fit even an empty store
  }
  const int64_t now = monotonic_time_us();
  std::lock_guard<std::mutex> g(mu_);
  auto it = blocks_.find(block_id);
  if (it != blocks_.end()) {
    if (it->second.deadline_us > now) {
      return kEKvExists;  // live block: ownership is exclusive
    }
    evict_locked(block_id, /*count_var=*/true);  // lapsed: fold to tombstone
  }
  // Budget pressure: evict expired leases first, then LRU by touch_seq.
  while (bytes_ + len > budget && !blocks_.empty()) {
    uint64_t victim = 0;
    uint64_t oldest_touch = std::numeric_limits<uint64_t>::max();
    bool found_expired = false;
    for (const auto& [id, b] : blocks_) {
      if (b.deadline_us <= now) {
        victim = id;
        found_expired = true;
        break;
      }
      if (b.touch_seq < oldest_touch) {
        oldest_touch = b.touch_seq;
        victim = id;
      }
    }
    (void)found_expired;
    evict_locked(victim, /*count_var=*/true);
  }
  Block b;
  b.meta.block_id = block_id;
  // min_generation: a hot-restart successor continues the DEAD pid's
  // sequence (its own tombstones start empty) by flooring at
  // last-known-gen + 1, so the registry's zombie fence accepts the
  // takeover and old cached records fail kv-stale into a re-resolve.
  b.meta.generation =
      std::max(tombstones_[block_id] + 1, min_generation);
  tombstones_[block_id] = b.meta.generation;
  b.meta.rkey = rkey;
  b.meta.off = off;
  b.meta.len = len;
  b.data = static_cast<const char*>(data);
  b.map = std::move(map);
  b.deadline_us = effective_lease_us(lease_ms);
  b.touch_seq = ++touch_counter_;
  bytes_ += len;
  if (out != nullptr) {
    *out = b.meta;
  }
  record_kv(block_id, kKvOpPublish, len);
  blocks_[block_id] = std::move(b);
  kv_vars().publish_total << 1;
  return 0;
}

int KvStore::withdraw(uint64_t block_id) {
  std::lock_guard<std::mutex> g(mu_);
  if (blocks_.find(block_id) == blocks_.end()) {
    return kEKvMiss;
  }
  evict_locked(block_id, /*count_var=*/true);
  return 0;
}

size_t KvStore::withdraw_all() {
  std::lock_guard<std::mutex> g(mu_);
  size_t n = 0;
  while (!blocks_.empty()) {
    evict_locked(blocks_.begin()->first, /*count_var=*/true);
    ++n;
  }
  // Drain covers the prefix tier too: every cached prefix block
  // tombstones, so a decode side holding this node's replica records
  // gets kv-stale and fails over to another replica (or re-publishes) —
  // never bytes from a dying pid.
  while (!prefix_blocks_.empty()) {
    evict_prefix_locked(prefix_blocks_.begin()->first);
    ++n;
  }
  return n;
}

int KvStore::renew(uint64_t block_id, int64_t lease_ms) {
  std::lock_guard<std::mutex> g(mu_);
  auto it = blocks_.find(block_id);
  if (it == blocks_.end()) {
    return kEKvMiss;
  }
  it->second.deadline_us = effective_lease_us(lease_ms);
  return 0;
}

namespace {
// Deleter context for a served block: co-owns the region mapping so the
// bytes stay mapped until the response's last IOBuf reference drops
// (send queues, rma rails, a late cancel) — rma_free's munmap defers.
struct KvServeCtx {
  std::shared_ptr<RmaMapping> map;
};
void kv_serve_deleter(void*, void* vctx) {
  delete static_cast<KvServeCtx*>(vctx);
}
}  // namespace

int KvStore::fetch(uint64_t block_id, uint64_t expected_gen, IOBuf* out) {
  std::lock_guard<std::mutex> g(mu_);
  auto it = blocks_.find(block_id);
  const int64_t now = monotonic_time_us();
  if (it == blocks_.end() || it->second.deadline_us <= now) {
    if (it != blocks_.end()) {
      // Lease lapsed: fold to a tombstone NOW — serve time is the
      // validity decision point, so a fetch racing the expiry can
      // never admit the stale bytes.
      evict_locked(block_id, /*count_var=*/true);
    }
    const bool known = tombstones_.find(block_id) != tombstones_.end();
    if (known) {
      kv_vars().stale_total << 1;
      record_kv(block_id, kKvOpStale, 0);
      return kEKvStale;
    }
    return kEKvMiss;
  }
  Block& b = it->second;
  if (b.meta.generation != expected_gen) {
    kv_vars().stale_total << 1;
    record_kv(block_id, kKvOpStale, b.meta.len);
    return kEKvStale;
  }
  b.touch_seq = ++touch_counter_;
  auto* ctx = new KvServeCtx{b.map};
  out->append_user_data(const_cast<char*>(b.data), b.meta.len,
                        &kv_serve_deleter, ctx);
  kv_vars().fetch_total << 1;
  kv_vars().fetch_bytes << static_cast<int64_t>(b.meta.len);
  record_kv(block_id, kKvOpServe, b.meta.len);
  return 0;
}

int KvStore::pin(uint64_t block_id, uint64_t expected_gen,
                 const char** data, uint64_t* len,
                 std::shared_ptr<RmaMapping>* map, uint64_t* gen_out) {
  std::lock_guard<std::mutex> g(mu_);
  auto it = blocks_.find(block_id);
  const int64_t now = monotonic_time_us();
  if (it == blocks_.end() || it->second.deadline_us <= now) {
    if (it != blocks_.end()) {
      evict_locked(block_id, /*count_var=*/true);  // serve-time validity
    }
    return tombstones_.find(block_id) != tombstones_.end() ? kEKvStale
                                                           : kEKvMiss;
  }
  Block& b = it->second;
  if (expected_gen != 0 && b.meta.generation != expected_gen) {
    return kEKvStale;
  }
  b.touch_seq = ++touch_counter_;
  if (data != nullptr) {
    *data = b.data;
  }
  if (len != nullptr) {
    *len = b.meta.len;
  }
  if (map != nullptr) {
    *map = b.map;
  }
  if (gen_out != nullptr) {
    *gen_out = b.meta.generation;
  }
  return 0;
}

size_t KvStore::count() {
  std::lock_guard<std::mutex> g(mu_);
  return blocks_.size();
}

uint64_t KvStore::bytes_used() {
  std::lock_guard<std::mutex> g(mu_);
  return bytes_;
}

void KvStore::clear() {
  std::lock_guard<std::mutex> g(mu_);
  blocks_.clear();
  tombstones_.clear();
  bytes_ = 0;
  for (auto& [hash, b] : prefix_blocks_) {
    if (b.hot && b.hot_data != nullptr) {
      b.map.reset();
      rma_free(b.hot_data);
    }
  }
  prefix_blocks_.clear();
  prefix_tombstones_.clear();
  prefix_hot_bytes_ = 0;
  prefix_cold_bytes_ = 0;
}

// ---- KvStore prefix tier (two-tier content-addressed store) --------------

void KvStore::demote_locked(PrefixBlock* b) {
  if (!b->hot) {
    return;
  }
  // Copy out FIRST, then release the pages: any in-flight serve holds
  // its own mapping reference (KvServeCtx), so rma_free's munmap defers
  // past it — the demote is invisible to readers mid-response.
  b->cold.assign(b->hot_data, b->meta.len);
  b->map.reset();
  rma_free(b->hot_data);
  b->hot_data = nullptr;
  b->meta.rkey = 0;
  b->meta.off = 0;
  b->hot = false;
  prefix_hot_bytes_ -= b->meta.len;
  prefix_cold_bytes_ += b->meta.len;
  kv_prefix_counters().bump(kv_prefix_counters().demote);
  record_kv(b->meta.hash.lo, kKvOpDemote, b->meta.len);
}

void KvStore::evict_prefix_locked(const Key128& hash) {
  auto it = prefix_blocks_.find(hash);
  if (it == prefix_blocks_.end()) {
    return;
  }
  PrefixBlock& b = it->second;
  prefix_tombstones_[hash] = b.meta.generation;
  if (b.hot) {
    prefix_hot_bytes_ -= b.meta.len;
    b.map.reset();
    rma_free(b.hot_data);
  } else {
    prefix_cold_bytes_ -= b.meta.len;
  }
  record_kv(hash.lo, kKvOpEvict, b.meta.len);
  kv_vars().evict_total << 1;
  prefix_blocks_.erase(it);
}

bool KvStore::fit_hot_locked(uint64_t incoming, uint64_t hot_budget) {
  if (incoming > hot_budget) {
    return false;  // publishes straight to cold
  }
  // Hot pressure DEMOTES (never drops): the bytes stay serveable, they
  // just lose the zero-copy fast path until a hit promotes them back.
  while (prefix_hot_bytes_ + incoming > hot_budget) {
    PrefixBlock* victim = nullptr;
    uint64_t oldest_touch = std::numeric_limits<uint64_t>::max();
    for (auto& [hash, b] : prefix_blocks_) {
      if (b.hot && b.touch_seq < oldest_touch) {
        oldest_touch = b.touch_seq;
        victim = &b;
      }
    }
    if (victim == nullptr) {
      return false;  // nothing left to demote yet still over: can't fit
    }
    demote_locked(victim);
  }
  return true;
}

int KvStore::publish_prefix(const Key128& key, uint32_t depth,
                            const void* data, size_t len,
                            const uint64_t* tokens, size_t ntokens,
                            int64_t lease_ms, KvPrefixMeta* out,
                            uint64_t min_generation) {
  kv_ensure_registered();
  if (key.zero() || data == nullptr || len == 0) {
    return -1;
  }
  Key128 hash;
  kv_content_hash(data, len, tokens, ntokens, &hash);
  const uint64_t total_budget = static_cast<uint64_t>(std::max<int64_t>(
      store_bytes_flag() != nullptr ? store_bytes_flag()->int64_value()
                                    : (1ll << 30),
      1));
  const uint64_t hot_budget = static_cast<uint64_t>(std::max<int64_t>(
      prefix_hot_bytes_flag() != nullptr
          ? prefix_hot_bytes_flag()->int64_value()
          : (256ll << 20),
      1));
  if (len > total_budget) {
    return -1;
  }
  const int64_t now = monotonic_time_us();
  std::lock_guard<std::mutex> g(mu_);
  auto it = prefix_blocks_.find(hash);
  if (it != prefix_blocks_.end()) {
    if (it->second.deadline_us > now) {
      // Live block with identical content: THE cache-hit path.  The
      // lease renews and the record echoes, but kEKvExists tells the
      // caller these bytes did NOT need recomputing/copying.
      PrefixBlock& b = it->second;
      b.deadline_us = effective_lease_us(lease_ms);
      b.touch_seq = ++touch_counter_;
      if (out != nullptr) {
        *out = b.meta;
      }
      return kEKvExists;
    }
    evict_prefix_locked(hash);  // lapsed: fold to tombstone, re-admit
  }
  // Total-store pressure (blocks + hot + cold vs trpc_kv_store_bytes):
  // expired blocks drop first, then LRU cold, then LRU hot — dropping
  // always tombstones so evicted fetches answer kv-stale.
  while (bytes_ + prefix_hot_bytes_ + prefix_cold_bytes_ + len >
             total_budget &&
         !prefix_blocks_.empty()) {
    Key128 victim;
    uint64_t oldest_cold = std::numeric_limits<uint64_t>::max();
    uint64_t oldest_hot = std::numeric_limits<uint64_t>::max();
    Key128 victim_cold;
    Key128 victim_hot;
    bool found = false;
    for (const auto& [h, b] : prefix_blocks_) {
      if (b.deadline_us <= now) {
        victim = h;
        found = true;
        break;
      }
      if (!b.hot && b.touch_seq < oldest_cold) {
        oldest_cold = b.touch_seq;
        victim_cold = h;
      }
      if (b.hot && b.touch_seq < oldest_hot) {
        oldest_hot = b.touch_seq;
        victim_hot = h;
      }
    }
    if (!found) {
      victim = oldest_cold != std::numeric_limits<uint64_t>::max()
                   ? victim_cold
                   : victim_hot;
    }
    evict_prefix_locked(victim);
  }
  if (bytes_ + prefix_hot_bytes_ + prefix_cold_bytes_ + len >
      total_budget) {
    return -1;  // regular blocks own the budget: don't evict them here
  }
  PrefixBlock b;
  b.meta.key = key;
  b.meta.hash = hash;
  b.meta.generation =
      std::max(prefix_tombstones_[hash] + 1, min_generation);
  prefix_tombstones_[hash] = b.meta.generation;
  b.meta.len = len;
  b.meta.depth = depth;
  b.deadline_us = effective_lease_us(lease_ms);
  b.touch_seq = ++touch_counter_;
  // Hot placement: store-owned registered pages so fetches serve
  // zero-copy.  Falls to the cold tier when the block outsizes the hot
  // budget or registered memory is exhausted — cold still serves.
  bool placed_hot = false;
  if (fit_hot_locked(len, hot_budget)) {
    uint64_t rkey = 0;
    void* pages = rma_alloc(len, &rkey);
    if (pages != nullptr) {
      memcpy(pages, data, len);
      uint64_t pin_rkey = 0;
      uint64_t pin_off = 0;
      b.map = rma_pin_exportable(pages, len, &pin_rkey, &pin_off);
      if (b.map != nullptr) {
        b.hot_data = static_cast<char*>(pages);
        b.meta.rkey = pin_rkey;
        b.meta.off = pin_off;
        b.hot = true;
        prefix_hot_bytes_ += len;
        placed_hot = true;
      } else {
        rma_free(pages);
      }
    }
  }
  if (!placed_hot) {
    b.cold.assign(static_cast<const char*>(data), len);
    prefix_cold_bytes_ += len;
  }
  if (out != nullptr) {
    *out = b.meta;
  }
  record_kv(hash.lo, kKvOpPublish, len);
  prefix_blocks_[hash] = std::move(b);
  kv_prefix_vars().publish_total << 1;
  return 0;
}

int KvStore::fetch_prefix(const Key128& hash, uint64_t expected_gen,
                          IOBuf* out) {
  kv_ensure_registered();
  const int64_t now = monotonic_time_us();
  std::lock_guard<std::mutex> g(mu_);
  auto it = prefix_blocks_.find(hash);
  if (it == prefix_blocks_.end() || it->second.deadline_us <= now) {
    if (it != prefix_blocks_.end()) {
      evict_prefix_locked(hash);  // serve-time validity, as fetch()
    }
    if (prefix_tombstones_.find(hash) != prefix_tombstones_.end()) {
      kv_vars().stale_total << 1;
      record_kv(hash.lo, kKvOpStale, 0);
      return kEKvStale;
    }
    return kEKvMiss;
  }
  PrefixBlock& b = it->second;
  // expected_gen 0 accepts any live generation (content addressing
  // already names the exact bytes; the generation only fences zombies).
  if (expected_gen != 0 && b.meta.generation != expected_gen) {
    kv_vars().stale_total << 1;
    record_kv(hash.lo, kKvOpStale, b.meta.len);
    return kEKvStale;
  }
  b.touch_seq = ++touch_counter_;
  if (!b.hot) {
    kv_prefix_counters().bump(kv_prefix_counters().cold_hits);
    // Promotion-on-hit: copy back into registered pages so the NEXT
    // fetch is zero-copy again.  Failure to promote (registered memory
    // exhausted) still serves — a plain copy of the cold bytes.
    const uint64_t hot_budget = static_cast<uint64_t>(std::max<int64_t>(
        prefix_hot_bytes_flag() != nullptr
            ? prefix_hot_bytes_flag()->int64_value()
            : (256ll << 20),
        1));
    bool promoted = false;
    if (fit_hot_locked(b.meta.len, hot_budget)) {
      uint64_t rkey = 0;
      void* pages = rma_alloc(b.meta.len, &rkey);
      if (pages != nullptr) {
        memcpy(pages, b.cold.data(), b.meta.len);
        uint64_t pin_rkey = 0;
        uint64_t pin_off = 0;
        std::shared_ptr<RmaMapping> map =
            rma_pin_exportable(pages, b.meta.len, &pin_rkey, &pin_off);
        if (map != nullptr) {
          b.hot_data = static_cast<char*>(pages);
          b.map = std::move(map);
          b.meta.rkey = pin_rkey;
          b.meta.off = pin_off;
          b.hot = true;
          prefix_hot_bytes_ += b.meta.len;
          prefix_cold_bytes_ -= b.meta.len;
          b.cold.clear();
          b.cold.shrink_to_fit();
          kv_prefix_counters().bump(kv_prefix_counters().promote);
          record_kv(hash.lo, kKvOpPromote, b.meta.len);
          promoted = true;
        } else {
          rma_free(pages);
        }
      }
    }
    if (!promoted) {
      out->append(b.cold.data(), b.meta.len);
      kv_prefix_vars().fetch_total << 1;
      kv_vars().fetch_bytes << static_cast<int64_t>(b.meta.len);
      record_kv(hash.lo, kKvOpServe, b.meta.len);
      return 0;
    }
  } else {
    kv_prefix_counters().bump(kv_prefix_counters().hot_hits);
  }
  auto* ctx = new KvServeCtx{b.map};
  out->append_user_data(b.hot_data, b.meta.len, &kv_serve_deleter, ctx);
  kv_prefix_vars().fetch_total << 1;
  kv_vars().fetch_bytes << static_cast<int64_t>(b.meta.len);
  record_kv(hash.lo, kKvOpServe, b.meta.len);
  return 0;
}

int KvStore::withdraw_prefix(const Key128& hash) {
  std::lock_guard<std::mutex> g(mu_);
  if (prefix_blocks_.find(hash) == prefix_blocks_.end()) {
    return kEKvMiss;
  }
  evict_prefix_locked(hash);
  return 0;
}

size_t KvStore::prefix_count() {
  std::lock_guard<std::mutex> g(mu_);
  return prefix_blocks_.size();
}

uint64_t KvStore::prefix_hot_bytes() {
  std::lock_guard<std::mutex> g(mu_);
  return prefix_hot_bytes_;
}

uint64_t KvStore::prefix_cold_bytes() {
  std::lock_guard<std::mutex> g(mu_);
  return prefix_cold_bytes_;
}

// ---- KvRegistry ----------------------------------------------------------

KvRegistry& kv_registry() {
  static KvRegistry* r = new KvRegistry();
  return *r;
}

int KvRegistry::do_register(const KvBlockMeta& meta, int64_t lease_ms,
                            uint64_t* gen_out) {
  kv_ensure_registered();
  if (meta.block_id == 0 || meta.len == 0 || meta.generation == 0) {
    return kEKvStale;  // generation 0 is never minted
  }
  const int64_t now = monotonic_time_us();
  std::lock_guard<std::mutex> g(mu_);
  auto it = entries_.find(meta.block_id);
  if (it != entries_.end()) {
    if (it->second.deadline_us <= now) {
      entries_.erase(it);  // lapsed: prune, fall through to admit
    } else if (meta.generation > it->second.meta.generation) {
      entries_.erase(it);  // re-publish with a newer generation replaces
    } else if (meta.generation == it->second.meta.generation) {
      return kEKvExists;  // double-register: ownership is exclusive
    } else {
      return kEKvStale;  // zombie publisher re-offering an old generation
    }
  }
  if (last_gen_[meta.block_id] != 0 &&
      meta.generation < last_gen_[meta.block_id]) {
    return kEKvStale;  // zombie publisher re-offering an old generation
  }
  Entry e;
  e.meta = meta;
  e.deadline_us = effective_lease_us(lease_ms);
  last_gen_[meta.block_id] =
      std::max(last_gen_[meta.block_id], meta.generation);
  entries_[meta.block_id] = e;
  if (gen_out != nullptr) {
    *gen_out = meta.generation;
  }
  kv_vars().register_total << 1;
  return 0;
}

int KvRegistry::lookup(uint64_t block_id, KvBlockMeta* out,
                       int64_t* lease_left_ms) {
  const int64_t now = monotonic_time_us();
  std::lock_guard<std::mutex> g(mu_);
  kv_vars().lookup_total << 1;
  auto it = entries_.find(block_id);
  if (it == entries_.end() || it->second.deadline_us <= now) {
    if (it != entries_.end()) {
      entries_.erase(it);  // lazy lease pruning
    }
    kv_vars().lookup_miss_total << 1;
    return kEKvMiss;
  }
  if (out != nullptr) {
    *out = it->second.meta;
  }
  if (lease_left_ms != nullptr) {
    *lease_left_ms = (it->second.deadline_us - now) / 1000;
  }
  return 0;
}

int KvRegistry::evict(uint64_t block_id, uint64_t* gen_out) {
  std::lock_guard<std::mutex> g(mu_);
  auto it = entries_.find(block_id);
  if (it == entries_.end()) {
    return kEKvMiss;
  }
  if (gen_out != nullptr) {
    *gen_out = it->second.meta.generation;
  }
  entries_.erase(it);
  return 0;
}

int KvRegistry::renew(uint64_t block_id, int64_t lease_ms,
                      uint64_t* gen_out) {
  const int64_t now = monotonic_time_us();
  std::lock_guard<std::mutex> g(mu_);
  auto it = entries_.find(block_id);
  if (it == entries_.end() || it->second.deadline_us <= now) {
    if (it != entries_.end()) {
      entries_.erase(it);
    }
    return kEKvMiss;  // a lapsed lease cannot be revived, only re-registered
  }
  it->second.deadline_us = effective_lease_us(lease_ms);
  if (gen_out != nullptr) {
    *gen_out = it->second.meta.generation;
  }
  return 0;
}

// ---- KvRegistry prefix records (content-addressed replica sets) ----------

int KvRegistry::put_prefix(const KvPrefixMeta& meta, int64_t lease_ms,
                           uint64_t* gen_out) {
  kv_ensure_registered();
  if (meta.key.zero() || meta.hash.zero() || meta.len == 0 ||
      meta.generation == 0 || meta.node[0] == '\0') {
    return kEKvStale;  // generation 0 is never minted; anonymous
                       // replicas can't be fetched from
  }
  const int64_t now = monotonic_time_us();
  std::lock_guard<std::mutex> g(mu_);
  auto it = prefix_.find(meta.key);
  if (it == prefix_.end()) {
    PrefixEntry e;
    e.hash = meta.hash;
    e.depth = meta.depth;
    e.len = meta.len;
    it = prefix_.emplace(meta.key, std::move(e)).first;
  } else if (it->second.hash != meta.hash) {
    // Same chain key, different bytes: token/content divergence (a
    // nondeterministic prefill, or corruption).  Never silently alias —
    // the publisher must treat its bytes as uncacheable.
    return kEKvStale;
  }
  PrefixEntry& e = it->second;
  // Lazy lease pruning (the fence map survives — pruning a replica
  // must not reopen the zombie window).
  e.replicas.erase(
      std::remove_if(e.replicas.begin(), e.replicas.end(),
                     [now](const PrefixReplica& r) {
                       return r.deadline_us <= now;
                     }),
      e.replicas.end());
  const std::string node(meta.node);
  uint64_t& fence = e.last_gen[node];
  if (meta.generation < fence) {
    return kEKvStale;  // zombie publisher re-offering an old generation
  }
  for (PrefixReplica& r : e.replicas) {
    if (node == r.meta.node) {
      if (meta.generation == r.meta.generation) {
        // Idempotent re-register: content addressing makes this the
        // common path (every cache hit re-offers) — renew the lease.
        r.deadline_us = effective_lease_us(lease_ms);
        if (gen_out != nullptr) {
          *gen_out = meta.generation;
        }
        return kEKvExists;
      }
      r.meta = meta;  // newer generation replaces in place
      r.deadline_us = effective_lease_us(lease_ms);
      fence = meta.generation;
      if (gen_out != nullptr) {
        *gen_out = meta.generation;
      }
      kv_prefix_vars().put_total << 1;
      return 0;
    }
  }
  const bool folded = !e.replicas.empty();
  PrefixReplica r;
  r.meta = meta;
  r.deadline_us = effective_lease_us(lease_ms);
  e.replicas.push_back(std::move(r));
  fence = std::max(fence, meta.generation);
  if (folded) {
    // N publishers, one record: the fleet-wide dedup event.
    kv_prefix_counters().bump(kv_prefix_counters().dedup);
  }
  kv_prefix_vars().put_total << 1;
  if (gen_out != nullptr) {
    *gen_out = meta.generation;
  }
  return 0;
}

size_t KvRegistry::match(const Key128* keys, size_t n,
                         std::vector<KvPrefixMeta>* out,
                         std::vector<int64_t>* lease_out) {
  kv_ensure_registered();
  const int64_t now = monotonic_time_us();
  std::lock_guard<std::mutex> g(mu_);
  kv_prefix_vars().match_total << 1;
  size_t matched = 0;
  for (size_t i = 0; i < n; ++i) {
    auto it = prefix_.find(keys[i]);
    if (it == prefix_.end()) {
      break;  // first miss ends the longest cached prefix
    }
    PrefixEntry& e = it->second;
    e.replicas.erase(
        std::remove_if(e.replicas.begin(), e.replicas.end(),
                       [now](const PrefixReplica& r) {
                         return r.deadline_us <= now;
                       }),
        e.replicas.end());
    if (e.replicas.empty()) {
      break;  // all replicas lapsed: the chain stops here
    }
    for (const PrefixReplica& r : e.replicas) {
      if (out != nullptr) {
        out->push_back(r.meta);
      }
      if (lease_out != nullptr) {
        lease_out->push_back((r.deadline_us - now) / 1000);
      }
    }
    ++matched;
  }
  kv_prefix_vars().match_blocks << static_cast<int64_t>(matched);
  return matched;
}

int KvRegistry::evict_prefix(const Key128& key, const char* node) {
  std::lock_guard<std::mutex> g(mu_);
  auto it = prefix_.find(key);
  if (it == prefix_.end()) {
    return kEKvMiss;
  }
  std::vector<PrefixReplica>& reps = it->second.replicas;
  for (auto r = reps.begin(); r != reps.end(); ++r) {
    if (node != nullptr && strncmp(r->meta.node, node,
                                   sizeof(r->meta.node)) == 0) {
      reps.erase(r);
      return 0;  // the fence map stays: no zombie window reopens
    }
  }
  return kEKvMiss;
}

size_t KvRegistry::prefix_count() {
  const int64_t now = monotonic_time_us();
  std::lock_guard<std::mutex> g(mu_);
  size_t n = 0;
  for (const auto& [key, e] : prefix_) {
    for (const PrefixReplica& r : e.replicas) {
      if (r.deadline_us > now) {
        ++n;
        break;
      }
    }
  }
  return n;
}

size_t KvRegistry::prefix_replicas() {
  const int64_t now = monotonic_time_us();
  std::lock_guard<std::mutex> g(mu_);
  size_t n = 0;
  for (const auto& [key, e] : prefix_) {
    for (const PrefixReplica& r : e.replicas) {
      if (r.deadline_us > now) {
        ++n;
      }
    }
  }
  return n;
}

size_t KvRegistry::count() {
  std::lock_guard<std::mutex> g(mu_);
  return entries_.size();
}

void KvRegistry::clear() {
  std::lock_guard<std::mutex> g(mu_);
  entries_.clear();
  last_gen_.clear();
  prefix_.clear();
}

// ---- native handlers -----------------------------------------------------

namespace {

bool parse_wire(const IOBuf& req, KvWire* w) {
  if (req.size() < sizeof(KvWire)) {
    return false;
  }
  req.copy_to(w, sizeof(KvWire));
  w->node[sizeof(w->node) - 1] = '\0';
  return true;
}

bool parse_prefix_wire(const IOBuf& req, KvPrefixWire* w) {
  if (req.size() < sizeof(KvPrefixWire)) {
    return false;
  }
  req.copy_to(w, sizeof(KvPrefixWire));
  w->node[sizeof(w->node) - 1] = '\0';
  return true;
}

void prefix_meta_to_wire(const KvPrefixMeta& m, int64_t lease_ms,
                         KvPrefixWire* w) {
  memset(w, 0, sizeof(*w));
  w->key_hi = m.key.hi;
  w->key_lo = m.key.lo;
  w->hash_hi = m.hash.hi;
  w->hash_lo = m.hash.lo;
  w->generation = m.generation;
  w->rkey = m.rkey;
  w->off = m.off;
  w->len = m.len;
  w->lease_ms = lease_ms;
  w->depth = m.depth;
  memcpy(w->node, m.node, sizeof(w->node));
}

KvBlockMeta wire_to_meta(const KvWire& w) {
  KvBlockMeta m;
  m.block_id = w.block_id;
  m.generation = w.generation;
  m.rkey = w.rkey;
  m.off = w.off;
  m.len = w.len;
  memcpy(m.node, w.node, sizeof(m.node));
  return m;
}

void meta_to_wire(const KvBlockMeta& m, int64_t lease_ms, KvWire* w) {
  memset(w, 0, sizeof(*w));
  w->block_id = m.block_id;
  w->generation = m.generation;
  w->rkey = m.rkey;
  w->off = m.off;
  w->len = m.len;
  w->lease_ms = lease_ms;
  memcpy(w->node, m.node, sizeof(w->node));
}

// The body of the three batch handlers: a u64 count then that many
// KvWire in, the count then one zero-initialised Entry per wire out,
// filled by `one` in request order.  An entry's failure is its own
// status; only a request that does not parse fails the call.
template <typename Entry, typename One>
void serve_many(Controller* cntl, const IOBuf& req, IOBuf* resp,
                const char* what, One one) {
  uint64_t n = 0;
  if (req.size() >= sizeof(n)) {
    req.copy_to(&n, sizeof(n));
  }
  if (n == 0 || n > kKvManyMax ||
      req.size() < sizeof(n) + n * sizeof(KvWire)) {
    cntl->SetFailed(EINVAL, std::string("bad ") + what + " record count");
    return;
  }
  std::vector<KvWire> wires(n);
  req.copy_to(wires.data(), n * sizeof(KvWire), sizeof(n));
  std::vector<Entry> out(n);
  for (uint64_t i = 0; i < n; ++i) {
    wires[i].node[sizeof(wires[i].node) - 1] = '\0';
    one(wires[i], &out[i]);
  }
  resp->append(&n, sizeof(n));
  resp->append(out.data(), n * sizeof(Entry));
  kv_vars().reg_many_total << 1;
  kv_vars().reg_many_records << static_cast<int64_t>(n);
}

void respond_gen(IOBuf* resp, uint64_t gen) {
  resp->append(&gen, sizeof(gen));
}

void fail_kv(Controller* cntl, int code, const char* what) {
  const char* why = code == kEKvMiss     ? "kv-miss"
                    : code == kEKvStale  ? "kv-stale"
                    : code == kEKvExists ? "kv-exists"
                                         : "kv-error";
  cntl->SetFailed(code, std::string(why) + ": " + what);
}

}  // namespace

int kv_attach_store(Server* s) {
  kv_ensure_registered();
  // Drain hook (Server::Drain, ISSUE 12): tombstone every published
  // block before the listener handoff — a decode cache holding this
  // node's records fails kv-stale, invalidates, and re-resolves through
  // the registry instead of ever fetching from a dying pid.
  s->add_drain_hook([] { kv_store().withdraw_all(); });
  const int rc_fetch = s->RegisterMethod(
      kKvFetchMethod, [](Controller* cntl, const IOBuf& req, IOBuf* resp,
                         Closure done) {
        KvWire w;
        if (!parse_wire(req, &w)) {
          cntl->SetFailed(EINVAL, "bad Kv.Fetch request");
          done();
          return;
        }
        if (cntl->remaining_us() == 0) {
          // The puller's budget died between dispatch and here (the
          // pre-dispatch shed catches arrival-expired requests; this
          // catches a budget that expired while other fetches queued
          // ahead): never pin megabytes of block pages for a response
          // the decode side has already abandoned.
          cntl->SetFailed(kEDeadlineExpired,
                          "deadline expired before block fetch");
          done();
          return;
        }
        const int rc = kv_store().fetch(w.block_id, w.generation, resp);
        if (rc != 0) {
          fail_kv(cntl, rc, "fetch");
        }
        done();
      });
  const int rc_prefix = s->RegisterMethod(
      kKvPrefixFetchMethod, [](Controller* cntl, const IOBuf& req,
                               IOBuf* resp, Closure done) {
        KvPrefixWire w;
        if (!parse_prefix_wire(req, &w)) {
          cntl->SetFailed(EINVAL, "bad Kv.FetchPrefix request");
          done();
          return;
        }
        if (cntl->remaining_us() == 0) {
          // Same shed as Kv.Fetch: never pin block pages for a response
          // whose budget already died in the queue.
          cntl->SetFailed(kEDeadlineExpired,
                          "deadline expired before prefix fetch");
          done();
          return;
        }
        Key128 hash;
        hash.hi = w.hash_hi;
        hash.lo = w.hash_lo;
        const int rc = kv_store().fetch_prefix(hash, w.generation, resp);
        if (rc != 0) {
          fail_kv(cntl, rc, "fetch-prefix");
        }
        done();
      });
  return rc_fetch == 0 && rc_prefix == 0 ? 0 : -1;
}

int kv_attach_registry(Server* s) {
  kv_ensure_registered();
  int rcs[9] = {0, 0, 0, 0, 0, 0, 0, 0, 0};
  rcs[0] = s->RegisterMethod(
      kKvRegisterMethod, [](Controller* cntl, const IOBuf& req, IOBuf* resp,
                            Closure done) {
        KvWire w;
        if (!parse_wire(req, &w)) {
          cntl->SetFailed(EINVAL, "bad KvReg.Register request");
          done();
          return;
        }
        uint64_t gen = 0;
        const int rc =
            kv_registry().do_register(wire_to_meta(w), w.lease_ms, &gen);
        if (rc != 0) {
          fail_kv(cntl, rc, "register");
        } else {
          respond_gen(resp, gen);
        }
        done();
      });
  rcs[1] = s->RegisterMethod(
      kKvLookupMethod, [](Controller* cntl, const IOBuf& req, IOBuf* resp,
                          Closure done) {
        KvWire w;
        if (!parse_wire(req, &w)) {
          cntl->SetFailed(EINVAL, "bad KvReg.Lookup request");
          done();
          return;
        }
        KvBlockMeta m;
        int64_t left_ms = 0;
        const int rc = kv_registry().lookup(w.block_id, &m, &left_ms);
        if (rc != 0) {
          fail_kv(cntl, rc, "lookup");
        } else {
          KvWire o;
          meta_to_wire(m, left_ms, &o);
          resp->append(&o, sizeof(o));
        }
        done();
      });
  rcs[2] = s->RegisterMethod(
      kKvEvictMethod, [](Controller* cntl, const IOBuf& req, IOBuf* resp,
                         Closure done) {
        KvWire w;
        if (!parse_wire(req, &w)) {
          cntl->SetFailed(EINVAL, "bad KvReg.Evict request");
          done();
          return;
        }
        uint64_t gen = 0;
        const int rc = kv_registry().evict(w.block_id, &gen);
        if (rc != 0) {
          fail_kv(cntl, rc, "evict");
        } else {
          respond_gen(resp, gen);
        }
        done();
      });
  rcs[3] = s->RegisterMethod(
      kKvRenewMethod, [](Controller* cntl, const IOBuf& req, IOBuf* resp,
                         Closure done) {
        KvWire w;
        if (!parse_wire(req, &w)) {
          cntl->SetFailed(EINVAL, "bad KvReg.Renew request");
          done();
          return;
        }
        uint64_t gen = 0;
        const int rc = kv_registry().renew(w.block_id, w.lease_ms, &gen);
        if (rc != 0) {
          fail_kv(cntl, rc, "renew");
        } else {
          respond_gen(resp, gen);  // the wire contract: one u64 generation
        }
        done();
      });
  rcs[4] = s->RegisterMethod(
      kKvPrefixPutMethod, [](Controller* cntl, const IOBuf& req,
                             IOBuf* resp, Closure done) {
        KvPrefixWire w;
        if (!parse_prefix_wire(req, &w)) {
          cntl->SetFailed(EINVAL, "bad KvReg.PutPrefix request");
          done();
          return;
        }
        KvPrefixMeta m;
        m.key.hi = w.key_hi;
        m.key.lo = w.key_lo;
        m.hash.hi = w.hash_hi;
        m.hash.lo = w.hash_lo;
        m.generation = w.generation;
        m.rkey = w.rkey;
        m.off = w.off;
        m.len = w.len;
        m.depth = w.depth;
        memcpy(m.node, w.node, sizeof(m.node));
        uint64_t gen = 0;
        const int rc = kv_registry().put_prefix(m, w.lease_ms, &gen);
        if (rc != 0) {
          // kEKvExists included: the caller already holds this exact
          // record (idempotent renew) — the Python client maps it to
          // its dedup/cache-hit accounting, not to a failure.
          fail_kv(cntl, rc, "put-prefix");
        } else {
          respond_gen(resp, gen);
        }
        done();
      });
  rcs[5] = s->RegisterMethod(
      kKvPrefixMatchMethod, [](Controller* cntl, const IOBuf& req,
                               IOBuf* resp, Closure done) {
        static_assert(sizeof(Key128) == 16, "Key128 is wire format");
        uint64_t nkeys = 0;
        if (req.size() < sizeof(nkeys)) {
          cntl->SetFailed(EINVAL, "bad KvReg.Match request");
          done();
          return;
        }
        req.copy_to(&nkeys, sizeof(nkeys));
        if (nkeys == 0 || nkeys > 4096 ||
            req.size() < sizeof(nkeys) + nkeys * sizeof(Key128)) {
          cntl->SetFailed(EINVAL, "bad KvReg.Match key count");
          done();
          return;
        }
        std::vector<Key128> keys(nkeys);
        req.copy_to(keys.data(), nkeys * sizeof(Key128), sizeof(nkeys));
        std::vector<KvPrefixMeta> metas;
        std::vector<int64_t> leases;
        kv_registry().match(keys.data(), keys.size(), &metas, &leases);
        // Response: u64 record count, then one KvPrefixWire per live
        // replica, grouped in chain order (lease_ms = remaining ms).
        // Zero records is a valid answer: no cached prefix.
        const uint64_t nrecords = metas.size();
        resp->append(&nrecords, sizeof(nrecords));
        for (size_t i = 0; i < metas.size(); ++i) {
          KvPrefixWire w;
          prefix_meta_to_wire(metas[i], leases[i], &w);
          resp->append(&w, sizeof(w));
        }
        done();
      });
  rcs[6] = s->RegisterMethod(
      kKvRegisterManyMethod, [](Controller* cntl, const IOBuf& req,
                                IOBuf* resp, Closure done) {
        serve_many<KvManyGen>(
            cntl, req, resp, kKvRegisterManyMethod,
            [](const KvWire& w, KvManyGen* o) {
              o->status = kv_registry().do_register(
                  wire_to_meta(w), w.lease_ms, &o->generation);
            });
        done();
      });
  rcs[7] = s->RegisterMethod(
      kKvLookupManyMethod, [](Controller* cntl, const IOBuf& req,
                              IOBuf* resp, Closure done) {
        serve_many<KvManyRecord>(
            cntl, req, resp, kKvLookupManyMethod,
            [](const KvWire& w, KvManyRecord* o) {
              KvBlockMeta m;
              int64_t left_ms = 0;
              o->status = kv_registry().lookup(w.block_id, &m, &left_ms);
              if (o->status == 0) {
                meta_to_wire(m, left_ms, &o->rec);
              }
            });
        done();
      });
  rcs[8] = s->RegisterMethod(
      kKvEvictManyMethod, [](Controller* cntl, const IOBuf& req,
                             IOBuf* resp, Closure done) {
        serve_many<KvManyGen>(
            cntl, req, resp, kKvEvictManyMethod,
            [](const KvWire& w, KvManyGen* o) {
              o->status = kv_registry().evict(w.block_id, &o->generation);
            });
        done();
      });
  for (int rc : rcs) {
    if (rc != 0) {
      return -1;
    }
  }
  return 0;
}

// ---- KvCache -------------------------------------------------------------

namespace {

// One registry RPC carrying a KvWire request; 0 or the call's error code.
int kv_call(Channel* ch, const char* method, const KvWire& w, IOBuf* resp) {
  IOBuf req;
  req.append(&w, sizeof(w));
  Controller cntl;
  ch->CallMethod(method, req, resp, &cntl);
  if (cntl.Failed()) {
    return cntl.error_code() != 0 ? cntl.error_code() : -1;
  }
  return 0;
}

}  // namespace

int KvCache::lookup(uint64_t block_id, KvBlockMeta* out, bool refresh) {
  if (!refresh) {
    std::lock_guard<std::mutex> g(mu_);
    auto it = cache_.find(block_id);
    if (it != cache_.end()) {
      *out = it->second;
      // Relaxed: monotonic stat counter, no ordering carried.
      hits_.fetch_add(1, std::memory_order_relaxed);
      return 0;
    }
  }
  // Relaxed: monotonic stat counter, no ordering carried.
  misses_.fetch_add(1, std::memory_order_relaxed);
  KvWire w;
  memset(&w, 0, sizeof(w));
  w.block_id = block_id;
  IOBuf resp;
  const int rc = kv_call(reg_, kKvLookupMethod, w, &resp);
  if (rc != 0) {
    return rc;
  }
  KvWire o;
  if (!parse_wire(resp, &o)) {
    return -1;
  }
  KvBlockMeta m;
  m.block_id = o.block_id;
  m.generation = o.generation;
  m.rkey = o.rkey;
  m.off = o.off;
  m.len = o.len;
  memcpy(m.node, o.node, sizeof(m.node));
  {
    std::lock_guard<std::mutex> g(mu_);
    cache_[block_id] = m;
  }
  *out = m;
  return 0;
}

void KvCache::invalidate(uint64_t block_id) {
  std::lock_guard<std::mutex> g(mu_);
  cache_.erase(block_id);
}

int KvCache::fetch(Channel* node_ch, uint64_t block_id, IOBuf* out) {
  for (int attempt = 0; attempt < 2; ++attempt) {
    KvBlockMeta m;
    int rc = lookup(block_id, &m, /*refresh=*/attempt > 0);
    if (rc != 0) {
      return rc;
    }
    KvWire w;
    memset(&w, 0, sizeof(w));
    w.block_id = block_id;
    w.generation = m.generation;
    out->clear();
    rc = kv_call(node_ch, kKvFetchMethod, w, out);
    if (rc == 0) {
      return 0;
    }
    if (rc != kEKvStale && rc != kEKvMiss) {
      return rc;  // transport/chaos failure: the record may be fine
    }
    invalidate(block_id);  // generation-checked invalidation, retry once
  }
  return kEKvStale;
}

}  // namespace trpc
