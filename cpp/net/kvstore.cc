#include "net/kvstore.h"

#include <errno.h>
#include <stdlib.h>
#include <string.h>
#include <unistd.h>

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

// The prefix tier's gauges; its counters are KvPrefixCounters' Adders.
struct KvPrefixVars {
  std::unique_ptr<PassiveStatus<long>> store_blocks;
  std::unique_ptr<PassiveStatus<long>> store_hot_bytes;
  std::unique_ptr<PassiveStatus<long>> store_cold_bytes;
  std::unique_ptr<PassiveStatus<long>> registry_records;
  KvPrefixVars() {
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

}  // namespace

void kv_ensure_registered() {
  lease_flag();
  store_bytes_flag();
  prefix_hot_bytes_flag();
  prefix_block_tokens_flag();
  kv_vars();
  kv_prefix_vars();
  kv_prefix_counters();
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

KvPrefixCounters::KvPrefixCounters() {
  publish_total.expose(
      "kv_prefix_publish_total",
      "content-addressed prefix blocks admitted into this node's two-tier "
      "prefix store (fresh bytes; a re-offer of live content is "
      "kv_prefix_publish_renewed)");
  publish_bytes.expose("kv_prefix_publish_bytes",
                       "bytes of those admitted prefix blocks");
  publish_copy_bytes.expose(
      "kv_prefix_publish_copy_bytes",
      "bytes of admitted prefix blocks copied once into store-owned "
      "pages: their source was not a landing block the store may keep");
  publish_in_place_bytes.expose(
      "kv_prefix_publish_in_place_bytes",
      "bytes of admitted prefix blocks taken where their device-to-host "
      "transfer landed them: nothing copied on the host");
  publish_renewed.expose(
      "kv_prefix_publish_renewed",
      "prefix publishes that found identical content live: the lease "
      "renewed, no bytes admitted (the cache-hit path of a re-offer)");
  renew_promote.expose(
      "kv_prefix_renew_promote",
      "renewing prefix publishes that found the block in the heap tier "
      "and brought it back hot on the publisher's bytes (in place, else "
      "one copy): a touch is a touch, a publisher's as a fetch's");
  hash_us.expose("kv_prefix_hash_us",
                 "time prefix publishes spent hashing block bytes and "
                 "token spans (us; a group of a run's pages hashed side "
                 "by side counts its time once)");
  hash_lanes.expose(
      "kv_prefix_hash_lanes",
      "prefix pages hashed side by side with others of their run, in a "
      "group of two to four (over kv_prefix_publish_total + "
      "kv_prefix_publish_renewed, the share grouped)");
  fetch_total.expose("kv_prefix_fetch_total",
                     "prefix-block fetches served by this node: "
                     "kv_prefix_hot_hits + kv_prefix_cold_hits");
  hot_hits.expose(
      "kv_prefix_hot_hits",
      "prefix fetches served zero-copy from hot registered pages");
  cold_hits.expose(
      "kv_prefix_cold_hits",
      "prefix fetches that found the block demoted in the cold tier "
      "(each one attempts promotion back to hot)");
  promote.expose(
      "kv_prefix_promote",
      "cold prefix blocks promoted back into registered-RMA pages on "
      "fetch (promotion-on-hit)");
  demote.expose(
      "kv_prefix_demote",
      "hot prefix blocks spilled to the unregistered cold tier under "
      "trpc_kv_prefix_hot_bytes pressure (demoted, not dropped)");
  dropped.expose(
      "kv_prefix_dropped",
      "prefix blocks dropped from the store with a generation tombstone: "
      "trpc_kv_store_bytes pressure, a lapsed lease, or a withdraw");
  fetch_stale.expose(
      "kv_prefix_fetch_stale",
      "prefix fetches answered kv-stale (dropped or withdrawn block, "
      "lapsed lease, generation mismatch): the cached prefix ends there");
  lock_wait_us.expose(
      "kv_prefix_lock_wait_us",
      "time prefix fetches and publishes waited for the store's lock "
      "(us; a lock found free counts nothing)");
  put_total.expose(
      "kv_prefix_put_total",
      "prefix-replica registrations accepted by the registry on this "
      "node (one chain key folds N publishers into a replica set)");
  put_many_total.expose(
      "kv_prefix_put_many_total",
      "KvReg.PutPrefixMany calls answered by the registry on this node");
  put_many_records.expose(
      "kv_prefix_put_many_records",
      "records those calls carried: divide by kv_prefix_put_many_total "
      "for the records a round trip");
  dedup.expose(
      "kv_prefix_dedup",
      "publishes that folded into an existing replica set instead of "
      "minting a new record (fleet-wide content dedup events)");
  match_total.expose(
      "kv_prefix_match_total",
      "longest-cached-prefix queries answered by the registry on this "
      "node (KvReg.Match walks chain keys until first miss)");
  match_keys.expose("kv_prefix_match_keys",
                    "chain keys those queries asked for");
  match_blocks.expose(
      "kv_prefix_match_blocks",
      "prefix blocks matched across all KvReg.Match answers (sum of "
      "matched depths: over kv_prefix_match_keys, the share cached)");
  state_publish_total.expose(
      "kv_prefix_state_publish_total",
      "recurrent-state snapshots admitted fresh into the prefix store "
      "(also counted in kv_prefix_publish_total)");
  state_publish_bytes.expose("kv_prefix_state_publish_bytes",
                             "bytes of those admitted states");
  state_fetch_total.expose(
      "kv_prefix_state_fetch_total",
      "states served by this node's prefix store (also counted in "
      "kv_prefix_fetch_total)");
  state_fetch_bytes.expose("kv_prefix_state_fetch_bytes",
                           "bytes of those served states");
  match_states.expose(
      "kv_prefix_match_states",
      "KvReg.Match answers that carried a live state: the pages up to its "
      "boundary, then the state");
  match_states_shallow.expose(
      "kv_prefix_match_states_shallow",
      "of those, answers whose deepest live state lay short of the "
      "matched run of pages (the pages past it left out)");
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

namespace {

// kv_content_hash of N blocks of one length, the word loop over all 2N
// chains at once (N a constant: the chains stay in registers).
template <size_t N>
void content_hash_lanes(const void* const* data, size_t len,
                        const uint64_t* const* tokens, const size_t* ntokens,
                        Key128* out) {
  // Two lanes with distinct seeds and distinct fold ops (xor-mix vs
  // add-mix) so hi/lo fail independently — 128 bits of key space from
  // two 64-bit walks.  Length and token count seed the lanes: a prefix
  // of the bytes can never alias the whole.
  uint64_t h1[N];
  uint64_t h2[N];
  const unsigned char* p[N];
  for (size_t j = 0; j < N; ++j) {
    h1[j] = 0x9e3779b97f4a7c15ull ^ kv_mix64(len);
    h2[j] = 0xc2b2ae3d27d4eb4full ^ kv_mix64(ntokens[j] + 0x100);
    p[j] = static_cast<const unsigned char*>(data[j]);
  }
  size_t i = 0;
  for (; i + 8 <= len; i += 8) {
#pragma GCC unroll 4
    for (size_t j = 0; j < N; ++j) {
      uint64_t w;
      memcpy(&w, p[j] + i, 8);
      h1[j] = kv_mix64(h1[j] ^ w);
      h2[j] = kv_mix64(h2[j] + w);
    }
  }
  for (size_t j = 0; j < N; ++j) {
    if (i < len) {
      uint64_t tail = 0;
      memcpy(&tail, p[j] + i, len - i);
      h1[j] = kv_mix64(h1[j] ^ tail);
      h2[j] = kv_mix64(h2[j] + tail);
    }
    for (size_t t = 0; t < ntokens[j]; ++t) {
      h1[j] = kv_mix64(h1[j] ^ tokens[j][t]);
      h2[j] = kv_mix64(h2[j] + kv_mix64(tokens[j][t]));
    }
    out[j].hi = h1[j];
    out[j].lo = h2[j];
  }
}

}  // namespace

void kv_content_hash(const void* data, size_t len, const uint64_t* tokens,
                     size_t ntokens, Key128* out) {
  content_hash_lanes<1>(&data, len, &tokens, &ntokens, out);
}

void kv_content_hash_lanes(const void* const* data, size_t len,
                           const uint64_t* const* tokens,
                           const size_t* ntokens, size_t n, Key128* out) {
  static_assert(kKvHashLanes == 4, "one case per group width");
  switch (n) {
    case 1:
      return content_hash_lanes<1>(data, len, tokens, ntokens, out);
    case 2:
      return content_hash_lanes<2>(data, len, tokens, ntokens, out);
    case 3:
      return content_hash_lanes<3>(data, len, tokens, ntokens, out);
    case 4:
      return content_hash_lanes<4>(data, len, tokens, ntokens, out);
    default:
      for (size_t at = 0; at < n; at += kKvHashLanes) {
        kv_content_hash_lanes(data + at, len, tokens + at, ntokens + at,
                              std::min(kKvHashLanes, n - at), out + at);
      }
  }
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

namespace {

// A store-owned hot region with nobody's bytes in it.
struct SpareRegion {
  char* data = nullptr;
  size_t len = 0;
  std::shared_ptr<RmaMapping> map;  // the holder's own reference
};

// What the two tiers let go of and take up again: store-owned registered
// regions (a demote or a drop frees one, a promote or a copied publish
// needs one) and heap blocks (the reverse), kept by size up to
// kSpareBytes each, so that a move in a store at its budgets writes
// pages that are faulted in already.  A process-wide list under its own
// lock, never taken with the store's; emptied by KvStore::clear() and
// when the process ends normally (a region is a name in /dev/shm).
class Spares;
Spares& spares();

class Spares {
 public:
  static constexpr size_t kSpareBytes = 256u << 20;
  // Who owns the mapping of a region nobody reads: the region registry
  // and this list (cpp/capi/hostpool_capi.cc reads the same count).
  static constexpr long kOwnersAtRest = 2;

  void give(SpareRegion r) {
    {
      std::lock_guard<std::mutex> g(mu_);
      if (region_bytes_ + r.len <= kSpareBytes) {
        if (made_by_ != getpid()) {
          made_by_ = getpid();
          // A forked child leaves its parent's names alone.
          atexit([] {
            if (getpid() == spares().made_by_) {
              spares().clear();
            }
          });
        }
        region_bytes_ += r.len;
        regions_.push_back(std::move(r));
        return;
      }
    }
    r.map.reset();
    rma_free(r.data);
  }
  // A region of `len` bytes that no response is still serving, or none.
  bool take(size_t len, SpareRegion* out) {
    std::lock_guard<std::mutex> g(mu_);
    for (size_t i = regions_.size(); i-- > 0;) {
      if (regions_[i].len == len &&
          regions_[i].map.use_count() <= kOwnersAtRest) {
        // Acquire: the last reader's reads happened before it let go of
        // the mapping, and so before whatever is written here next.
        std::atomic_thread_fence(std::memory_order_acquire);
        *out = std::move(regions_[i]);
        regions_.erase(regions_.begin() + static_cast<ptrdiff_t>(i));
        region_bytes_ -= len;
        return true;
      }
    }
    return false;
  }
  void give_heap(char* data, size_t len) {
    {
      std::lock_guard<std::mutex> g(mu_);
      if (heap_bytes_ + len <= kSpareBytes) {
        heap_bytes_ += len;
        heap_.emplace_back(len, data);
        return;
      }
    }
    free(data);
  }
  char* take_heap(size_t len) {
    {
      std::lock_guard<std::mutex> g(mu_);
      for (size_t i = heap_.size(); i-- > 0;) {
        if (heap_[i].first == len) {
          char* data = heap_[i].second;
          heap_.erase(heap_.begin() + static_cast<ptrdiff_t>(i));
          heap_bytes_ -= len;
          return data;
        }
      }
    }
    return static_cast<char*>(malloc(len));
  }
  void clear() {
    std::vector<SpareRegion> regions;
    std::vector<std::pair<size_t, char*>> heap;
    {
      std::lock_guard<std::mutex> g(mu_);
      regions.swap(regions_);
      heap.swap(heap_);
      region_bytes_ = heap_bytes_ = 0;
    }
    for (SpareRegion& r : regions) {
      r.map.reset();
      rma_free(r.data);
    }
    for (auto& [len, data] : heap) {
      free(data);
    }
  }

 private:
  std::mutex mu_;
  std::vector<SpareRegion> regions_;
  std::vector<std::pair<size_t, char*>> heap_;
  size_t region_bytes_ = 0;
  size_t heap_bytes_ = 0;
  pid_t made_by_ = 0;
};

Spares& spares() {
  static Spares* s = new Spares();
  return *s;
}

}  // namespace

// One prefix block's bytes in the heap tier: immutable once made, shared
// by the block and by every response that serves them.  The memory goes
// back to the spares when the last owner lets go, and the next demote of
// that size takes it from there: pages already faulted in (a fresh 9 MB
// block costs this host's first touch of 2,196 pages).
class KvHeapBlock {
 public:
  static std::shared_ptr<const KvHeapBlock> copy_of(const void* data,
                                                    size_t len) {
    char* mine = spares().take_heap(len);
    if (mine == nullptr) {
      return nullptr;
    }
    memcpy(mine, data, len);
    return std::shared_ptr<const KvHeapBlock>(new KvHeapBlock(mine, len));
  }
  ~KvHeapBlock() { spares().give_heap(data_, len_); }
  KvHeapBlock(const KvHeapBlock&) = delete;
  KvHeapBlock& operator=(const KvHeapBlock&) = delete;
  const char* data() const { return data_; }

 private:
  KvHeapBlock(char* data, size_t len) : data_(data), len_(len) {}
  char* data_;
  size_t len_;
};

// What a drop or a move of a prefix block let go of (kvstore.h): freed
// by its destructor, which every user declares BEFORE its lock so that
// it runs after the unlock.
struct KvStore::PrefixTrash {
  std::vector<std::shared_ptr<RmaMapping>> maps;  // of blocks taken in place
  std::vector<SpareRegion> regions;               // store-owned
  std::vector<std::shared_ptr<const KvHeapBlock>> cold;
  // Takes the memory of either tier, whoever held it.
  void take(const char* hot_data, size_t len, bool owned,
            std::shared_ptr<RmaMapping> map,
            std::shared_ptr<const KvHeapBlock> heap) {
    if (owned && hot_data != nullptr) {
      regions.push_back({const_cast<char*>(hot_data), len, std::move(map)});
    } else if (map != nullptr) {
      maps.push_back(std::move(map));
    }
    if (heap != nullptr) {
      cold.push_back(std::move(heap));
    }
  }
  // Takes what `b` holds of either tier; `b` holds nothing afterwards.
  void take(PrefixBlock* b) {
    take(b->hot_data, b->meta.len, b->owned, std::move(b->map),
         std::move(b->cold));
    b->map = nullptr;
    b->cold = nullptr;
    b->hot_data = nullptr;
    b->owned = false;
  }
  ~PrefixTrash() {
    for (SpareRegion& r : regions) {
      spares().give(std::move(r));
    }
  }
};

void KvStore::evict_locked(uint64_t block_id, bool count_var) {
  auto it = blocks_.find(block_id);
  if (it == blocks_.end()) {
    return;
  }
  tombstones_[block_id] = it->second.meta.generation;
  bytes_ -= it->second.meta.len;
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
  PrefixTrash trash;
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
    evict_prefix_locked(prefix_blocks_.begin()->first, &trash);
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
      return kEKvStale;
    }
    return kEKvMiss;
  }
  Block& b = it->second;
  if (b.meta.generation != expected_gen) {
    kv_vars().stale_total << 1;
    return kEKvStale;
  }
  b.touch_seq = ++touch_counter_;
  auto* ctx = new KvServeCtx{b.map};
  out->append_user_data(const_cast<char*>(b.data), b.meta.len,
                        &kv_serve_deleter, ctx);
  kv_vars().fetch_total << 1;
  kv_vars().fetch_bytes << static_cast<int64_t>(b.meta.len);
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
  {
    PrefixTrash trash;
    std::lock_guard<std::mutex> g(mu_);
    blocks_.clear();
    tombstones_.clear();
    bytes_ = 0;
    for (auto& [hash, b] : prefix_blocks_) {
      trash.take(&b);
    }
    prefix_blocks_.clear();
    prefix_tombstones_.clear();
    lru_hot_.clear();
    lru_cold_.clear();
    prefix_leases_.clear();
    prefix_hot_bytes_ = 0;
    prefix_cold_bytes_ = 0;
  }
  spares().clear();
}

// ---- KvStore prefix tier (two-tier content-addressed store) --------------
//
// Everything a block's bytes are copied for (the one copy of a publish
// whose source the store may not keep, a demote, a promote) and every
// allocation and release of megabytes runs with mu_ RELEASED: the mover
// marks the block `moving` under the lock, copies outside it from memory
// it co-owns (the mapping, the heap block), and under the lock again
// installs the result only if the block it finds is still the one it
// left (same generation, still moving); a block dropped or withdrawn
// meanwhile just loses the copy, and so does a demote whose block was
// touched meanwhile (it stays hot, as the most recently used).  A block
// that is moving is served from the tier it is still in.  Room in the
// hot tier is RESERVED under the lock before the bytes are written
// (prefix_hot_reserved_), so the hot bytes never pass their budget while
// copies are under way.

namespace {

// The store's lock for a prefix fetch or publish: what it waited is
// kv_prefix_lock_wait_us (a lock found free reads no clock).
void lock_counted(std::unique_lock<std::mutex>* lk) {
  if (lk->try_lock()) {
    return;
  }
  const int64_t t0 = monotonic_time_us();
  lk->lock();
  kv_prefix_counters().lock_wait_us << (monotonic_time_us() - t0);
}

uint64_t flag_bytes(Flag* f, int64_t otherwise) {
  return static_cast<uint64_t>(
      std::max<int64_t>(f != nullptr ? f->int64_value() : otherwise, 1));
}

// A store-owned registered region holding a copy of [data, data+len),
// pinned: a spare one of that size, else a new one (fresh pages); nullptr
// when registered memory is exhausted.  A copy of megabytes: never under
// mu_.
char* copy_to_region(const void* data, size_t len,
                     std::shared_ptr<RmaMapping>* map, uint64_t* rkey,
                     uint64_t* off) {
  SpareRegion spare;
  void* pages = nullptr;
  if (spares().take(len, &spare)) {
    pages = spare.data;
    spare.map.reset();
  } else {
    uint64_t alloc_rkey = 0;
    pages = rma_alloc(len, &alloc_rkey);
    if (pages == nullptr) {
      return nullptr;
    }
  }
  memcpy(pages, data, len);
  *map = rma_pin_exportable(pages, len, rkey, off);
  if (*map == nullptr) {
    rma_free(pages);
    return nullptr;
  }
  return static_cast<char*>(pages);
}

// Deleter context for a block served from the heap tier: co-owns the
// bytes, which are never written once they are the block's.
struct KvColdServeCtx {
  std::shared_ptr<const KvHeapBlock> bytes;
};
void kv_cold_serve_deleter(void*, void* vctx) {
  delete static_cast<KvColdServeCtx*>(vctx);
}

}  // namespace

void KvStore::touch_prefix_locked(PrefixBlock* b) {
  PrefixLru& lru = b->hot ? lru_hot_ : lru_cold_;
  lru.splice(lru.end(), lru, b->lru_at);
  ++b->touches;
}

void KvStore::set_prefix_lease_locked(PrefixBlock* b, const Key128& hash,
                                      int64_t deadline_us) {
  if (b->deadline_us != 0) {
    prefix_leases_.erase(b->lease_at);
  }
  b->deadline_us = deadline_us;
  b->lease_at = prefix_leases_.emplace(deadline_us, hash);
}

void KvStore::evict_prefix_locked(const Key128& hash, PrefixTrash* trash) {
  auto it = prefix_blocks_.find(hash);
  if (it == prefix_blocks_.end()) {
    return;
  }
  PrefixBlock& b = it->second;
  prefix_tombstones_[hash] = b.meta.generation;
  if (b.hot) {
    prefix_hot_bytes_ -= b.meta.len;
    lru_hot_.erase(b.lru_at);
  } else {
    prefix_cold_bytes_ -= b.meta.len;
    lru_cold_.erase(b.lru_at);
  }
  prefix_leases_.erase(b.lease_at);
  trash->take(&b);
  kv_vars().evict_total << 1;
  kv_prefix_counters().dropped << 1;
  prefix_blocks_.erase(it);
}

bool KvStore::fit_total_locked(uint64_t incoming, uint64_t total_budget,
                               int64_t now, PrefixTrash* trash) {
  // Total-store pressure (blocks + hot + cold vs trpc_kv_store_bytes):
  // expired blocks drop first, then LRU cold, then LRU hot — dropping
  // always tombstones so evicted fetches answer kv-stale.
  auto used = [this] {
    return bytes_ + prefix_hot_bytes_ + prefix_hot_reserved_ +
           prefix_cold_bytes_;
  };
  while (used() + incoming > total_budget && !prefix_blocks_.empty()) {
    Key128 victim;
    if (prefix_leases_.begin()->first <= now) {
      victim = prefix_leases_.begin()->second;
    } else if (!lru_cold_.empty()) {
      victim = lru_cold_.front();
    } else {
      victim = lru_hot_.front();
    }
    evict_prefix_locked(victim, trash);
  }
  return used() + incoming <= total_budget;
}

bool KvStore::demote_one(std::unique_lock<std::mutex>* lk,
                         PrefixTrash* trash) {
  // The victim: the least recently touched hot block that no move is
  // copying already.
  PrefixBlock* victim = nullptr;
  Key128 hash;
  for (const Key128& h : lru_hot_) {
    PrefixBlock& b = prefix_blocks_.find(h)->second;
    if (!b.moving) {
      victim = &b;
      hash = h;
      break;
    }
  }
  if (victim == nullptr) {
    return false;
  }
  victim->moving = true;
  const uint64_t gen = victim->meta.generation;
  const uint64_t touches = victim->touches;
  const char* data = victim->hot_data;
  const size_t len = victim->meta.len;
  // Co-owned for the copy: a drop meanwhile cannot unmap the pages.
  std::shared_ptr<RmaMapping> pinned = victim->map;
  lk->unlock();
  auto cold = KvHeapBlock::copy_of(data, len);
  pinned.reset();
  lk->lock();
  auto it = prefix_blocks_.find(hash);
  if (it == prefix_blocks_.end() || it->second.meta.generation != gen ||
      !it->second.moving) {
    trash->cold.push_back(std::move(cold));  // dropped meanwhile
    return true;
  }
  if (it->second.touches != touches) {
    // Fetched or renewed while the copy ran (a window's fetches reach a
    // chain's hot blocks while its cold ones, promoted, push them out):
    // it is the most recently used block now and stays hot; the caller
    // finds the next victim.  Installed, it would lie in the heap tier
    // in front of every hot block, and be dropped before them.
    it->second.moving = false;
    trash->cold.push_back(std::move(cold));
    return true;
  }
  // Any in-flight serve holds its own mapping reference (KvServeCtx), so
  // letting the pages go is invisible to readers mid-response.
  PrefixBlock& b = it->second;
  trash->take(&b);
  b.cold = std::move(cold);
  b.meta.rkey = 0;
  b.meta.off = 0;
  b.hot = false;
  b.moving = false;
  lru_cold_.splice(lru_cold_.end(), lru_hot_, b.lru_at);
  prefix_hot_bytes_ -= len;
  prefix_cold_bytes_ += len;
  kv_prefix_counters().demote << 1;
  return true;
}

bool KvStore::reserve_hot(std::unique_lock<std::mutex>* lk,
                          uint64_t incoming, PrefixTrash* trash) {
  const uint64_t hot_budget =
      flag_bytes(prefix_hot_bytes_flag(), 256ll << 20);
  if (incoming > hot_budget) {
    return false;  // publishes straight to cold
  }
  // Hot pressure DEMOTES (never drops): the bytes stay serveable, they
  // just lose the registered pages until a hit promotes them back.
  while (prefix_hot_bytes_ + prefix_hot_reserved_ + incoming > hot_budget) {
    if (!demote_one(lk, trash)) {
      return false;  // nothing left to demote yet still over: can't fit
    }
  }
  prefix_hot_reserved_ += incoming;
  return true;
}

KvStore::PrefixBlock* KvStore::promote_marked(
    std::unique_lock<std::mutex>* lk, const Key128& hash, const void* src,
    std::shared_ptr<RmaMapping> map, uint64_t rkey, uint64_t off,
    PrefixTrash* trash) {
  PrefixBlock* b = &prefix_blocks_.find(hash)->second;
  const uint64_t gen = b->meta.generation;
  const uint64_t len = b->meta.len;
  const char* pages = nullptr;
  bool owned = false;
  if (reserve_hot(lk, len, trash)) {
    if (map != nullptr) {
      pages = static_cast<const char*>(src);
    } else {
      lk->unlock();
      pages = copy_to_region(src, len, &map, &rkey, &off);
      lk->lock();
      owned = pages != nullptr;
    }
    prefix_hot_reserved_ -= len;
  }
  // The lock was released (a demote's copy, this copy): the block may
  // have been dropped, or dropped and published again.
  auto it = prefix_blocks_.find(hash);
  b = it == prefix_blocks_.end() ? nullptr : &it->second;
  if (b == nullptr || b->meta.generation != gen || !b->moving) {
    trash->take(pages, len, owned, std::move(map), nullptr);
    return nullptr;
  }
  b->moving = false;
  if (pages == nullptr) {
    trash->take(nullptr, len, false, std::move(map), nullptr);
    return b;
  }
  trash->take(b);  // the heap block
  b->hot_data = pages;
  b->map = std::move(map);
  b->owned = owned;
  b->meta.rkey = rkey;
  b->meta.off = off;
  b->hot = true;
  lru_hot_.splice(lru_hot_.end(), lru_cold_, b->lru_at);
  prefix_hot_bytes_ += len;
  prefix_cold_bytes_ -= len;
  return b;
}

int KvStore::publish_prefix(const Key128& key, uint32_t depth,
                            const void* data, size_t len,
                            const uint64_t* tokens, size_t ntokens,
                            int64_t lease_ms, KvPrefixMeta* out,
                            uint64_t min_generation, bool in_place) {
  PrefixPage page;
  page.key = key;
  page.data = data;
  page.tokens = tokens;
  page.ntokens = ntokens;
  page.in_place = in_place;
  int rc = -1;
  KvPrefixMeta meta;
  publish_prefix_run(&page, 1, len, depth, lease_ms, &rc, &meta,
                     min_generation);
  if (out != nullptr && (rc == 0 || rc == kEKvExists)) {
    *out = meta;
  }
  return rc;
}

size_t KvStore::publish_prefix_run(const PrefixPage* pages, size_t n,
                                   size_t len, uint32_t first_depth,
                                   int64_t lease_ms, int* rcs,
                                   KvPrefixMeta* outs,
                                   uint64_t min_generation) {
  kv_ensure_registered();
  KvPrefixCounters& counted = kv_prefix_counters();
  for (size_t at = 0; at < n;) {
    // The group: the next pages up to kKvHashLanes, up to the first
    // without a key or bytes, which fails (-1) and ends the run.
    size_t width = 0;
    while (width < std::min(kKvHashLanes, n - at)) {
      const PrefixPage& page = pages[at + width];
      if (page.key.zero() || page.data == nullptr || len == 0) {
        break;
      }
      ++width;
    }
    if (width == 0) {
      rcs[at] = -1;
      return at + 1;
    }
    const void* data[kKvHashLanes];
    const uint64_t* tokens[kKvHashLanes];
    size_t ntokens[kKvHashLanes];
    Key128 hashes[kKvHashLanes];
    for (size_t j = 0; j < width; ++j) {
      data[j] = pages[at + j].data;
      tokens[j] = pages[at + j].tokens;
      ntokens[j] = pages[at + j].ntokens;
    }
    const int64_t hash_t0 = monotonic_time_us();
    kv_content_hash_lanes(data, len, tokens, ntokens, width, hashes);
    counted.hash_us << (monotonic_time_us() - hash_t0);
    if (width > 1) {
      counted.hash_lanes << static_cast<int64_t>(width);
    }
    for (size_t j = 0; j < width; ++j, ++at) {
      const uint32_t depth = first_depth + static_cast<uint32_t>(at);
      rcs[at] = admit_prefix(pages[at], depth, len, hashes[j], lease_ms,
                             &outs[at], min_generation);
      if (rcs[at] == -1) {
        return at + 1;
      }
    }
  }
  return n;
}

int KvStore::admit_prefix(const PrefixPage& page, uint32_t depth,
                          size_t len, const Key128& hash, int64_t lease_ms,
                          KvPrefixMeta* out, uint64_t min_generation) {
  const Key128& key = page.key;
  const void* data = page.data;
  KvPrefixCounters& counted = kv_prefix_counters();
  const uint64_t total_budget =
      flag_bytes(store_bytes_flag(), 1ll << 30);
  if (len > total_budget) {
    return -1;
  }
  // A source the store may keep: registered memory the caller lets it
  // co-own (pinned before the lock; a refusal just means a copy).
  std::shared_ptr<RmaMapping> map;
  uint64_t rkey = 0;
  uint64_t off = 0;
  if (page.in_place) {
    map = rma_pin_exportable(data, len, &rkey, &off);
  }
  const int64_t now = monotonic_time_us();
  PrefixTrash trash;
  std::unique_lock<std::mutex> lk(mu_, std::defer_lock);
  lock_counted(&lk);
  auto it = prefix_blocks_.find(hash);
  if (it != prefix_blocks_.end()) {
    if (it->second.deadline_us > now) {
      // Live block with identical content: THE cache-hit path.  The
      // lease renews and the record echoes, but kEKvExists tells the
      // caller these bytes did NOT need recomputing/copying.
      PrefixBlock* b = &it->second;
      set_prefix_lease_locked(b, hash, effective_lease_us(lease_ms));
      touch_prefix_locked(b);
      KvPrefixMeta meta = b->meta;
      if (!b->hot && !b->moving) {
        // A touch brings a block to the hot tier, a publisher's as a
        // fetch's: the heap block takes the publisher's bytes as its hot
        // pages (in place, else copied once) and lets its own go.  Left
        // in the heap tier it would be dropped before every hot block,
        // however recently it was renewed.
        b->moving = true;
        b = promote_marked(&lk, hash, data, std::move(map), rkey, off,
                           &trash);
        if (b != nullptr) {
          meta = b->meta;
          if (b->hot) {
            counted.renew_promote << 1;
          }
        }
      }
      if (out != nullptr) {
        *out = meta;
      }
      counted.publish_renewed << 1;
      return kEKvExists;
    }
    evict_prefix_locked(hash, &trash);  // lapsed: tombstone, re-admit
  }
  if (!fit_total_locked(len, total_budget, now, &trash)) {
    return -1;  // regular blocks own the budget: don't evict them here
  }
  // Hot placement: registered pages so fetches serve from them.  The
  // room is reserved first; the bytes then move with the lock released.
  // Falls to the cold tier when the block outsizes the hot budget or
  // registered memory is exhausted — cold still serves.
  const char* hot_data = nullptr;
  bool owned = false;
  std::shared_ptr<const KvHeapBlock> cold;
  if (reserve_hot(&lk, len, &trash)) {
    if (map != nullptr) {
      hot_data = static_cast<const char*>(data);
    } else {
      lk.unlock();
      hot_data = copy_to_region(data, len, &map, &rkey, &off);
      lk.lock();
      owned = hot_data != nullptr;
    }
    if (hot_data == nullptr) {
      prefix_hot_reserved_ -= len;
    }
  }
  if (hot_data == nullptr) {
    map.reset();
    // The heap tier's bytes count against the total from here on; the
    // copy itself runs outside the lock.
    prefix_cold_bytes_ += len;
    lk.unlock();
    cold = KvHeapBlock::copy_of(data, len);
    lk.lock();
    prefix_cold_bytes_ -= len;
  }
  if (hot_data != nullptr) {
    prefix_hot_reserved_ -= len;  // the room is the block's own from here
  }
  auto discard = [&] {
    trash.take(hot_data, len, owned, std::move(map), std::move(cold));
  };
  // The lock was released: another publisher of the same content may
  // have admitted it meanwhile, and then this copy is not needed.
  it = prefix_blocks_.find(hash);
  if (it != prefix_blocks_.end()) {
    discard();
    PrefixBlock& b = it->second;
    set_prefix_lease_locked(&b, hash, effective_lease_us(lease_ms));
    touch_prefix_locked(&b);
    if (out != nullptr) {
      *out = b.meta;
    }
    counted.publish_renewed << 1;
    return kEKvExists;
  }
  // And other publishers may have filled the store meanwhile: the total
  // is made to fit again now.
  if (!fit_total_locked(len, total_budget, now, &trash)) {
    discard();
    return -1;
  }
  PrefixBlock& b = prefix_blocks_[hash];
  b.meta.key = key;
  b.meta.hash = hash;
  b.meta.generation =
      std::max(prefix_tombstones_[hash] + 1, min_generation);
  prefix_tombstones_[hash] = b.meta.generation;
  b.meta.len = len;
  b.meta.depth = depth;
  b.meta.flags = page.flags;
  set_prefix_lease_locked(&b, hash, effective_lease_us(lease_ms));
  if (hot_data != nullptr) {
    b.hot_data = hot_data;
    b.map = std::move(map);
    b.owned = owned;
    b.meta.rkey = rkey;
    b.meta.off = off;
    b.hot = true;
    prefix_hot_bytes_ += len;
    b.lru_at = lru_hot_.insert(lru_hot_.end(), hash);
  } else {
    b.cold = std::move(cold);
    prefix_cold_bytes_ += len;
    b.lru_at = lru_cold_.insert(lru_cold_.end(), hash);
  }
  if (out != nullptr) {
    *out = b.meta;
  }
  counted.publish_total << 1;
  counted.publish_bytes << static_cast<int64_t>(len);
  if ((page.flags & kKvPrefixState) != 0) {
    counted.state_publish_total << 1;
    counted.state_publish_bytes << static_cast<int64_t>(len);
  }
  (b.hot && !b.owned ? counted.publish_in_place_bytes
                     : counted.publish_copy_bytes)
      << static_cast<int64_t>(len);
  return 0;
}

int KvStore::fetch_prefix(const Key128& hash, uint64_t expected_gen,
                          IOBuf* out) {
  kv_ensure_registered();
  KvPrefixCounters& counted = kv_prefix_counters();
  const int64_t now = monotonic_time_us();
  PrefixTrash trash;
  std::unique_lock<std::mutex> lk(mu_, std::defer_lock);
  lock_counted(&lk);
  auto it = prefix_blocks_.find(hash);
  if (it == prefix_blocks_.end() || it->second.deadline_us <= now) {
    if (it != prefix_blocks_.end()) {
      evict_prefix_locked(hash, &trash);  // serve-time validity, as fetch()
    }
    if (prefix_tombstones_.find(hash) != prefix_tombstones_.end()) {
      kv_vars().stale_total << 1;
      counted.fetch_stale << 1;
      return kEKvStale;
    }
    return kEKvMiss;
  }
  PrefixBlock* b = &it->second;
  // expected_gen 0 accepts any live generation (content addressing
  // already names the exact bytes; the generation only fences zombies).
  if (expected_gen != 0 && b->meta.generation != expected_gen) {
    kv_vars().stale_total << 1;
    counted.fetch_stale << 1;
    return kEKvStale;
  }
  const uint64_t len = b->meta.len;
  const bool state = (b->meta.flags & kKvPrefixState) != 0;
  auto served = [&] {
    counted.fetch_total << 1;
    if (state) {
      counted.state_fetch_total << 1;
      counted.state_fetch_bytes << static_cast<int64_t>(len);
    }
    kv_vars().fetch_bytes << static_cast<int64_t>(len);
    return 0;
  };
  auto serve_heap = [&](const std::shared_ptr<const KvHeapBlock>& bytes) {
    out->append_user_data(const_cast<char*>(bytes->data()), len,
                          &kv_cold_serve_deleter, new KvColdServeCtx{bytes});
    return served();
  };
  touch_prefix_locked(b);
  if (b->hot) {
    counted.hot_hits << 1;
  } else {
    counted.cold_hits << 1;
    // Promotion-on-hit: copy back into registered pages so the NEXT
    // fetch is a hot hit again.  One mover a block: a second fetch of
    // it meanwhile is served from the heap, as is one whose promotion
    // finds no room or no registered memory.
    if (!b->moving) {
      b->moving = true;
      std::shared_ptr<const KvHeapBlock> bytes = b->cold;
      b = promote_marked(&lk, hash, bytes->data(), nullptr, 0, 0, &trash);
      if (b == nullptr) {
        // Gone meanwhile: the bytes read are still this generation's,
        // validated when the fetch began; serve them and keep nothing.
        return serve_heap(bytes);
      }
      if (b->hot) {
        counted.promote << 1;
      }
    }
  }
  if (!b->hot) {
    return serve_heap(b->cold);
  }
  out->append_user_data(const_cast<char*>(b->hot_data), len,
                        &kv_serve_deleter, new KvServeCtx{b->map});
  return served();
}

int KvStore::withdraw_prefix(const Key128& hash) {
  PrefixTrash trash;
  std::lock_guard<std::mutex> g(mu_);
  if (prefix_blocks_.find(hash) == prefix_blocks_.end()) {
    return kEKvMiss;
  }
  evict_prefix_locked(hash, &trash);
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
  auto& records =
      (meta.flags & kKvPrefixState) != 0 ? prefix_states_ : prefix_;
  auto it = records.find(meta.key);
  if (it == records.end()) {
    PrefixEntry e;
    e.hash = meta.hash;
    e.depth = meta.depth;
    e.len = meta.len;
    it = records.emplace(meta.key, std::move(e)).first;
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
      kv_prefix_counters().put_total << 1;
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
    kv_prefix_counters().dedup << 1;
  }
  kv_prefix_counters().put_total << 1;
  if (gen_out != nullptr) {
    *gen_out = meta.generation;
  }
  return 0;
}

namespace {

// Drops an entry's lapsed replicas; true if a live one is left.
template <typename Entry>
bool prune_lapsed(Entry* e, int64_t now) {
  auto& reps = e->replicas;
  reps.erase(std::remove_if(reps.begin(), reps.end(),
                            [now](const auto& r) {
                              return r.deadline_us <= now;
                            }),
             reps.end());
  return !reps.empty();
}

}  // namespace

size_t KvRegistry::match(const Key128* keys, size_t n,
                         std::vector<KvPrefixMeta>* out,
                         std::vector<int64_t>* lease_out) {
  kv_ensure_registered();
  KvPrefixCounters& counted = kv_prefix_counters();
  const int64_t now = monotonic_time_us();
  std::lock_guard<std::mutex> g(mu_);
  counted.match_total << 1;
  counted.match_keys << static_cast<int64_t>(n);
  std::vector<PrefixEntry*> run;
  for (size_t i = 0; i < n; ++i) {
    auto it = prefix_.find(keys[i]);
    // The first miss, or a block whose replicas all lapsed, ends the
    // longest cached prefix.
    if (it == prefix_.end() || !prune_lapsed(&it->second, now)) {
      break;
    }
    run.push_back(&it->second);
  }
  // The deepest boundary of the run at which a live state lies.
  PrefixEntry* state = nullptr;
  size_t answered = run.size();
  for (size_t b = run.size(); b > 0 && !prefix_states_.empty(); --b) {
    auto it = prefix_states_.find(keys[b - 1]);
    if (it != prefix_states_.end() && prune_lapsed(&it->second, now)) {
      state = &it->second;
      answered = b;
      break;
    }
  }
  auto answer = [&](const PrefixEntry& e) {
    for (const PrefixReplica& r : e.replicas) {
      if (out != nullptr) {
        out->push_back(r.meta);
      }
      if (lease_out != nullptr) {
        lease_out->push_back((r.deadline_us - now) / 1000);
      }
    }
  };
  for (size_t i = 0; i < answered; ++i) {
    answer(*run[i]);
  }
  if (state != nullptr) {
    answer(*state);
    counted.match_states << 1;
    if (answered < run.size()) {
      counted.match_states_shallow << 1;
    }
  }
  counted.match_blocks << static_cast<int64_t>(answered);
  return answered;
}

int KvRegistry::evict_prefix(const Key128& key, const char* node) {
  std::lock_guard<std::mutex> g(mu_);
  // The page under this chain key and the state at its boundary: both
  // are the node's replicas of this prefix.
  int rc = kEKvMiss;
  for (auto* records : {&prefix_, &prefix_states_}) {
    auto it = records->find(key);
    if (it == records->end()) {
      continue;
    }
    std::vector<PrefixReplica>& reps = it->second.replicas;
    for (auto r = reps.begin(); r != reps.end(); ++r) {
      if (node != nullptr && strncmp(r->meta.node, node,
                                     sizeof(r->meta.node)) == 0) {
        reps.erase(r);
        rc = 0;  // the fence map stays: no zombie window reopens
        break;
      }
    }
  }
  return rc;
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
  prefix_states_.clear();
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
  w->flags = m.flags & kKvPrefixState;
  memcpy(w->node, m.node, sizeof(w->node));
}

KvPrefixMeta wire_to_prefix_meta(const KvPrefixWire& w) {
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
  m.flags = w.flags & kKvPrefixState;
  memcpy(m.node, w.node, sizeof(m.node));
  return m;
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
template <typename Entry, typename Wire = KvWire, typename One>
uint64_t serve_many(Controller* cntl, const IOBuf& req, IOBuf* resp,
                    const char* what, One one) {
  uint64_t n = 0;
  if (req.size() >= sizeof(n)) {
    req.copy_to(&n, sizeof(n));
  }
  if (n == 0 || n > kKvManyMax ||
      req.size() < sizeof(n) + n * sizeof(Wire)) {
    cntl->SetFailed(EINVAL, std::string("bad ") + what + " record count");
    return 0;
  }
  std::vector<Wire> wires(n);
  req.copy_to(wires.data(), n * sizeof(Wire), sizeof(n));
  std::vector<Entry> out(n);
  for (uint64_t i = 0; i < n; ++i) {
    wires[i].node[sizeof(wires[i].node) - 1] = '\0';
    one(wires[i], &out[i]);
  }
  resp->append(&n, sizeof(n));
  resp->append(out.data(), n * sizeof(Entry));
  return n;
}

// The three batch calls over block records count themselves here.
template <typename Entry, typename One>
void serve_many_blocks(Controller* cntl, const IOBuf& req, IOBuf* resp,
                       const char* what, One one) {
  const uint64_t n = serve_many<Entry>(cntl, req, resp, what, one);
  if (n != 0) {
    kv_vars().reg_many_total << 1;
    kv_vars().reg_many_records << static_cast<int64_t>(n);
  }
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
  int rcs[10] = {0, 0, 0, 0, 0, 0, 0, 0, 0, 0};
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
        uint64_t gen = 0;
        const int rc = kv_registry().put_prefix(wire_to_prefix_meta(w),
                                                w.lease_ms, &gen);
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
        serve_many_blocks<KvManyGen>(
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
        serve_many_blocks<KvManyRecord>(
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
        serve_many_blocks<KvManyGen>(
            cntl, req, resp, kKvEvictManyMethod,
            [](const KvWire& w, KvManyGen* o) {
              o->status = kv_registry().evict(w.block_id, &o->generation);
            });
        done();
      });
  rcs[9] = s->RegisterMethod(
      kKvPrefixPutManyMethod, [](Controller* cntl, const IOBuf& req,
                                 IOBuf* resp, Closure done) {
        const uint64_t n = serve_many<KvManyGen, KvPrefixWire>(
            cntl, req, resp, kKvPrefixPutManyMethod,
            [](const KvPrefixWire& w, KvManyGen* o) {
              o->status = kv_registry().put_prefix(
                  wire_to_prefix_meta(w), w.lease_ms, &o->generation);
            });
        if (n != 0) {
          kv_prefix_counters().put_many_total << 1;
          kv_prefix_counters().put_many_records
              << static_cast<int64_t>(n);
        }
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
