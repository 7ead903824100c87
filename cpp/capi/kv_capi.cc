// C ABI for the paged KV-block registry (net/kvstore.h) — the Python
// surface brpc_tpu/rpc/kv.py binds.  The data plane stays native: the
// store serves block bytes zero-copy out of registered regions with no
// Python in the path; these entry points only publish/withdraw blocks
// and attach the native handlers to a server.
#include <string.h>

#include <utility>
#include <vector>

#include "base/iobuf.h"
#include "net/kvstore.h"
#include "net/server.h"

using namespace trpc;

extern "C" {

// Attaches the registry handlers (KvReg.Register/Lookup/Evict/Renew) to
// a not-yet-started server.  Returns 0, or -1 (server already running —
// the registrations were refused).
int trpc_server_enable_kv_registry(void* srv) {
  return kv_attach_registry(static_cast<Server*>(srv));
}

// Attaches the block-store fetch handler (Kv.Fetch).  Returns 0, or -1
// (server already running — the registration was refused).
int trpc_server_enable_kv_store(void* srv) {
  return kv_attach_store(static_cast<Server*>(srv));
}

// Publishes [data, data+len) — which must lie inside an rma_alloc'd
// region (RmaBuffer bytes) — as block_id under a lease (lease_ms <= 0:
// the trpc_kv_lease_ms default).  Fills the minted generation and the
// region coordinates for the registry record.  Returns 0, kEKvExists
// (2103) while the block is live, or -1 (not registered memory / over
// budget).
int trpc_kv_publish_ex(const void* data, size_t len, uint64_t block_id,
                       int64_t lease_ms, uint64_t min_generation,
                       uint64_t* gen_out, uint64_t* rkey_out,
                       uint64_t* off_out);

int trpc_kv_publish(const void* data, size_t len, uint64_t block_id,
                    int64_t lease_ms, uint64_t* gen_out, uint64_t* rkey_out,
                    uint64_t* off_out) {
  return trpc_kv_publish_ex(data, len, block_id, lease_ms, 0, gen_out,
                            rkey_out, off_out);
}

// Takeover variant (net/naming.h drain + hot restart): min_generation
// floors the minted generation so a successor pid's re-publish outranks
// the dead predecessor's registry record and cached lookups.
int trpc_kv_publish_ex(const void* data, size_t len, uint64_t block_id,
                       int64_t lease_ms, uint64_t min_generation,
                       uint64_t* gen_out, uint64_t* rkey_out,
                       uint64_t* off_out) {
  KvBlockMeta m;
  const int rc = kv_store().publish(block_id, data, len, lease_ms, &m,
                                    min_generation);
  if (rc != 0) {
    return rc;
  }
  if (gen_out != nullptr) {
    *gen_out = m.generation;
  }
  if (rkey_out != nullptr) {
    *rkey_out = m.rkey;
  }
  if (off_out != nullptr) {
    *off_out = m.off;
  }
  return 0;
}

// Evicts a local block (generation tombstoned).  0 or kEKvMiss (2101).
int trpc_kv_withdraw(uint64_t block_id) {
  return kv_store().withdraw(block_id);
}

// Extends a local block's lease.  0 or kEKvMiss.
int trpc_kv_renew(uint64_t block_id, int64_t lease_ms) {
  return kv_store().renew(block_id, lease_ms);
}

size_t trpc_kv_store_count() { return kv_store().count(); }

uint64_t trpc_kv_store_bytes_used() { return kv_store().bytes_used(); }

size_t trpc_kv_registry_count() { return kv_registry().count(); }

// The kv error-code family (net/kvstore.h), read once by kv.py so the
// Python exception mapping can never drift from the C++ constants.
void trpc_kv_codes(int* miss, int* stale, int* exists) {
  if (miss != nullptr) {
    *miss = kEKvMiss;
  }
  if (stale != nullptr) {
    *stale = kEKvStale;
  }
  if (exists != nullptr) {
    *exists = kEKvExists;
  }
}

// Counts one KvClient.fetch_many of `records` records in the registry's
// kv_fetch_many_total / kv_fetch_many_records (the fan-out runs in
// kv.py over the batch pipeline; the counters live with the kv vars).
void trpc_kv_note_fetch_many(uint64_t records) {
  kv_note_fetch_many(records);
}

// Counts one KvClient.fetch_sequence in the kv_seq_* counters: what it
// handed over of each kind, or that it refused the hand-over whole.
void trpc_kv_note_sequence(uint64_t page_records, uint64_t page_bytes,
                           uint64_t snapshot_records,
                           uint64_t snapshot_bytes, int handed_over) {
  kv_note_sequence(page_records, page_bytes, snapshot_records,
                   snapshot_bytes, handed_over != 0);
}

// Counts one kv.publish_page / publish_sequence: the bytes published
// from the block they landed in, and the bytes copied into the slab.
void trpc_kv_note_publish(uint64_t in_place_bytes, uint64_t copy_bytes) {
  kv_note_publish(in_place_bytes, copy_bytes);
}

// ---- content-addressed prefix cache (ISSUE 17) ---------------------------

// 128-bit content hash of (block bytes, token-id span) — deterministic
// across processes: the fleet-wide dedup key.
void trpc_kv_content_hash(const void* data, size_t len,
                          const uint64_t* tokens, size_t ntokens,
                          uint64_t* hi, uint64_t* lo) {
  Key128 k;
  kv_content_hash(data, len, tokens, ntokens, &k);
  if (hi != nullptr) {
    *hi = k.hi;
  }
  if (lo != nullptr) {
    *lo = k.lo;
  }
}

// trpc_kv_content_hash of n blocks of `len` bytes each, side by side
// (kv_content_hash_lanes): block j at data[j] with the token span of
// ntokens[j] ids that starts at tokens[j]; hash j to hi[j], lo[j].
void trpc_kv_content_hash_lanes(const void* const* data, size_t len,
                                const uint64_t* const* tokens,
                                const uint64_t* ntokens, size_t n,
                                uint64_t* hi, uint64_t* lo) {
  std::vector<size_t> counts(ntokens, ntokens + n);
  std::vector<Key128> keys(n);
  kv_content_hash_lanes(data, len, tokens, counts.data(), n, keys.data());
  for (size_t j = 0; j < n; ++j) {
    hi[j] = keys[j].hi;
    lo[j] = keys[j].lo;
  }
}

// Chain keys for a token-id sequence, written as interleaved (hi, lo)
// u64 pairs (Key128's exact layout).  block_tokens <= 0 uses
// trpc_kv_prefix_block_tokens.  Returns the number of FULL blocks.
size_t trpc_kv_prefix_chain(const uint64_t* tokens, size_t ntokens,
                            int64_t block_tokens, uint64_t* keys_out,
                            size_t max_keys) {
  static_assert(sizeof(Key128) == 16, "interleaved (hi, lo) pairs");
  return kv_prefix_chain(tokens, ntokens, block_tokens,
                         reinterpret_cast<Key128*>(keys_out), max_keys);
}

// Publishes one prefix block into the two-tier store.  `in_place` != 0
// and a source in registered memory: the store takes the bytes where
// they lie and co-owns their region (the caller's promise that nobody
// writes them meanwhile: kv.py passes it for the host pool's landing
// blocks); else they are copied once into store-owned pages — any caller
// memory works.  Fills the content hash, minted generation and hot-tier
// coordinates.  Returns 0 (fresh bytes admitted), kEKvExists (2103:
// identical content already live — the cache-hit path, lease renewed,
// outputs filled), or -1 (over budget / bad args).
int trpc_kv_prefix_publish_at(uint64_t key_hi, uint64_t key_lo,
                              uint32_t depth, const void* data, size_t len,
                              const uint64_t* tokens, size_t ntokens,
                              int64_t lease_ms, uint64_t min_generation,
                              int in_place, uint64_t* hash_hi,
                              uint64_t* hash_lo, uint64_t* gen_out,
                              uint64_t* rkey_out, uint64_t* off_out) {
  Key128 key;
  key.hi = key_hi;
  key.lo = key_lo;
  KvPrefixMeta m;
  const int rc = kv_store().publish_prefix(key, depth, data, len, tokens,
                                           ntokens, lease_ms, &m,
                                           min_generation, in_place != 0);
  if (rc != 0 && rc != kEKvExists) {
    return rc;
  }
  if (hash_hi != nullptr) {
    *hash_hi = m.hash.hi;
  }
  if (hash_lo != nullptr) {
    *hash_lo = m.hash.lo;
  }
  if (gen_out != nullptr) {
    *gen_out = m.generation;
  }
  if (rkey_out != nullptr) {
    *rkey_out = m.rkey;
  }
  if (off_out != nullptr) {
    *off_out = m.off;
  }
  return rc;
}

// Publishes a run of n prefix blocks of `len` bytes each, as n calls of
// trpc_kv_prefix_publish_at would in order (min_generation 0), with the
// content hashes taken kKvHashLanes blocks at a time: block j at
// data[j] under chain key (keys[2j], keys[2j+1]) at depth first_depth + j,
// its token span the ntokens[j] ids from tokens' running offset on,
// in_place[j] as trpc_kv_prefix_publish_at's.  Block j's return in
// rcs[j], its outputs at [j] of the five arrays where it is 0 or
// kEKvExists.  Stops after the first block that returns -1; returns the
// blocks handled.
size_t trpc_kv_prefix_publish_run(const uint64_t* keys, uint32_t first_depth,
                                  const void* const* data, size_t len,
                                  const uint64_t* tokens,
                                  const uint64_t* ntokens,
                                  const int* in_place, size_t n,
                                  int64_t lease_ms, int* rcs,
                                  uint64_t* hash_hi, uint64_t* hash_lo,
                                  uint64_t* gen_out, uint64_t* rkey_out,
                                  uint64_t* off_out) {
  std::vector<KvStore::PrefixPage> pages(n);
  size_t token_at = 0;
  for (size_t j = 0; j < n; ++j) {
    pages[j].key.hi = keys[2 * j];
    pages[j].key.lo = keys[2 * j + 1];
    pages[j].data = data[j];
    pages[j].tokens = tokens + token_at;
    pages[j].ntokens = ntokens[j];
    pages[j].in_place = in_place[j] != 0;
    token_at += ntokens[j];
  }
  std::vector<KvPrefixMeta> metas(n);
  const size_t handled = kv_store().publish_prefix_run(
      pages.data(), n, len, first_depth, lease_ms, rcs, metas.data());
  for (size_t j = 0; j < handled; ++j) {
    if (rcs[j] != 0 && rcs[j] != kEKvExists) {
      continue;
    }
    hash_hi[j] = metas[j].hash.hi;
    hash_lo[j] = metas[j].hash.lo;
    gen_out[j] = metas[j].generation;
    rkey_out[j] = metas[j].rkey;
    off_out[j] = metas[j].off;
  }
  return handled;
}

// Publishes the recurrent state of a hybrid cache taken at `boundary`
// pages, under the chain key (key_hi, key_lo) of the boundary's last
// page: the store's one admission path, as trpc_kv_prefix_publish_run
// admits a run of one, its record flagged kKvPrefixState.  Returns and
// fills as trpc_kv_prefix_publish_at.
int trpc_kv_prefix_publish_state(uint64_t key_hi, uint64_t key_lo,
                                 uint32_t boundary, const void* data,
                                 size_t len, const uint64_t* tokens,
                                 size_t ntokens, int64_t lease_ms,
                                 int in_place, uint64_t* hash_hi,
                                 uint64_t* hash_lo, uint64_t* gen_out,
                                 uint64_t* rkey_out, uint64_t* off_out) {
  KvStore::PrefixPage page;
  page.key.hi = key_hi;
  page.key.lo = key_lo;
  page.data = data;
  page.tokens = tokens;
  page.ntokens = ntokens;
  page.in_place = in_place != 0;
  page.flags = kKvPrefixState;
  int rc = -1;
  KvPrefixMeta m;
  kv_store().publish_prefix_run(&page, 1, len, boundary, lease_ms, &rc, &m);
  if (rc != 0 && rc != kEKvExists) {
    return rc;
  }
  *hash_hi = m.hash.hi;
  *hash_lo = m.hash.lo;
  *gen_out = m.generation;
  *rkey_out = m.rkey;
  *off_out = m.off;
  return rc;
}

// Evicts a local prefix block by content hash (generation tombstoned).
int trpc_kv_prefix_withdraw(uint64_t hash_hi, uint64_t hash_lo) {
  Key128 h;
  h.hi = hash_hi;
  h.lo = hash_lo;
  return kv_store().withdraw_prefix(h);
}

size_t trpc_kv_prefix_store_count() { return kv_store().prefix_count(); }

uint64_t trpc_kv_prefix_hot_bytes() { return kv_store().prefix_hot_bytes(); }

uint64_t trpc_kv_prefix_cold_bytes() {
  return kv_store().prefix_cold_bytes();
}

size_t trpc_kv_prefix_registry_count() {
  return kv_registry().prefix_count();
}

size_t trpc_kv_prefix_registry_replicas() {
  return kv_registry().prefix_replicas();
}

// Prefix-tier outcome counters (the registry's kv_prefix_* Adders) since
// process start or the last trpc_kv_reset.
void trpc_kv_prefix_counters(uint64_t* promote, uint64_t* demote,
                             uint64_t* hot_hits, uint64_t* cold_hits,
                             uint64_t* dedup) {
  KvPrefixCounters& c = kv_prefix_counters();
  const std::pair<uint64_t*, const Adder*> outs[] = {
      {promote, &c.promote}, {demote, &c.demote}, {hot_hits, &c.hot_hits},
      {cold_hits, &c.cold_hits}, {dedup, &c.dedup}};
  for (const auto& [out, counter] : outs) {
    if (out != nullptr) {
      *out = KvPrefixCounters::read(*counter);
    }
  }
}

// Test support: drops every local block, tombstone, and registry record
// (both the id-addressed and content-addressed tiers) and zeroes the
// five prefix outcome counters trpc_kv_prefix_counters reads.
void trpc_kv_reset() {
  kv_store().clear();
  kv_registry().clear();
  KvPrefixCounters& c = kv_prefix_counters();
  for (Adder* counter :
       {&c.promote, &c.demote, &c.hot_hits, &c.cold_hits, &c.dedup}) {
    counter->reset();
  }
}

}  // extern "C"
