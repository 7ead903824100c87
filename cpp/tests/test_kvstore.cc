// Paged KV-block registry tests (net/kvstore.h): registry lifecycle and
// lease semantics, generation minting across evictions, double-register
// rejection, store eviction under byte-budget pressure, zero-copy
// serving out of registered pages, client lookup-cache invalidation on
// stale generations, the one-sided fetch ride over shm, and chunk-fault
// whole-or-nothing composition — the block-addressed transfer tier the
// prefill/decode disaggregation workload (tools/kv_disagg.py) runs on.
#include <unistd.h>

#include <atomic>
#include <chrono>
#include <cstring>
#include <string>
#include <thread>
#include <vector>

#include "base/flags.h"
#include "base/time.h"
#include "fiber/fiber.h"
#include "net/channel.h"
#include "net/controller.h"
#include "net/fault.h"
#include "net/hotpath_stats.h"
#include "net/kvstore.h"
#include "net/rma.h"
#include "net/server.h"
#include "tests/test_util.h"

using namespace trpc;

namespace {

Server* g_server = nullptr;
int g_port = 0;

void start_once() {
  if (g_server != nullptr) {
    return;
  }
  g_server = new Server();
  kv_attach_store(g_server);
  kv_attach_registry(g_server);
  g_server->RegisterMethod("Token.Step", [](Controller*, const IOBuf& req,
                                            IOBuf* resp, Closure done) {
    resp->append(req);  // zero-copy ref share
    done();
  });
  EXPECT_EQ(g_server->Start(0), 0);
  g_port = g_server->port();
}

std::string addr() { return "127.0.0.1:" + std::to_string(g_port); }

// Patterned block content: a mis-offset or torn landing can never
// byte-match its own pattern.
void fill_pattern(char* p, size_t n, uint32_t salt) {
  for (size_t i = 0; i < n; ++i) {
    p[i] = static_cast<char>(((i + salt) * 2654435761u) >> 13);
  }
}

bool check_pattern(const IOBuf& buf, size_t n, uint32_t salt) {
  if (buf.size() != n) {
    return false;
  }
  std::string got = buf.to_string();
  for (size_t i = 0; i < n; ++i) {
    if (got[i] != static_cast<char>(((i + salt) * 2654435761u) >> 13)) {
      return false;
    }
  }
  return true;
}

struct FaultGuard {
  ~FaultGuard() { FaultActor::global().set(""); }
};

struct FlagGuard {
  std::string name, old_value;
  FlagGuard(const std::string& n, const std::string& v) : name(n) {
    old_value = Flag::find(n)->value_string();
    EXPECT_EQ(Flag::set(n, v), 0);
  }
  ~FlagGuard() { Flag::set(name, old_value); }
};

struct KvReset {
  KvReset() {
    kv_store().clear();
    kv_registry().clear();
  }
  ~KvReset() {
    kv_store().clear();
    kv_registry().clear();
  }
};

KvBlockMeta meta_for(uint64_t id, uint64_t gen, uint64_t len,
                     const char* node = "127.0.0.1:1") {
  KvBlockMeta m;
  m.block_id = id;
  m.generation = gen;
  m.rkey = 0x42;
  m.off = 0;
  m.len = len;
  snprintf(m.node, sizeof(m.node), "%s", node);
  return m;
}

}  // namespace

// -- registry ---------------------------------------------------------------

TEST_CASE(kv_registry_lifecycle_and_leases) {
  KvReset reset;
  KvRegistry& reg = kv_registry();
  uint64_t gen = 0;
  EXPECT_EQ(reg.do_register(meta_for(7, 1, 1024), 60000, &gen), 0);
  EXPECT_EQ(gen, 1u);
  KvBlockMeta out;
  int64_t left = 0;
  EXPECT_EQ(reg.lookup(7, &out, &left), 0);
  EXPECT_EQ(out.generation, 1u);
  EXPECT_EQ(out.len, 1024u);
  EXPECT(left > 0 && left <= 60000);
  EXPECT(std::string(out.node) == "127.0.0.1:1");
  // Unknown block: miss.
  EXPECT_EQ(reg.lookup(8, &out), kEKvMiss);
  // Eviction removes; a later lookup misses.
  uint64_t egen = 0;
  EXPECT_EQ(reg.evict(7, &egen), 0);
  EXPECT_EQ(egen, 1u);
  EXPECT_EQ(reg.lookup(7, &out), kEKvMiss);
  EXPECT_EQ(reg.evict(7, &egen), kEKvMiss);

  // Lease expiry: a 60ms lease lapses and the record prunes lazily.
  EXPECT_EQ(reg.do_register(meta_for(9, 2, 64), 60, &gen), 0);
  EXPECT_EQ(reg.lookup(9, &out), 0);
  usleep(90 * 1000);
  EXPECT_EQ(reg.lookup(9, &out), kEKvMiss);
  // A lapsed lease cannot be renewed, only re-registered.
  EXPECT_EQ(reg.renew(9, 60000), kEKvMiss);
  EXPECT_EQ(reg.do_register(meta_for(9, 3, 64), 60, &gen), 0);
  EXPECT_EQ(reg.renew(9, 60000), 0);
  usleep(90 * 1000);  // outlives the ORIGINAL 60ms lease
  EXPECT_EQ(reg.lookup(9, &out), 0);  // renew extended it
}

TEST_CASE(kv_registry_double_register_rejected) {
  KvReset reset;
  KvRegistry& reg = kv_registry();
  uint64_t gen = 0;
  EXPECT_EQ(reg.do_register(meta_for(5, 1, 128), 60000, &gen), 0);
  // Same generation while live: exclusive ownership holds.
  EXPECT_EQ(reg.do_register(meta_for(5, 1, 128), 60000, &gen), kEKvExists);
  // Older generation after the block moved on: zombie publisher.
  EXPECT_EQ(reg.do_register(meta_for(5, 3, 128), 60000, &gen), 0);
  EXPECT_EQ(reg.do_register(meta_for(5, 2, 128), 60000, &gen), kEKvStale);
  // The newer generation replaced the record in place.
  KvBlockMeta out;
  EXPECT_EQ(reg.lookup(5, &out), 0);
  EXPECT_EQ(out.generation, 3u);
  // Generation 0 is never minted: malformed registration.
  EXPECT_EQ(reg.do_register(meta_for(6, 0, 128), 60000, &gen), kEKvStale);
}

// -- store ------------------------------------------------------------------

TEST_CASE(kv_store_publish_fetch_zero_copy_generations) {
  KvReset reset;
  const size_t len = 1 << 20;
  uint64_t rkey = 0;
  char* region = static_cast<char*>(rma_alloc(4 << 20, &rkey));
  EXPECT(region != nullptr);
  fill_pattern(region, len, 3);
  KvBlockMeta m;
  EXPECT_EQ(kv_store().publish(21, region, len, 60000, &m), 0);
  EXPECT_EQ(m.generation, 1u);
  EXPECT_EQ(m.rkey, rkey);
  EXPECT_EQ(m.off, 0u);
  // Double-publish of a live block: rejected.
  EXPECT_EQ(kv_store().publish(21, region, len, 60000, &m), kEKvExists);
  // Non-registered memory is not publishable (zero-copy serving only).
  char stack_buf[64];
  EXPECT_EQ(kv_store().publish(22, stack_buf, sizeof(stack_buf), 0, &m), -1);

  IOBuf out;
  EXPECT_EQ(kv_store().fetch(21, 1, &out), 0);
  EXPECT(check_pattern(out, len, 3));
  // Zero-copy: the served payload is ONE block pointing into the region.
  EXPECT_EQ(out.block_count(), 1u);

  // Wrong generation: stale, nothing served.
  IOBuf out2;
  EXPECT_EQ(kv_store().fetch(21, 2, &out2), kEKvStale);
  EXPECT_EQ(out2.size(), 0u);
  // Withdraw tombstones the generation; fetch answers stale (the caller
  // held a record once), unknown ids answer miss.
  EXPECT_EQ(kv_store().withdraw(21), 0);
  EXPECT_EQ(kv_store().fetch(21, 1, &out2), kEKvStale);
  EXPECT_EQ(kv_store().fetch(999, 1, &out2), kEKvMiss);
  // Re-publish continues the generation sequence.
  EXPECT_EQ(kv_store().publish(21, region, len, 60000, &m), 0);
  EXPECT_EQ(m.generation, 2u);
  IOBuf out3;
  EXPECT_EQ(kv_store().fetch(21, 1, &out3), kEKvStale);  // old record
  EXPECT_EQ(kv_store().fetch(21, 2, &out3), 0);
  rma_free(region);
}

TEST_CASE(kv_store_lease_expiry_never_admits_stale) {
  KvReset reset;
  const size_t len = 64 << 10;
  uint64_t rkey = 0;
  char* region = static_cast<char*>(rma_alloc(len, &rkey));
  EXPECT(region != nullptr);
  fill_pattern(region, len, 5);
  KvBlockMeta m;
  EXPECT_EQ(kv_store().publish(31, region, len, 60, &m), 0);
  IOBuf ok;
  EXPECT_EQ(kv_store().fetch(31, m.generation, &ok), 0);
  usleep(90 * 1000);
  // Validity is decided AT SERVE TIME: the lapsed lease serves nothing,
  // even with the generation the caller legitimately held.
  IOBuf out;
  EXPECT_EQ(kv_store().fetch(31, m.generation, &out), kEKvStale);
  EXPECT_EQ(out.size(), 0u);
  EXPECT_EQ(kv_store().count(), 0u);  // folded to a tombstone
  rma_free(region);
}

TEST_CASE(kv_store_eviction_under_budget_pressure) {
  KvReset reset;
  const size_t len = 1 << 20;
  FlagGuard budget("trpc_kv_store_bytes", std::to_string(3 << 20));
  uint64_t rkey = 0;
  char* region = static_cast<char*>(rma_alloc(8 << 20, &rkey));
  EXPECT(region != nullptr);
  KvBlockMeta m;
  for (uint64_t id = 1; id <= 3; ++id) {
    EXPECT_EQ(kv_store().publish(id, region + (id - 1) * len, len, 60000,
                                 &m), 0);
  }
  EXPECT_EQ(kv_store().count(), 3u);
  EXPECT_EQ(kv_store().bytes_used(), static_cast<uint64_t>(3 << 20));
  // Touch block 1 (a fetch bumps LRU), then publish block 4: the budget
  // holds 3 — the LRU victim must be block 2, never the just-touched 1.
  IOBuf touch;
  EXPECT_EQ(kv_store().fetch(1, 1, &touch), 0);
  EXPECT_EQ(kv_store().publish(4, region + 3 * len, len, 60000, &m), 0);
  EXPECT_EQ(kv_store().count(), 3u);
  IOBuf out;
  EXPECT_EQ(kv_store().fetch(2, 1, &out), kEKvStale);  // evicted
  EXPECT_EQ(kv_store().fetch(1, 1, &out), 0);          // LRU-protected
  // A block bigger than the whole budget is rejected outright.
  EXPECT_EQ(kv_store().publish(9, region, 4 << 20, 60000, &m), -1);
  // A re-publish of the evicted block mints a NEWER generation.
  EXPECT_EQ(kv_store().publish(2, region + len, len, 60000, &m), 0);
  EXPECT_EQ(m.generation, 2u);
  rma_free(region);
}

// -- RPC surface + cache ----------------------------------------------------

TEST_CASE(kv_rpc_end_to_end_with_cache_invalidation) {
  KvReset reset;
  start_once();
  const size_t len = 1 << 20;
  uint64_t rkey = 0;
  char* region = static_cast<char*>(rma_alloc(4 << 20, &rkey));
  EXPECT(region != nullptr);
  fill_pattern(region, len, 11);
  KvBlockMeta m;
  EXPECT_EQ(kv_store().publish(41, region, len, 60000, &m), 0);
  snprintf(m.node, sizeof(m.node), "%s", addr().c_str());

  Channel reg_ch;
  Channel::Options opts;
  opts.timeout_ms = 20000;
  EXPECT_EQ(reg_ch.Init(addr(), &opts), 0);
  // Register over the wire.
  {
    KvWire w;
    memset(&w, 0, sizeof(w));
    w.block_id = m.block_id;
    w.generation = m.generation;
    w.rkey = m.rkey;
    w.off = m.off;
    w.len = m.len;
    w.lease_ms = 60000;
    memcpy(w.node, m.node, sizeof(w.node));
    IOBuf req, resp;
    req.append(&w, sizeof(w));
    Controller cntl;
    reg_ch.CallMethod(kKvRegisterMethod, req, &resp, &cntl);
    EXPECT(!cntl.Failed());
    uint64_t gen = 0;
    EXPECT_EQ(resp.size(), sizeof(gen));
    resp.copy_to(&gen, sizeof(gen));
    EXPECT_EQ(gen, 1u);
  }

  KvCache cache(&reg_ch);
  KvBlockMeta got;
  EXPECT_EQ(cache.lookup(41, &got), 0);
  EXPECT_EQ(got.generation, 1u);
  EXPECT_EQ(cache.misses(), 1u);
  EXPECT_EQ(cache.lookup(41, &got), 0);  // cached
  EXPECT_EQ(cache.hits(), 1u);

  IOBuf bytes;
  EXPECT_EQ(cache.fetch(&reg_ch, 41, &bytes), 0);
  EXPECT(check_pattern(bytes, len, 11));

  // The publisher re-publishes (evict + publish = generation 2) and
  // re-registers; the decode side's CACHED generation-1 record must be
  // invalidated by the stale answer and the retry must land gen 2.
  EXPECT_EQ(kv_store().withdraw(41), 0);
  fill_pattern(region, len, 12);
  EXPECT_EQ(kv_store().publish(41, region, len, 60000, &m), 0);
  EXPECT_EQ(m.generation, 2u);
  {
    KvWire w;
    memset(&w, 0, sizeof(w));
    w.block_id = 41;
    w.generation = 2;
    w.rkey = m.rkey;
    w.off = m.off;
    w.len = m.len;
    w.lease_ms = 60000;
    snprintf(w.node, sizeof(w.node), "%s", addr().c_str());
    IOBuf req, resp;
    req.append(&w, sizeof(w));
    Controller cntl;
    reg_ch.CallMethod(kKvRegisterMethod, req, &resp, &cntl);
    EXPECT(!cntl.Failed());
  }
  IOBuf bytes2;
  const uint64_t misses_before = cache.misses();
  EXPECT_EQ(cache.fetch(&reg_ch, 41, &bytes2), 0);
  EXPECT(check_pattern(bytes2, len, 12));  // the NEW generation's bytes
  EXPECT_EQ(cache.misses(), misses_before + 1);  // stale → re-lookup
  rma_free(region);
}

// The batch forms: a block's records in one RPC, one status per entry.
namespace {

KvWire wire_for(uint64_t id, uint64_t gen, uint64_t len) {
  KvWire w;
  memset(&w, 0, sizeof(w));
  w.block_id = id;
  w.generation = gen;
  w.rkey = 0x42;
  w.len = len;
  w.lease_ms = 60000;
  snprintf(w.node, sizeof(w.node), "127.0.0.1:1");
  return w;
}

// One batch call: count + wires out, count + one Entry per wire back.
template <typename Entry>
std::vector<Entry> call_many(Channel* ch, const char* method,
                             const std::vector<KvWire>& wires,
                             uint64_t count, int* error_code) {
  IOBuf req, resp;
  req.append(&count, sizeof(count));
  req.append(wires.data(), wires.size() * sizeof(KvWire));
  Controller cntl;
  ch->CallMethod(method, req, &resp, &cntl);
  *error_code = cntl.Failed() ? cntl.error_code() : 0;
  std::vector<Entry> out;
  if (cntl.Failed()) {
    return out;
  }
  uint64_t n = 0;
  EXPECT(resp.size() >= sizeof(n));
  resp.copy_to(&n, sizeof(n));
  EXPECT_EQ(resp.size(), sizeof(n) + n * sizeof(Entry));
  out.resize(n);
  resp.copy_to(out.data(), n * sizeof(Entry), sizeof(n));
  return out;
}

}  // namespace

TEST_CASE(kv_registry_batch_wire_forms) {
  KvReset reset;
  start_once();
  Channel ch;
  Channel::Options opts;
  opts.timeout_ms = 20000;
  EXPECT_EQ(ch.Init(addr(), &opts), 0);
  int err = 0;

  // Register 61 records in one RPC; a duplicate in the middle (entry 30
  // repeats entry 3's block at the same generation) is kv-exists for
  // that entry alone, and generation 0 is that entry's kv-stale.
  std::vector<KvWire> wires;
  for (uint64_t i = 0; i < 61; ++i) {
    wires.push_back(wire_for(1000 + i, 1, 147456));
  }
  wires[30] = wire_for(1003, 1, 147456);
  wires[45].generation = 0;
  auto gens = call_many<KvManyGen>(&ch, kKvRegisterManyMethod, wires,
                                   wires.size(), &err);
  EXPECT_EQ(err, 0);
  EXPECT_EQ(gens.size(), 61u);
  for (uint64_t i = 0; i < gens.size(); ++i) {
    const int64_t want = i == 30 ? kEKvExists : i == 45 ? kEKvStale : 0;
    EXPECT_EQ(gens[i].status, want);
    EXPECT_EQ(gens[i].generation, want == 0 ? 1u : 0u);
  }
  EXPECT_EQ(kv_registry().count(), 59u);

  // Lookup: hits carry the record as KvReg.Lookup answers it, the two
  // unregistered ids miss in place, and the order is the request's.
  std::vector<KvWire> asks;
  for (uint64_t i = 0; i < 61; ++i) {
    asks.push_back(wire_for(1000 + i, 0, 0));
  }
  auto recs = call_many<KvManyRecord>(&ch, kKvLookupManyMethod, asks,
                                      asks.size(), &err);
  EXPECT_EQ(err, 0);
  EXPECT_EQ(recs.size(), 61u);
  for (uint64_t i = 0; i < recs.size(); ++i) {
    if (i == 30 || i == 45) {
      EXPECT_EQ(recs[i].status, kEKvMiss);
      EXPECT_EQ(recs[i].rec.block_id, 0u);
      continue;
    }
    EXPECT_EQ(recs[i].status, 0);
    EXPECT_EQ(recs[i].rec.block_id, 1000 + i);
    EXPECT_EQ(recs[i].rec.generation, 1u);
    EXPECT_EQ(recs[i].rec.len, 147456u);
    EXPECT(recs[i].rec.lease_ms > 0 && recs[i].rec.lease_ms <= 60000);
    EXPECT(std::string(recs[i].rec.node) == "127.0.0.1:1");
    KvBlockMeta single;
    EXPECT_EQ(kv_registry().lookup(1000 + i, &single), 0);
    EXPECT_EQ(single.generation, recs[i].rec.generation);
  }

  // Evict: the evicted generation per entry, a miss for the two holes.
  auto gone = call_many<KvManyGen>(&ch, kKvEvictManyMethod, asks,
                                   asks.size(), &err);
  EXPECT_EQ(err, 0);
  EXPECT_EQ(gone.size(), 61u);
  for (uint64_t i = 0; i < gone.size(); ++i) {
    const bool hole = i == 30 || i == 45;
    EXPECT_EQ(gone[i].status, hole ? kEKvMiss : 0);
    EXPECT_EQ(gone[i].generation, hole ? 0u : 1u);
  }
  EXPECT_EQ(kv_registry().count(), 0u);

  // A malformed request fails the CALL: count 0, a count over the cap,
  // and a count the body does not hold.
  call_many<KvManyGen>(&ch, kKvRegisterManyMethod, {}, 0, &err);
  EXPECT_EQ(err, EINVAL);
  std::vector<KvWire> over(kKvManyMax + 1, wire_for(1, 1, 8));
  call_many<KvManyGen>(&ch, kKvRegisterManyMethod, over, over.size(), &err);
  EXPECT_EQ(err, EINVAL);
  call_many<KvManyRecord>(&ch, kKvLookupManyMethod, asks, asks.size() + 1,
                          &err);
  EXPECT_EQ(err, EINVAL);
  EXPECT_EQ(kv_registry().count(), 0u);
  // The cap itself is served.
  over.pop_back();
  for (uint64_t i = 0; i < over.size(); ++i) {
    over[i].block_id = 5000 + i;
  }
  gens = call_many<KvManyGen>(&ch, kKvRegisterManyMethod, over, over.size(),
                              &err);
  EXPECT_EQ(err, 0);
  EXPECT_EQ(gens.size(), kKvManyMax);
  EXPECT_EQ(kv_registry().count(), kKvManyMax);
}

TEST_CASE(kv_fetch_rides_one_sided_over_shm) {
  KvReset reset;
  start_once();
  const size_t len = 8 << 20;
  uint64_t rkey = 0;
  char* region = static_cast<char*>(rma_alloc(len, &rkey));
  EXPECT(region != nullptr);
  fill_pattern(region, len, 21);
  KvBlockMeta m;
  EXPECT_EQ(kv_store().publish(51, region, len, 60000, &m), 0);

  Channel ch;
  Channel::Options opts;
  opts.timeout_ms = 60000;
  opts.use_shm = true;
  EXPECT_EQ(ch.Init(addr(), &opts), 0);
  {
    Controller warm;  // establish the ring
    IOBuf req, resp;
    req.append("warm");
    ch.CallMethod("Token.Step", req, &resp, &warm);
    EXPECT(!warm.Failed());
  }
  HotPathVars& v = hotpath_vars();
  const int64_t rx0 = v.rma_rx_msgs.get_value();
  KvWire w;
  memset(&w, 0, sizeof(w));
  w.block_id = 51;
  w.generation = m.generation;
  IOBuf req, resp;
  req.append(&w, sizeof(w));
  Controller cntl;
  cntl.set_timeout_ms(60000);
  ch.CallMethod(kKvFetchMethod, req, &resp, &cntl);
  EXPECT(!cntl.Failed());
  EXPECT(check_pattern(resp, len, 21));
  // The MB-scale response rode the one-sided window put, not the frame
  // plane: block-addressed transfer over the RMA fabric, verified.
  EXPECT(v.rma_rx_msgs.get_value() > rx0);
  rma_free(region);
}

TEST_CASE(kv_chunk_fault_whole_or_nothing_and_recovery) {
  KvReset reset;
  start_once();
  const size_t len = 8 << 20;
  uint64_t rkey = 0;
  char* region = static_cast<char*>(rma_alloc(len, &rkey));
  EXPECT(region != nullptr);
  fill_pattern(region, len, 31);
  KvBlockMeta m;
  EXPECT_EQ(kv_store().publish(61, region, len, 600000, &m), 0);

  Channel ch;
  Channel::Options opts;
  opts.timeout_ms = 60000;
  opts.use_shm = true;
  EXPECT_EQ(ch.Init(addr(), &opts), 0);
  {
    Controller warm;
    IOBuf req, resp;
    req.append("warm");
    ch.CallMethod("Token.Step", req, &resp, &warm);
    EXPECT(!warm.Failed());
  }
  KvWire w;
  memset(&w, 0, sizeof(w));
  w.block_id = 61;
  w.generation = m.generation;
  {
    FaultGuard guard;
    EXPECT_EQ(FaultActor::global().set("seed=11;drop=0.7"), 0);
    IOBuf req, resp;
    req.append(&w, sizeof(w));
    Controller cntl;
    cntl.set_timeout_ms(1500);
    ch.CallMethod(kKvFetchMethod, req, &resp, &cntl);
    // Dropped chunks leave completion bits clear: the block fetch fails
    // WHOLE — no partial bytes are ever dispatched as a response.
    EXPECT(cntl.Failed());
    EXPECT_EQ(resp.size(), 0u);
  }
  // Faults cleared: the SAME cached record still works (transport
  // failures never invalidate the block's generation), byte-exact.
  IOBuf req2, resp2;
  req2.append(&w, sizeof(w));
  Controller ok;
  ok.set_timeout_ms(60000);
  ch.CallMethod(kKvFetchMethod, req2, &resp2, &ok);
  EXPECT(!ok.Failed());
  EXPECT(check_pattern(resp2, len, 31));
  rma_free(region);
}

// -- content-addressed prefix cache (ISSUE 17) ------------------------------

namespace {

KvPrefixMeta prefix_meta_for(const Key128& key, const Key128& hash,
                             uint64_t gen, uint64_t len,
                             const char* node, uint32_t depth = 0) {
  KvPrefixMeta m;
  m.key = key;
  m.hash = hash;
  m.generation = gen;
  m.len = len;
  m.depth = depth;
  snprintf(m.node, sizeof(m.node), "%s", node);
  return m;
}

Key128 k128(uint64_t hi, uint64_t lo) {
  Key128 k;
  k.hi = hi;
  k.lo = lo;
  return k;
}

}  // namespace

TEST_CASE(kv_prefix_registry_dedup_replica_sets) {
  KvReset reset;
  KvRegistry& reg = kv_registry();
  const Key128 key = k128(0x11, 0x22);
  const Key128 hash = k128(0xAA, 0xBB);
  uint64_t gen = 0;
  // Two publishers of the SAME (key, hash): one record, two replicas.
  EXPECT_EQ(reg.put_prefix(
                prefix_meta_for(key, hash, 1, 4096, "127.0.0.1:1"),
                60000, &gen), 0);
  const uint64_t dedup0 =
      KvPrefixCounters::read(kv_prefix_counters().dedup);
  EXPECT_EQ(reg.put_prefix(
                prefix_meta_for(key, hash, 1, 4096, "127.0.0.1:2"),
                60000, &gen), 0);
  EXPECT_EQ(reg.prefix_count(), 1u);
  EXPECT_EQ(reg.prefix_replicas(), 2u);
  EXPECT_EQ(KvPrefixCounters::read(kv_prefix_counters().dedup),
            dedup0 + 1);
  // Same node, same generation: idempotent renew (every cache hit
  // re-offers), answered kEKvExists — no third replica.
  EXPECT_EQ(reg.put_prefix(
                prefix_meta_for(key, hash, 1, 4096, "127.0.0.1:1"),
                60000, &gen), kEKvExists);
  EXPECT_EQ(reg.prefix_replicas(), 2u);
  // Same node, newer generation: replaces in place.
  EXPECT_EQ(reg.put_prefix(
                prefix_meta_for(key, hash, 3, 4096, "127.0.0.1:1"),
                60000, &gen), 0);
  EXPECT_EQ(gen, 3u);
  EXPECT_EQ(reg.prefix_replicas(), 2u);
  // Zombie publisher re-offering an older generation: fenced.
  EXPECT_EQ(reg.put_prefix(
                prefix_meta_for(key, hash, 2, 4096, "127.0.0.1:1"),
                60000, &gen), kEKvStale);
  // Same chain key, DIFFERENT content hash: divergence, never aliased.
  EXPECT_EQ(reg.put_prefix(
                prefix_meta_for(key, k128(0xAA, 0xCC), 1, 4096,
                                "127.0.0.1:3"),
                60000, &gen), kEKvStale);
  // Generation 0 is never minted: malformed.
  EXPECT_EQ(reg.put_prefix(
                prefix_meta_for(key, hash, 0, 4096, "127.0.0.1:4"),
                60000, &gen), kEKvStale);
}

TEST_CASE(kv_prefix_replica_lease_expiry_and_zombie_fence) {
  KvReset reset;
  KvRegistry& reg = kv_registry();
  const Key128 key = k128(0x31, 0x32);
  const Key128 hash = k128(0xDD, 0xEE);
  uint64_t gen = 0;
  EXPECT_EQ(reg.put_prefix(
                prefix_meta_for(key, hash, 5, 1024, "127.0.0.1:1"),
                60, &gen), 0);
  EXPECT_EQ(reg.put_prefix(
                prefix_meta_for(key, hash, 2, 1024, "127.0.0.1:2"),
                60000, &gen), 0);
  EXPECT_EQ(reg.prefix_replicas(), 2u);
  usleep(90 * 1000);  // node 1's lease lapses; node 2's holds
  std::vector<KvPrefixMeta> out;
  EXPECT_EQ(reg.match(&key, 1, &out), 1u);
  EXPECT_EQ(out.size(), 1u);  // the expired replica pruned in match
  EXPECT(std::string(out[0].node) == "127.0.0.1:2");
  // The per-node fence SURVIVES pruning: node 1 re-offering its old
  // generation is still a zombie; a fresh generation re-admits.
  EXPECT_EQ(reg.put_prefix(
                prefix_meta_for(key, hash, 4, 1024, "127.0.0.1:1"),
                60000, &gen), kEKvStale);
  EXPECT_EQ(reg.put_prefix(
                prefix_meta_for(key, hash, 6, 1024, "127.0.0.1:1"),
                60000, &gen), 0);
  EXPECT_EQ(reg.prefix_replicas(), 2u);
}

TEST_CASE(kv_prefix_trie_longest_match_walk) {
  KvReset reset;
  // Chain keys: deterministic, prefix-stable, block-size-sensitive.
  uint64_t tokens[512];
  for (size_t i = 0; i < 512; ++i) {
    tokens[i] = 1000 + i;
  }
  Key128 chain[4], chain2[4], shorter[2];
  EXPECT_EQ(kv_prefix_chain(tokens, 512, 128, chain, 4), 4u);
  EXPECT_EQ(kv_prefix_chain(tokens, 512, 128, chain2, 4), 4u);
  EXPECT_EQ(kv_prefix_chain(tokens, 300, 128, shorter, 2), 2u);
  for (int i = 0; i < 4; ++i) {
    EXPECT(chain[i] == chain2[i]);
  }
  EXPECT(chain[0] == shorter[0] && chain[1] == shorter[1]);
  Key128 other_bs[2];
  EXPECT_EQ(kv_prefix_chain(tokens, 512, 256, other_bs, 2), 2u);
  EXPECT(other_bs[0] != chain[0]);  // block size folds into the keys
  // A diverging token in block 1 changes keys 1..3 but not key 0.
  uint64_t diverged[512];
  memcpy(diverged, tokens, sizeof(tokens));
  diverged[200] ^= 1;
  Key128 chain_d[4];
  EXPECT_EQ(kv_prefix_chain(diverged, 512, 128, chain_d, 4), 4u);
  EXPECT(chain_d[0] == chain[0]);
  EXPECT(chain_d[1] != chain[1] && chain_d[3] != chain[3]);

  // Registry walk: 3 of 4 blocks cached -> longest prefix is 3; a hole
  // at depth 1 stops the walk at 1 regardless of deeper blocks.
  KvRegistry& reg = kv_registry();
  const Key128 hash = k128(0x77, 0x88);
  uint64_t gen = 0;
  for (uint32_t d = 0; d < 3; ++d) {
    EXPECT_EQ(reg.put_prefix(
                  prefix_meta_for(chain[d], k128(0x77, 0x88 + d), 1,
                                  4096, "127.0.0.1:1", d),
                  60000, &gen), 0);
  }
  (void)hash;
  std::vector<KvPrefixMeta> out;
  std::vector<int64_t> leases;
  EXPECT_EQ(reg.match(chain, 4, &out, &leases), 3u);
  EXPECT_EQ(out.size(), 3u);
  EXPECT_EQ(leases.size(), 3u);
  for (size_t i = 0; i < out.size(); ++i) {
    EXPECT_EQ(out[i].depth, static_cast<uint32_t>(i));
    EXPECT(leases[i] > 0);
  }
  EXPECT_EQ(reg.evict_prefix(chain[1], "127.0.0.1:1"), 0);
  EXPECT_EQ(reg.match(chain, 4, nullptr), 1u);  // the walk stops at the hole
}

TEST_CASE(kv_prefix_two_tier_promotion_on_hit) {
  KvReset reset;
  FlagGuard hot("trpc_kv_prefix_hot_bytes", std::to_string(1 << 20));
  const size_t len = 768 << 10;
  std::string a(len, '\0'), b(len, '\0');
  fill_pattern(a.data(), len, 41);
  fill_pattern(b.data(), len, 42);
  uint64_t toks_a[4] = {1, 2, 3, 4}, toks_b[4] = {5, 6, 7, 8};
  KvPrefixMeta ma, mb;
  EXPECT_EQ(kv_store().publish_prefix(k128(1, 1), 0, a.data(), len,
                                      toks_a, 4, 60000, &ma), 0);
  EXPECT_EQ(ma.generation, 1u);
  EXPECT(ma.rkey != 0);  // hot: registered pages
  EXPECT_EQ(kv_store().prefix_hot_bytes(), len);
  // Identical re-publish: the cache-hit path — kEKvExists, record
  // echoed, NO new bytes admitted.
  KvPrefixMeta dup;
  EXPECT_EQ(kv_store().publish_prefix(k128(1, 1), 0, a.data(), len,
                                      toks_a, 4, 60000, &dup), kEKvExists);
  EXPECT(dup.hash == ma.hash);
  EXPECT_EQ(kv_store().prefix_count(), 1u);
  // Block B exceeds the remaining hot budget: A (LRU) demotes, B lands
  // hot.  Nothing drops.
  const uint64_t demote0 =
      KvPrefixCounters::read(kv_prefix_counters().demote);
  EXPECT_EQ(kv_store().publish_prefix(k128(1, 2), 1, b.data(), len,
                                      toks_b, 4, 60000, &mb), 0);
  EXPECT_EQ(kv_store().prefix_count(), 2u);
  EXPECT_EQ(kv_store().prefix_hot_bytes(), len);
  EXPECT_EQ(kv_store().prefix_cold_bytes(), len);
  EXPECT_EQ(KvPrefixCounters::read(kv_prefix_counters().demote),
            demote0 + 1);
  // Fetching demoted A is a COLD hit that promotes it back (B demotes
  // in turn) — the bytes are identical either way.
  const uint64_t promote0 =
      KvPrefixCounters::read(kv_prefix_counters().promote);
  IOBuf out_a;
  EXPECT_EQ(kv_store().fetch_prefix(ma.hash, ma.generation, &out_a), 0);
  EXPECT(check_pattern(out_a, len, 41));
  EXPECT_EQ(KvPrefixCounters::read(kv_prefix_counters().promote),
            promote0 + 1);
  EXPECT_EQ(kv_store().prefix_hot_bytes(), len);   // A hot again
  EXPECT_EQ(kv_store().prefix_cold_bytes(), len);  // B demoted
  // A second fetch of A is a hot zero-copy hit.
  const uint64_t hot0 =
      KvPrefixCounters::read(kv_prefix_counters().hot_hits);
  IOBuf out_a2;
  EXPECT_EQ(kv_store().fetch_prefix(ma.hash, ma.generation, &out_a2), 0);
  EXPECT(check_pattern(out_a2, len, 41));
  EXPECT_EQ(out_a2.block_count(), 1u);  // served from registered pages
  EXPECT_EQ(KvPrefixCounters::read(kv_prefix_counters().hot_hits),
            hot0 + 1);
  // Wrong generation: stale.  Unknown hash: miss.
  IOBuf bad;
  EXPECT_EQ(kv_store().fetch_prefix(ma.hash, 99, &bad), kEKvStale);
  EXPECT_EQ(kv_store().fetch_prefix(k128(9, 9), 0, &bad), kEKvMiss);
}

TEST_CASE(kv_prefix_demote_under_budget_drops_cold_last) {
  KvReset reset;
  FlagGuard total("trpc_kv_store_bytes", std::to_string(3 << 20));
  FlagGuard hot("trpc_kv_prefix_hot_bytes", std::to_string(1 << 20));
  const size_t len = 1 << 20;
  std::string buf(len, '\0');
  KvPrefixMeta m[4];
  for (uint64_t i = 0; i < 4; ++i) {
    fill_pattern(buf.data(), len, 50 + i);
    uint64_t toks[2] = {i, i + 1};
    EXPECT_EQ(kv_store().publish_prefix(k128(2, i), 0, buf.data(), len,
                                        toks, 2, 60000, &m[i]), 0);
  }
  // Budget holds 3 x 1MB: block 0 (the LRU COLD block) dropped with a
  // tombstone; 1..3 survive — the newest hot, the others demoted.
  EXPECT_EQ(kv_store().prefix_count(), 3u);
  EXPECT_EQ(kv_store().prefix_hot_bytes(), len);
  EXPECT_EQ(kv_store().prefix_cold_bytes(), 2 * len);
  IOBuf out;
  EXPECT_EQ(kv_store().fetch_prefix(m[0].hash, m[0].generation, &out),
            kEKvStale);  // dropped block: tombstoned, never silent
  EXPECT_EQ(kv_store().fetch_prefix(m[1].hash, m[1].generation, &out), 0);
  EXPECT(check_pattern(out, len, 51));
  // A re-publish of the dropped block mints a NEWER generation.
  fill_pattern(buf.data(), len, 50);
  uint64_t toks0[2] = {0, 1};
  KvPrefixMeta again;
  EXPECT_EQ(kv_store().publish_prefix(k128(2, 0), 0, buf.data(), len,
                                      toks0, 2, 60000, &again), 0);
  EXPECT_EQ(again.generation, m[0].generation + 1);
  // Drain tombstones EVERY prefix block (successor re-homing relies on
  // the stale answer, never on silence).
  EXPECT(kv_store().withdraw_all() >= 3u);
  EXPECT_EQ(kv_store().prefix_count(), 0u);
  EXPECT_EQ(kv_store().fetch_prefix(again.hash, again.generation, &out),
            kEKvStale);
}

// The tier moves copy OUTSIDE the store's lock: while one thread's cold
// hits promote 8 MB blocks (each displacing the other, so a demote a
// promote), fetches of another, hot block are served — many a move, not
// one a gap between moves — and publishes in place and by copy go on
// beside them.  Under TSan this is the race check of the mover protocol
// (moving flag, re-find by generation, reserved hot room).
TEST_CASE(kv_prefix_moves_run_outside_the_lock) {
  KvReset reset;
  const size_t big = 8u << 20;
  const size_t small = 64u << 10;
  // Hot room for the small block and ONE big one; everything fits cold.
  FlagGuard hot("trpc_kv_prefix_hot_bytes",
                std::to_string(big + small + (1 << 20)));
  FlagGuard total("trpc_kv_store_bytes", std::to_string(64ll << 20));
  std::string a(big, '\0'), b(big, '\0'), c(small, '\0');
  fill_pattern(a.data(), big, 71);
  fill_pattern(b.data(), big, 72);
  fill_pattern(c.data(), small, 73);
  uint64_t toks[2] = {7, 8};
  KvPrefixMeta ma, mb, mc;
  EXPECT_EQ(kv_store().publish_prefix(k128(3, 1), 0, a.data(), big, toks, 2,
                                      60000, &ma), 0);
  EXPECT_EQ(kv_store().publish_prefix(k128(3, 2), 1, b.data(), big, toks, 2,
                                      60000, &mb), 0);  // demotes A
  EXPECT_EQ(kv_store().publish_prefix(k128(3, 3), 2, c.data(), small, toks,
                                      2, 60000, &mc), 0);
  EXPECT_EQ(kv_store().prefix_hot_bytes(), big + small);
  EXPECT_EQ(kv_store().prefix_cold_bytes(), big);
  const uint64_t promote0 =
      KvPrefixCounters::read(kv_prefix_counters().promote);
  const uint64_t demote0 =
      KvPrefixCounters::read(kv_prefix_counters().demote);
  const int kMoves = 24;
  std::atomic<bool> moving{false};
  std::atomic<bool> stop{false};
  std::atomic<int> served_during_moves{0};
  std::atomic<int> wrong{0};
  std::thread mover([&] {
    for (int i = 0; i < kMoves; ++i) {
      const KvPrefixMeta& m = i % 2 == 0 ? ma : mb;  // always the cold one
      IOBuf out;
      moving.store(true);
      const int rc = kv_store().fetch_prefix(m.hash, m.generation, &out);
      moving.store(false);
      if (rc != 0 || !check_pattern(out, big, i % 2 == 0 ? 71 : 72)) {
        wrong.fetch_add(1);
      }
    }
    stop.store(true);
  });
  std::thread publisher([&] {
    // Fresh content each round, copied (a heap source): it lands cold or
    // hot by what room there is, and is withdrawn again.
    std::string d(small, '\0');
    for (uint32_t i = 0; !stop.load(); ++i) {
      fill_pattern(d.data(), small, 100 + i);
      uint64_t t[1] = {i};
      KvPrefixMeta md;
      if (kv_store().publish_prefix(k128(4, i + 1), 0, d.data(), small, t,
                                    1, 60000, &md) != 0 ||
          kv_store().withdraw_prefix(md.hash) != 0) {
        wrong.fetch_add(1);
      }
    }
  });
  while (!stop.load()) {
    IOBuf out;
    const bool during = moving.load();
    if (kv_store().fetch_prefix(mc.hash, mc.generation, &out) != 0 ||
        out.size() != small) {
      wrong.fetch_add(1);
    } else if (during && moving.load()) {
      served_during_moves.fetch_add(1);
    }
  }
  mover.join();
  publisher.join();
  EXPECT_EQ(wrong.load(), 0);
  // Every mover fetch was a cold hit that promoted and displaced the
  // other big block (and the small one, where a move found it the least
  // recently touched: its next fetch promoted it back).
  EXPECT(KvPrefixCounters::read(kv_prefix_counters().promote) >=
         promote0 + kMoves);
  EXPECT(KvPrefixCounters::read(kv_prefix_counters().demote) >=
         demote0 + kMoves);
  // A move is two copies of 8 MB; a fetch of the small block is
  // microseconds.  Copies under the lock would let one through a move
  // at best.
  EXPECT(served_during_moves.load() > 10 * kMoves);
  EXPECT(kv_store().prefix_hot_bytes() <= big + small + (1 << 20));
  IOBuf last;
  EXPECT_EQ(kv_store().fetch_prefix(mc.hash, mc.generation, &last), 0);
  EXPECT(check_pattern(last, small, 73));
}

// The two tiers are ONE order by last touch: whatever touches a block
// leaves it hot.  A fetch of a hot block that arrives while a demote is
// copying that block (a window's fetches reach a chain's hot blocks while
// its cold ones, promoted, push them out) cancels the demote: installed,
// the block would lie in the heap tier in front of every hot block and be
// dropped before them, the most recently used first.  In every
// interleaving of the two fetches the block just fetched ends hot and the
// block nobody touched ends in the heap tier.
TEST_CASE(kv_prefix_a_block_touched_during_its_demote_stays_hot) {
  KvReset reset;
  const size_t big = 8u << 20;
  FlagGuard hot("trpc_kv_prefix_hot_bytes",
                std::to_string(2 * big + (1 << 20)));
  FlagGuard total("trpc_kv_store_bytes", std::to_string(64ll << 20));
  std::string buf(big, '\0');
  KvPrefixMeta m[3];
  uint64_t toks[1] = {3};
  for (int i = 0; i < 3; ++i) {
    fill_pattern(buf.data(), big, 90 + i);
    EXPECT_EQ(kv_store().publish_prefix(k128(6, i + 1), i, buf.data(), big,
                                        toks, 1, 60000, &m[i]), 0);
  }
  KvPrefixCounters& c = kv_prefix_counters();
  int cold = 0, front = 1, back = 2;  // block 0 was demoted by block 2
  std::atomic<int> wrong{0};
  auto fetch = [&](int i) {
    IOBuf out;
    if (kv_store().fetch_prefix(m[i].hash, m[i].generation, &out) != 0 ||
        !check_pattern(out, big, 90 + i)) {
      wrong.fetch_add(1);
    }
  };
  for (int round = 0; round < 24; ++round) {
    fetch(back);  // a hot hit: `front` is the least recently touched now
    std::thread promoter([&] { fetch(cold); });  // demotes `front` ...
    std::thread toucher([&] {
      std::this_thread::sleep_for(std::chrono::microseconds(80 * round));
      fetch(front);  // ... which is fetched before, during or after that
    });
    promoter.join();
    toucher.join();
    const uint64_t hot_hits = KvPrefixCounters::read(c.hot_hits);
    fetch(front);
    fetch(cold);
    EXPECT_EQ(KvPrefixCounters::read(c.hot_hits), hot_hits + 2);
    EXPECT_EQ(kv_store().prefix_hot_bytes(), 2 * big);
    EXPECT_EQ(kv_store().prefix_cold_bytes(), big);
    std::swap(cold, back);  // the block nobody touched went to the heap
  }
  EXPECT_EQ(wrong.load(), 0);
}

// A publish of content the store holds in the heap tier brings the block
// hot on the publisher's bytes, in place where they lie in registered
// memory: same generation, the heap copy let go, and the block is then
// dropped after the ones touched before it, not before them.
TEST_CASE(kv_prefix_a_renewal_brings_a_heap_block_hot) {
  KvReset reset;
  const size_t len = 1 << 20;
  FlagGuard hot("trpc_kv_prefix_hot_bytes", std::to_string(2 * len));
  FlagGuard total("trpc_kv_store_bytes", std::to_string(4 * len));
  uint64_t rkey = 0;
  char* region = static_cast<char*>(rma_alloc(len, &rkey));
  EXPECT(region != nullptr);
  std::string buf(len, '\0');
  KvPrefixMeta m[5];
  auto publish = [&](uint64_t i, const void* data, bool in_place,
                     KvPrefixMeta* out) {
    uint64_t toks[1] = {i};
    return kv_store().publish_prefix(k128(7, i + 1), 0, data, len, toks, 1,
                                     60000, out, 0, in_place);
  };
  for (uint64_t i = 0; i < 3; ++i) {
    fill_pattern(buf.data(), len, 110 + i);
    EXPECT_EQ(publish(i, buf.data(), false, &m[i]), 0);
  }
  EXPECT_EQ(kv_store().prefix_cold_bytes(), len);  // block 0
  KvPrefixCounters& c = kv_prefix_counters();
  const uint64_t renewed0 = KvPrefixCounters::read(c.publish_renewed);
  const uint64_t brought0 = KvPrefixCounters::read(c.renew_promote);
  const uint64_t promote0 = KvPrefixCounters::read(c.promote);
  const uint64_t bytes0 = KvPrefixCounters::read(c.publish_bytes);
  fill_pattern(region, len, 110);
  KvPrefixMeta again;
  EXPECT_EQ(publish(0, region, true, &again), kEKvExists);
  EXPECT_EQ(again.generation, m[0].generation);
  EXPECT_EQ(again.rkey, rkey);  // served from the publisher's region now
  EXPECT_EQ(KvPrefixCounters::read(c.publish_renewed), renewed0 + 1);
  EXPECT_EQ(KvPrefixCounters::read(c.renew_promote), brought0 + 1);
  EXPECT_EQ(KvPrefixCounters::read(c.promote), promote0);  // fetches' only
  EXPECT_EQ(KvPrefixCounters::read(c.publish_bytes), bytes0);
  EXPECT_EQ(kv_store().prefix_hot_bytes(), 2 * len);   // blocks 2 and 0
  EXPECT_EQ(kv_store().prefix_cold_bytes(), len);      // block 1
  rma_free(region);  // the block co-owns the mapping
  // A renewal of a hot block, and one from a heap source, copy nothing
  // they need not: the first touches, the second copies once.
  fill_pattern(buf.data(), len, 112);
  EXPECT_EQ(publish(2, buf.data(), false, &again), kEKvExists);
  EXPECT_EQ(KvPrefixCounters::read(c.renew_promote), brought0 + 1);
  // Two more blocks pass the total budget: block 1, touched least
  // recently, goes; block 0 stays.
  for (uint64_t i = 3; i < 5; ++i) {
    fill_pattern(buf.data(), len, 110 + i);
    EXPECT_EQ(publish(i, buf.data(), false, &m[i]), 0);
  }
  IOBuf out;
  EXPECT_EQ(kv_store().fetch_prefix(m[1].hash, m[1].generation, &out),
            kEKvStale);
  EXPECT_EQ(kv_store().fetch_prefix(m[0].hash, m[0].generation, &out), 0);
  EXPECT(check_pattern(out, len, 110));
  fill_pattern(buf.data(), len, 112);
  // Block 2 went to the heap meanwhile: renewed from a heap source it
  // comes back on one copy.
  EXPECT_EQ(publish(2, buf.data(), false, &again), kEKvExists);
  EXPECT_EQ(KvPrefixCounters::read(c.renew_promote), brought0 + 2);
  IOBuf two;
  EXPECT_EQ(kv_store().fetch_prefix(m[2].hash, m[2].generation, &two), 0);
  EXPECT(check_pattern(two, len, 112));
}

// A block taken IN PLACE is served from the caller's registered pages
// and co-owns their mapping until it is demoted or dropped; any other
// source is copied once.  The counters say which.
TEST_CASE(kv_prefix_publish_in_place_or_one_copy) {
  KvReset reset;
  FlagGuard hot("trpc_kv_prefix_hot_bytes", std::to_string(4 << 20));
  const size_t len = 1 << 20;
  uint64_t rkey = 0;
  char* region = static_cast<char*>(rma_alloc(2 * len, &rkey));
  EXPECT(region != nullptr);
  fill_pattern(region, len, 81);
  fill_pattern(region + len, len, 82);
  KvPrefixCounters& c = kv_prefix_counters();
  const uint64_t in_place0 = KvPrefixCounters::read(c.publish_in_place_bytes);
  const uint64_t copy0 = KvPrefixCounters::read(c.publish_copy_bytes);
  uint64_t toks[1] = {9};
  KvPrefixMeta m1, m2, m3;
  EXPECT_EQ(kv_store().publish_prefix(k128(5, 1), 0, region, len, toks, 1,
                                      60000, &m1, 0, /*in_place=*/true), 0);
  // The second half of the same region, not offered in place: copied.
  EXPECT_EQ(kv_store().publish_prefix(k128(5, 2), 1, region + len, len,
                                      toks, 1, 60000, &m2), 0);
  // A heap source offered in place: no registered memory, so copied.
  std::string heap(len, '\0');
  fill_pattern(heap.data(), len, 83);
  EXPECT_EQ(kv_store().publish_prefix(k128(5, 3), 2, heap.data(), len, toks,
                                      1, 60000, &m3, 0, /*in_place=*/true),
            0);
  EXPECT_EQ(KvPrefixCounters::read(c.publish_in_place_bytes),
            in_place0 + len);
  EXPECT_EQ(KvPrefixCounters::read(c.publish_copy_bytes), copy0 + 2 * len);
  EXPECT_EQ(m1.rkey, rkey);  // served from the caller's region itself
  EXPECT(m2.rkey != rkey && m2.rkey != 0);
  IOBuf out;
  EXPECT_EQ(kv_store().fetch_prefix(m1.hash, m1.generation, &out), 0);
  EXPECT(check_pattern(out, len, 81));
  // rma_free drops the caller's reference; the block co-owns the
  // mapping, so its bytes stay served until it is dropped.
  rma_free(region);
  IOBuf again;
  EXPECT_EQ(kv_store().fetch_prefix(m1.hash, m1.generation, &again), 0);
  EXPECT(check_pattern(again, len, 81));
  EXPECT_EQ(kv_store().withdraw_prefix(m1.hash), 0);
  EXPECT_EQ(kv_store().fetch_prefix(m1.hash, m1.generation, &again),
            kEKvStale);
}

// KvReg.PutPrefixMany records N replicas in one call, each entry with
// the answer its own PutPrefix would have got.
TEST_CASE(kv_prefix_put_many_is_n_puts) {
  KvReset reset;
  start_once();
  Channel ch;
  EXPECT_EQ(ch.Init(addr()), 0);
  const uint64_t n = 5;
  std::vector<KvPrefixWire> wires(n);
  uint64_t tokens[5 * 4];
  for (size_t i = 0; i < 20; ++i) {
    tokens[i] = 1000 + i;
  }
  Key128 chain[5];
  EXPECT_EQ(kv_prefix_chain(tokens, 20, 4, chain, 5), 5u);
  for (uint64_t i = 0; i < n; ++i) {
    memset(&wires[i], 0, sizeof(KvPrefixWire));
    wires[i].key_hi = chain[i].hi;
    wires[i].key_lo = chain[i].lo;
    wires[i].hash_hi = 0x50 + i;
    wires[i].hash_lo = 0x60 + i;
    wires[i].generation = i == 3 ? 0 : 1;  // generation 0: never minted
    wires[i].len = 4096;
    wires[i].depth = static_cast<uint32_t>(i);
    wires[i].lease_ms = 60000;
    snprintf(wires[i].node, sizeof(wires[i].node), "127.0.0.1:1");
  }
  const uint64_t many0 =
      KvPrefixCounters::read(kv_prefix_counters().put_many_records);
  for (int round = 0; round < 2; ++round) {
    IOBuf req, resp;
    req.append(&n, sizeof(n));
    req.append(wires.data(), n * sizeof(KvPrefixWire));
    Controller cntl;
    ch.CallMethod(kKvPrefixPutManyMethod, req, &resp, &cntl);
    EXPECT(!cntl.Failed());
    EXPECT_EQ(resp.size(), sizeof(n) + n * sizeof(KvManyGen));
    uint64_t count = 0;
    resp.copy_to(&count, sizeof(count));
    EXPECT_EQ(count, n);
    std::vector<KvManyGen> answers(n);
    resp.copy_to(answers.data(), n * sizeof(KvManyGen), sizeof(count));
    for (uint64_t i = 0; i < n; ++i) {
      // The first call accepts, the second is the idempotent re-offer;
      // the record that never had a generation is refused both times.
      const int64_t want = i == 3 ? kEKvStale : round == 0 ? 0 : kEKvExists;
      EXPECT_EQ(answers[i].status, want);
      if (i != 3) {
        EXPECT_EQ(answers[i].generation, 1u);
      }
    }
  }
  EXPECT_EQ(KvPrefixCounters::read(kv_prefix_counters().put_many_records),
            many0 + 2 * n);
  // The walk stops at the record that was refused.
  EXPECT_EQ(kv_registry().match(chain, 5, nullptr), 3u);
  EXPECT_EQ(kv_registry().prefix_count(), 4u);
  // A request that does not parse fails the call, as RegisterMany's.
  IOBuf bad, resp;
  const uint64_t zero = 0;
  bad.append(&zero, sizeof(zero));
  Controller cntl;
  ch.CallMethod(kKvPrefixPutManyMethod, bad, &resp, &cntl);
  EXPECT(cntl.Failed());
}

TEST_CASE(kv_prefix_run_hashes_four_at_a_time_as_one_by_one) {
  KvReset reset;
  const size_t len = 4093;  // a tail that is not a word
  const size_t n = 9;       // groups of 4, 4 and 1
  std::vector<std::string> bytes(n, std::string(len, '\0'));
  std::vector<uint64_t> toks(n);
  std::vector<KvStore::PrefixPage> pages(n);
  const void* data[n];
  const uint64_t* spans[n];
  size_t ntokens[n];
  for (size_t j = 0; j < n; ++j) {
    fill_pattern(bytes[j].data(), len, 90 + static_cast<uint32_t>(j));
    toks[j] = 7 * j;
    pages[j].key = k128(8, j + 1);
    pages[j].data = data[j] = bytes[j].data();
    pages[j].tokens = spans[j] = &toks[j];
    pages[j].ntokens = ntokens[j] = j % 2;  // spans empty and not
  }
  Key128 lanes[n];
  kv_content_hash_lanes(data, len, spans, ntokens, n, lanes);
  for (size_t j = 0; j < n; ++j) {
    Key128 one;
    kv_content_hash(data[j], len, spans[j], ntokens[j], &one);
    EXPECT(lanes[j] == one);
  }
  KvPrefixCounters& c = kv_prefix_counters();
  const uint64_t grouped0 = KvPrefixCounters::read(c.hash_lanes);
  int rcs[n];
  KvPrefixMeta metas[n];
  EXPECT_EQ(kv_store().publish_prefix_run(pages.data(), n, len, 2, 60000,
                                          rcs, metas),
            n);
  EXPECT_EQ(KvPrefixCounters::read(c.hash_lanes), grouped0 + 8);
  for (size_t j = 0; j < n; ++j) {
    EXPECT_EQ(rcs[j], 0);
    EXPECT(metas[j].hash == lanes[j]);
    EXPECT_EQ(metas[j].depth, 2 + j);
  }
  // A run of one is publish_prefix: renewed, and grouped with nothing.
  KvPrefixMeta again;
  EXPECT_EQ(kv_store().publish_prefix(pages[8].key, 10, data[8], len,
                                      spans[8], ntokens[8], 60000, &again),
            kEKvExists);
  EXPECT(again.hash == lanes[8]);
  EXPECT_EQ(KvPrefixCounters::read(c.hash_lanes), grouped0 + 8);
}

TEST_MAIN
