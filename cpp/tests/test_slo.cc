// SLO / fleet-observability tests (stat/slo.h + stat/digest.h +
// net/naming.h fleet publication, ISSUE 19): flag-off invisibility with
// every slo_* var frozen at 0, digest wire roundtrip, the
// merge-vs-pooled-oracle property (fleet percentiles from octave-wise
// sample pooling stay within the recorder's one-octave bound of a
// single recorder that saw all the traffic), spec parsing, compressed-
// window burn-rate breach fire + clear counted on edges only,
// the fleet blob roundtrip, and in-process Announcer publication +
// /fleet merge over a live naming registry.  Rides TSan/ASan via
// tests/test_cpp.py with zero new suppressions.
#include "stat/slo.h"

#include <unistd.h>

#include <algorithm>
#include <cstdlib>
#include <memory>
#include <string>
#include <vector>

#include "base/flags.h"
#include "base/json.h"
#include "base/time.h"
#include "net/channel.h"
#include "net/controller.h"
#include "net/naming.h"
#include "net/server.h"
#include "stat/digest.h"
#include "stat/latency_recorder.h"
#include "stat/variable.h"
#include "tests/test_util.h"

using namespace trpc;

namespace {

struct FlagGuard {
  std::string name, old_value;
  FlagGuard(const std::string& n, const std::string& v) : name(n) {
    slo::ensure_registered();
    naming_ensure_registered();
    old_value = Flag::find(n)->value_string();
    EXPECT_EQ(Flag::set(n, v), 0);
  }
  ~FlagGuard() { Flag::set(name, old_value); }
};

// Deterministic LCG so the merge-vs-oracle property replays bit-exact.
struct Rng {
  uint64_t s;
  explicit Rng(uint64_t seed) : s(seed) {}
  uint64_t next() {
    s = s * 6364136223846793005ULL + 1442695040888963407ULL;
    return s >> 33;
  }
  int64_t latency() {
    // Mixed tenant-like distribution: mostly fast, a heavy tail.
    const uint64_t r = next() % 100;
    if (r < 70) {
      return 50 + static_cast<int64_t>(next() % 200);
    }
    if (r < 95) {
      return 1000 + static_cast<int64_t>(next() % 4000);
    }
    return 20000 + static_cast<int64_t>(next() % 80000);
  }
};

int64_t exact_percentile(std::vector<int64_t> v, double p) {
  std::sort(v.begin(), v.end());
  size_t n = static_cast<size_t>(p * static_cast<double>(v.size()));
  if (n >= v.size()) {
    n = v.size() - 1;
  }
  return v[n];
}

// merged-vs-oracle agreement within the documented octave bound: the
// two values land in the same or adjacent octave, i.e. ratio <= 2 (plus
// reservoir-vs-exact slack inside one octave on tiny values).
void expect_within_octave(int64_t got, int64_t want) {
  EXPECT(got > 0 && want > 0);
  const double hi = static_cast<double>(std::max(got, want));
  const double lo = static_cast<double>(std::min(got, want));
  EXPECT(hi / lo <= 2.0 + 1e-9);
}

std::string var_str(const std::string& name) {
  std::string v;
  EXPECT(Variable::read_exposed(name, &v));
  return v;
}

}  // namespace

// ---- flag-off invisibility (MUST run first: registration order) ----------

TEST_CASE(slo_flag_off_invisible) {
  slo::ensure_registered();
  EXPECT(!slo::enabled());
  Server srv;
  srv.RegisterMethod("Echo.Echo", [](Controller*, const IOBuf& req,
                                     IOBuf* resp, Closure done) {
    resp->append(req);
    done();
  });
  EXPECT_EQ(srv.SetSlo("tenantA:p99_us=2000,avail=99.9;*:p99_us=10000"),
            0);
  EXPECT_EQ(srv.Start(0), 0);
  Channel ch;
  Channel::Options opts;
  opts.timeout_ms = 30000;
  EXPECT_EQ(ch.Init("127.0.0.1:" + std::to_string(srv.port()), &opts), 0);
  for (int i = 0; i < 32; ++i) {
    Controller cntl;
    cntl.set_qos("tenantA", 0);
    IOBuf req, resp;
    req.append("ping");
    ch.CallMethod("Echo.Echo", req, &resp, &cntl);
    EXPECT(!cntl.Failed());
  }
  // Flag off: the dispatch hook never touched the engine — every global
  // and per-tenant slo var is provably frozen at 0.
  EXPECT_EQ(slo::breach_total(), 0u);
  EXPECT(var_str("slo_observed_total") == "0");
  EXPECT(var_str("slo_breach_total") == "0");
  EXPECT(var_str("slo_tenant_tenantA_burn_fast_milli") == "0");
  EXPECT(var_str("slo_tenant_tenantA_attainment_ppm") == "0");
  EXPECT(var_str("slo_tenant_tenantA_breached") == "0");
  // on_response offered while off is a no-op, not a crash.
  srv.slo_engine()->on_response("tenantA", 99999, true);
  Json root;
  EXPECT(Json::parse(srv.slo_engine()->dump_json(), &root));
  const Json* tenants = root.find("tenants");
  EXPECT(tenants != nullptr && tenants->size() == 2);
  for (size_t i = 0; i < tenants->size(); ++i) {
    EXPECT_EQ((*tenants)[i].find("fast")->find("total")->as_number(), 0.0);
    EXPECT_EQ((*tenants)[i].find("slow")->find("total")->as_number(), 0.0);
  }
  srv.Stop();
}

// ---- digest wire ----------------------------------------------------------

TEST_CASE(digest_encode_decode_roundtrip) {
  LatencyRecorder rec;
  Rng rng(41);
  std::vector<int64_t> fed;
  for (int i = 0; i < 500; ++i) {
    const int64_t v = rng.latency();
    fed.push_back(v);
    rec << v;
  }
  LatencyDigest d;
  rec.snapshot_digest(&d);
  EXPECT_EQ(d.count, 500);
  const std::string wire = digest_encode(d);
  LatencyDigest back;
  EXPECT_EQ(digest_decode(wire.data(), wire.size(), &back), wire.size());
  EXPECT_EQ(back.count, d.count);
  EXPECT_EQ(back.sum_us, d.sum_us);
  EXPECT_EQ(back.max_us, d.max_us);
  EXPECT_EQ(back.total_count, d.total_count);
  for (int i = 0; i < LatencyDigest::kOctaves; ++i) {
    EXPECT_EQ(back.oct[i].added, d.oct[i].added);
    EXPECT_EQ(back.oct[i].samples.size(), d.oct[i].samples.size());
  }
  // Percentiles survive the roundtrip bit-exact (samples fit u32 here).
  for (double p : {0.5, 0.9, 0.99}) {
    EXPECT_EQ(digest_percentile_us(back, p), digest_percentile_us(d, p));
  }
}

TEST_CASE(digest_decode_rejects_malformed) {
  LatencyDigest d;
  EXPECT_EQ(digest_decode("NOTMAGIC________", 16, &d), 0u);
  LatencyRecorder rec;
  rec << 100;
  rec << 200;
  LatencyDigest src;
  rec.snapshot_digest(&src);
  const std::string wire = digest_encode(src);
  // Every truncation point fails cleanly instead of over-reading.
  for (size_t cut = 0; cut < wire.size(); ++cut) {
    EXPECT_EQ(digest_decode(wire.data(), cut, &d), 0u);
  }
}

TEST_CASE(digest_merge_matches_pooled_oracle) {
  // THE acceptance property: merging per-node digests then rank-walking
  // must agree with (a) one recorder that saw all the traffic and
  // (b) the exact sorted percentile, within the one-octave (2x) bound —
  // for several seeds, so this is a property, not an example.
  for (uint64_t seed = 1; seed <= 5; ++seed) {
    LatencyDigest merged;
    LatencyRecorder pooled;
    std::vector<int64_t> all;
    for (int node = 0; node < 3; ++node) {
      LatencyRecorder rec;
      Rng rng(seed * 1000 + node);
      for (int i = 0; i < 400; ++i) {
        const int64_t v = rng.latency();
        rec << v;
        pooled << v;
        all.push_back(v);
      }
      LatencyDigest d;
      rec.snapshot_digest(&d);
      digest_merge(&merged, d);
    }
    EXPECT_EQ(merged.count, static_cast<int64_t>(all.size()));
    LatencyDigest oracle;
    pooled.snapshot_digest(&oracle);
    for (double p : {0.5, 0.9, 0.99}) {
      const int64_t got = digest_percentile_us(merged, p);
      expect_within_octave(got, digest_percentile_us(oracle, p));
      expect_within_octave(got, exact_percentile(all, p));
    }
  }
}

// ---- spec parsing ---------------------------------------------------------

TEST_CASE(slo_spec_parse_and_reject) {
  slo::ensure_registered();
  std::string err;
  auto e = SloEngine::parse(
      "tenantA:p99_us=2000,avail=99.9;*:p99_us=10000", &err);
  EXPECT(e != nullptr);
  EXPECT_EQ(e->tenant_count(), 2u);
  EXPECT(SloEngine::parse("tenantA:avail=99.5", &err) != nullptr);
  // A typo must not silently mean "no SLO": every malformed spec rejects.
  const char* bad[] = {
      "tenantA",                      // no clause body
      "tenantA:p99us=2000",           // unknown key
      "tenantA:p99_us=0",             // target must be >= 1
      "tenantA:avail=0",              // availability in (0, 100)
      "tenantA:avail=100",
      "tenantA:avail=abc",
      "tenantA:p99_us=5;tenantA:p99_us=9",  // duplicate clause
      ":p99_us=5",                    // empty tenant
      "bad tenant!:p99_us=5",         // invalid tenant charset
  };
  for (const char* s : bad) {
    EXPECT(SloEngine::parse(s, &err) == nullptr);
    EXPECT(!err.empty());
  }
  Server srv;
  EXPECT_EQ(srv.SetSlo("tenantA:p99us=2000"), -1);  // reject, loudly
  EXPECT_EQ(srv.SetSlo("tenantA:p99_us=2000"), 0);
  EXPECT_EQ(srv.SetSlo(""), 0);  // removes
  EXPECT(srv.slo_engine() == nullptr);
  srv.RegisterMethod("Echo.Echo", [](Controller*, const IOBuf& req,
                                     IOBuf* resp, Closure done) {
    resp->append(req);
    done();
  });
  EXPECT_EQ(srv.Start(0), 0);
  EXPECT_EQ(srv.SetSlo("tenantA:p99_us=2000"), -1);  // running: refuse
  srv.Stop();
}

// ---- burn-rate breach fire + clear (compressed windows) -------------------

TEST_CASE(slo_burn_breach_fires_and_clears) {
  // Window widths are captured at parse time, so compress BEFORE parse.
  FlagGuard fast("trpc_slo_fast_window_ms", "300");
  FlagGuard slow("trpc_slo_slow_window_ms", "1200");
  FlagGuard on("trpc_slo", "true");

  std::string err;
  auto e = SloEngine::parse("tenantA:p99_us=2000,avail=99.0", &err);
  EXPECT(e != nullptr);
  const uint64_t breaches_before = slo::breach_total();

  // Sustained damage: every response blows the latency target, so both
  // windows burn at (1.0 / 0.01) = 100x >> the 2x alert threshold.
  for (int i = 0; i < 50; ++i) {
    e->on_response("tenantA", 50000, false);
  }
  EXPECT(e->any_breached());
  EXPECT_EQ(slo::breach_total(), breaches_before + 1);
  // Re-evaluating while still bad is NOT a new edge.
  for (int i = 0; i < 20; ++i) {
    e->on_response("tenantA", 50000, false);
  }
  EXPECT_EQ(slo::breach_total(), breaches_before + 1);

  // Recovery: after one fast window of healthy traffic the fast burn
  // falls below the alert and the breach clears (the slow window still
  // remembers the damage — that is the point of the pair).
  const int64_t deadline = monotonic_time_us() + 2 * 1000 * 1000;
  while (e->any_breached() && monotonic_time_us() < deadline) {
    e->on_response("tenantA", 100, false);
    usleep(20 * 1000);
  }
  EXPECT(!e->any_breached());

  // Clearing is an edge too, but not a breach: the count stays.
  EXPECT_EQ(slo::breach_total(), breaches_before + 1);
}

TEST_CASE(slo_error_responses_burn_budget) {
  FlagGuard fast("trpc_slo_fast_window_ms", "300");
  FlagGuard slow("trpc_slo_slow_window_ms", "1200");
  FlagGuard on("trpc_slo", "true");
  std::string err;
  // Availability-only clause: latency-unbounded, only errors are bad.
  auto e = SloEngine::parse("tenantB:avail=99.0", &err);
  EXPECT(e != nullptr);
  for (int i = 0; i < 40; ++i) {
    e->on_response("tenantB", 100, true);  // errors, fast latency
  }
  EXPECT(e->any_breached());
  Json root;
  EXPECT(Json::parse(e->dump_json(), &root));
  const Json& t = (*root.find("tenants"))[0];
  EXPECT_EQ(t.find("fast")->find("err")->as_number(), 40.0);
  EXPECT_EQ(t.find("p99_target_us")->as_number(), -1.0);
}

// ---- fleet blob -----------------------------------------------------------

TEST_CASE(fleet_blob_roundtrip) {
  FlagGuard on("trpc_slo", "true");
  std::string err;
  auto e = SloEngine::parse(
      "tenantA:p99_us=2000,avail=99.9;*:p99_us=10000", &err);
  EXPECT(e != nullptr);
  Rng rng(7);
  for (int i = 0; i < 300; ++i) {
    e->on_response("tenantA", rng.latency(), i % 50 == 0);
  }
  const std::string blob = e->encode_blob(1234567);
  FleetNodeBlob node;
  EXPECT(fleet_blob_decode(blob.data(), blob.size(), &node));
  EXPECT_EQ(node.wall_us, 1234567);
  EXPECT_EQ(node.tenants.size(), 2u);
  const FleetTenantRecord* a = nullptr;
  for (const auto& t : node.tenants) {
    if (t.tenant == "tenantA") {
      a = &t;
    }
  }
  EXPECT(a != nullptr);
  EXPECT_EQ(a->p99_target_us, 2000);
  EXPECT(a->avail_target > 0.998 && a->avail_target < 1.0);
  EXPECT_EQ(a->fast_total, 300);
  EXPECT_EQ(a->fast_err, 6);
  EXPECT_EQ(a->digest.count, 300);
  EXPECT(digest_percentile_us(a->digest, 0.5) > 0);
  // Malformed blobs reject instead of over-reading.
  FleetNodeBlob junk;
  EXPECT(!fleet_blob_decode(blob.data(), blob.size() / 2, &junk));
  EXPECT(!fleet_blob_decode("XXXXXXXX", 8, &junk));
}

// ---- announcer publication + fleet merge over a live registry -------------

TEST_CASE(fleet_publish_and_merged_dump) {
  naming_registry().clear();
  FlagGuard lease("trpc_naming_lease_ms", "400");
  FlagGuard on("trpc_slo", "true");
  FlagGuard pub("trpc_fleet_publish", "true");

  Server registry;
  EXPECT_EQ(naming_attach(&registry), 0);
  EXPECT_EQ(registry.Start(0), 0);
  const std::string reg_addr =
      "127.0.0.1:" + std::to_string(registry.port());

  auto mk = [](Server* s) {
    s->RegisterMethod("Echo.Echo", [](Controller*, const IOBuf& req,
                                      IOBuf* resp, Closure done) {
      resp->append(req);
      done();
    });
    // A wide latency target: this test exercises the MERGE arithmetic
    // (summed counters, folded targets, pooled percentiles), and must
    // not burn budget just because a sanitizer build dispatches slowly.
    EXPECT_EQ(s->SetSlo("tenantA:p99_us=2000000,avail=99.9"), 0);
    EXPECT_EQ(s->Start(0), 0);
  };
  Server n1, n2;
  mk(&n1);
  mk(&n2);
  // Feed distinct per-node traffic through the REAL dispatch path.
  int64_t per_node[2] = {40, 60};
  Server* nodes[2] = {&n1, &n2};
  for (int n = 0; n < 2; ++n) {
    Channel ch;
    Channel::Options opts;
    opts.timeout_ms = 30000;
    EXPECT_EQ(
        ch.Init("127.0.0.1:" + std::to_string(nodes[n]->port()), &opts),
        0);
    for (int64_t i = 0; i < per_node[n]; ++i) {
      Controller cntl;
      cntl.set_qos("tenantA", 0);
      IOBuf req, resp;
      req.append("ping");
      ch.CallMethod("Echo.Echo", req, &resp, &cntl);
      EXPECT(!cntl.Failed());
    }
  }
  EXPECT_EQ(server_announce(&n1, reg_addr, "fleet", "z1", 1), 0);
  EXPECT_EQ(server_announce(&n2, reg_addr, "fleet", "z2", 1), 0);

  // Start publishes once immediately; renew rounds re-publish.  Wait for
  // both nodes' payloads to land and carry the traffic fed above.
  const int64_t deadline = monotonic_time_us() + 5 * 1000 * 1000;
  bool merged_ok = false;
  while (!merged_ok && monotonic_time_us() < deadline) {
    Json root;
    EXPECT(Json::parse(fleet_dump_json("fleet"), &root));
    const Json* tenants = root.find("tenants");
    for (size_t i = 0; tenants != nullptr && i < tenants->size(); ++i) {
      const Json& t = (*tenants)[i];
      if (t.find("tenant")->as_string() == "tenantA" &&
          t.find("nodes")->as_number() == 2.0 &&
          t.find("count")->as_number() == 100.0) {
        // Merged fleet view: counters SUMMED across nodes, targets
        // folded (min p99 / max avail), percentiles from pooled samples.
        EXPECT_EQ(t.find("p99_target_us")->as_number(), 2000000.0);
        EXPECT(t.find("p99_us")->as_number() > 0);
        EXPECT(t.find("burn_slow")->as_number() < 2.0);
        EXPECT_EQ(t.find("breached_nodes")->as_number(), 0.0);
        merged_ok = true;
      }
    }
    usleep(50 * 1000);
  }
  EXPECT(merged_ok);

  // Unknown service answers structurally, not with a crash.
  Json miss;
  EXPECT(Json::parse(fleet_dump_json("nope"), &miss));
  EXPECT(miss.find("error") != nullptr);
  n1.Stop();
  n2.Stop();
  registry.Stop();
  naming_registry().clear();
}

TEST_MAIN
