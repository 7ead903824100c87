#include "net/cluster.h"

#include "net/concurrency_limiter.h"
#include "net/span.h"

#include <arpa/inet.h>
#include <errno.h>
#include <netdb.h>
#include <string.h>
#include <sys/socket.h>
#include <unistd.h>

#include <algorithm>
#include <atomic>
#include <fstream>
#include <sstream>

#include "base/flags.h"
#include "base/logging.h"
#include "base/rand.h"
#include "base/time.h"
#include "fiber/fiber.h"
#include "fiber/sync.h"
#include "net/deadline.h"
#include "net/fault.h"
#include "net/lb_hint.h"
#include "net/naming.h"
#include "stat/reducer.h"
#include "stat/timeline.h"

namespace trpc {

// ---- load balancers -------------------------------------------------------

namespace {

uint64_t mix_u64(uint64_t v) {
  v ^= v >> 33;
  v *= 0xff51afd7ed558ccdull;
  v ^= v >> 33;
  return v;
}

// This client's locality label for the zone-preferring balancer.
Flag* zone_flag() {
  static Flag* f = [] {
    Flag* flag = Flag::define_string(
        "trpc_cluster_zone", "",
        "this client's locality zone for the zone_la balancer: same-"
        "zone members keep their full latency-derived share, members in "
        "a DIFFERENT non-empty zone pay a 4x share penalty ('' = no "
        "preference); max 15 chars (the naming wire zone field)");
    if (flag != nullptr) {
      flag->set_validator(
          [](const std::string& v) { return v.size() <= 15; });
    }
    return flag;
  }();
  return f;
}

// Bounded-load factor for c_hash_bl (Mirrokni et al: consistent hashing
// with bounded loads — ring affinity, but a node already carrying more
// than factor x the mean in-flight load is skipped clockwise).
Flag* chash_load_factor_flag() {
  static Flag* f = [] {
    Flag* flag = Flag::define_double(
        "trpc_cluster_chash_load_factor", 1.25,
        "bounded-load factor for the c_hash_bl balancer ([1.0, 16.0]): "
        "a ring-preferred node whose in-flight count exceeds factor x "
        "the healthy-set mean is skipped clockwise, trading affinity "
        "for overload diffusion");
    if (flag != nullptr) {
      flag->set_validator([](const std::string& v) {
        char* end = nullptr;
        const double d = strtod(v.c_str(), &end);
        return end != v.c_str() && *end == '\0' && d >= 1.0 && d <= 16.0;
      });
    }
    return flag;
  }();
  return f;
}

Flag* subset_size_flag() {
  static Flag* f = [] {
    Flag* flag = Flag::define_int64(
        "trpc_cluster_subset_size", 0,
        "deterministic subsetting: each ClusterChannel holds member "
        "channels to at most this many servers (rendezvous-hashed by a "
        "per-process seed, so the fleet's clients spread evenly and "
        "each keeps a STABLE subset across refreshes).  0 = unlimited.  "
        "Mandatory at scale — N clients x M servers full-mesh is what "
        "exhausts the fd budget ([0, 65536])");
    if (flag != nullptr) {
      flag->set_validator([](const std::string& v) {
        char* end = nullptr;
        const long long n = strtoll(v.c_str(), &end, 10);
        return end != v.c_str() && *end == '\0' && n >= 0 && n <= 65536;
      });
    }
    return flag;
  }();
  return f;
}

class RoundRobinLB : public LoadBalancer {
 public:
  size_t select(const std::vector<size_t>& healthy,
                const std::vector<ServerNode>&, uint64_t, int) override {
    return healthy[next_.fetch_add(1, std::memory_order_relaxed) %
                   healthy.size()];
  }

 private:
  std::atomic<uint64_t> next_{0};
};

class RandomLB : public LoadBalancer {
 public:
  size_t select(const std::vector<size_t>& healthy,
                const std::vector<ServerNode>&, uint64_t, int) override {
    return healthy[fast_rand_less_than(healthy.size())];
  }
};

// Ketama-style ring with virtual nodes (parity: policy/
// consistent_hashing_load_balancer — single hash points skew badly on small
// clusters, so each endpoint contributes kReplicas ring points).
class ConsistentHashLB : public LoadBalancer {
 public:
  static constexpr int kReplicas = 32;

  size_t select(const std::vector<size_t>& healthy,
                const std::vector<ServerNode>& nodes, uint64_t key,
                int attempt) override {
    size_t best = healthy[0];
    uint64_t best_dist = UINT64_MAX;
    const uint64_t h = mix_u64(key);
    for (size_t idx : healthy) {
      const uint64_t base = EndPointHash()(nodes[idx].ep);
      for (int r = 0; r < kReplicas; ++r) {
        const uint64_t nh = mix_u64(base + r * 0x9e3779b97f4a7c15ull);
        const uint64_t dist = nh - h;  // wrapping distance clockwise
        if (dist < best_dist) {
          best_dist = dist;
          best = idx;
        }
      }
    }
    if (attempt > 0) {
      return healthy[(std::find(healthy.begin(), healthy.end(), best) -
                      healthy.begin() + attempt) %
                     healthy.size()];
    }
    return best;
  }
};

// Consistent hashing with BOUNDED loads (c_hash_bl): same ketama ring,
// but the clockwise walk skips any node whose live in-flight count
// exceeds trpc_cluster_chash_load_factor x the healthy-set mean — key
// affinity holds while a node is healthy-and-not-hot, and a hotspot
// key's overflow diffuses to the next nodes on the ring instead of
// melting one server (the fabric-serving failure mode plain c_hash has).
class ConsistentHashBoundedLB : public LoadBalancer {
 public:
  size_t select(const std::vector<size_t>& healthy,
                const std::vector<ServerNode>& nodes, uint64_t key,
                int attempt) override {
    // Ring order: every healthy node's minimal clockwise distance.
    const uint64_t h = mix_u64(key);
    std::vector<std::pair<uint64_t, size_t>> order;
    order.reserve(healthy.size());
    int64_t inflight_sum = 0;
    for (size_t idx : healthy) {
      const uint64_t base = EndPointHash()(nodes[idx].ep);
      uint64_t best_dist = UINT64_MAX;
      for (int r = 0; r < ConsistentHashLB::kReplicas; ++r) {
        const uint64_t nh = mix_u64(base + r * 0x9e3779b97f4a7c15ull);
        best_dist = std::min(best_dist, nh - h);  // wrapping clockwise
      }
      order.emplace_back(best_dist, idx);
      // Relaxed: advisory load sample; staleness only softens the bound.
      inflight_sum +=
          nodes[idx].inflight->load(std::memory_order_relaxed);
    }
    std::sort(order.begin(), order.end());
    Flag* f = chash_load_factor_flag();
    const double factor = f != nullptr ? f->double_value() : 1.25;
    // +1: the candidate's own admission counts against the bound, and
    // the ceiling keeps a cold cluster (mean 0) from rejecting everyone.
    const double bound =
        factor * (static_cast<double>(inflight_sum) / healthy.size() + 1);
    // Cache-aware routing (ISSUE 17): a caller-installed hint names the
    // member holding the longest cached prefix.  Honor it on the FIRST
    // attempt only (retries already exclude the tried node) and only
    // while it is under the same bounded-load bound the ring walk
    // enforces — affinity never outranks overload diffusion (veto).
    EndPoint hinted;
    if (attempt == 0 && lb_hint_get(&hinted)) {
      bool found = false;
      for (size_t idx : healthy) {
        if (nodes[idx].ep == hinted) {
          found = true;
          // Relaxed: advisory load sample, see the ring walk below.
          if (nodes[idx].inflight->load(std::memory_order_relaxed) + 1 <=
              bound) {
            lb_hint_counters().bump(lb_hint_counters().hit);
            return idx;
          }
          lb_hint_counters().bump(lb_hint_counters().veto);
          break;
        }
      }
      if (!found) {
        lb_hint_counters().bump(lb_hint_counters().miss);
      }
    }
    const size_t start = static_cast<size_t>(attempt) % order.size();
    // Full wrap from the retry offset: an under-bound node earlier in
    // ring order must stay reachable on retries, or the walk would hand
    // a retry to an over-bound node while an idle one exists.
    for (size_t i = 0; i < order.size(); ++i) {
      const size_t idx = order[(start + i) % order.size()].second;
      // Relaxed: see above.
      if (nodes[idx].inflight->load(std::memory_order_relaxed) + 1 <=
          bound) {
        return idx;
      }
    }
    // Every node over the bound (burst): ring-preferred wins anyway.
    return order[start].second;
  }
};

// Weighted round robin: node i is picked weight_i times per cycle,
// interleaved (parity: policy/weighted_round_robin_load_balancer.*,
// condensed to the smooth-wrr scheme).
class WeightedRoundRobinLB : public LoadBalancer {
 public:
  size_t select(const std::vector<size_t>& healthy,
                const std::vector<ServerNode>& nodes, uint64_t,
                int) override {
    // Smooth WRR over the healthy subset using a stateless stride: walk
    // the cumulative weights with an incrementing cursor.
    int64_t total = 0;
    for (size_t idx : healthy) {
      total += std::max(1, nodes[idx].weight);
    }
    int64_t tick = static_cast<int64_t>(
        cursor_.fetch_add(1, std::memory_order_relaxed) % total);
    for (size_t idx : healthy) {
      tick -= std::max(1, nodes[idx].weight);
      if (tick < 0) {
        return idx;
      }
    }
    return healthy.back();
  }

 private:
  std::atomic<uint64_t> cursor_{0};
};

// Power-of-two-choices with EWMA latency x in-flight scoring (parity:
// policy/p2c_ewma and the locality-aware balancer's latency/load feedback
// tree, condensed: same feedback signals, two-probe selection).
class P2cEwmaLB : public LoadBalancer {
 public:
  size_t select(const std::vector<size_t>& healthy,
                const std::vector<ServerNode>& nodes, uint64_t,
                int attempt) override {
    if (healthy.size() == 1) {
      return healthy[0];
    }
    const size_t a = healthy[fast_rand_less_than(healthy.size())];
    size_t b = healthy[fast_rand_less_than(healthy.size())];
    if (a == b) {
      b = healthy[(std::find(healthy.begin(), healthy.end(), a) -
                   healthy.begin() + 1 + attempt) %
                  healthy.size()];
    }
    return score(nodes[a]) <= score(nodes[b]) ? a : b;
  }

 private:
  static int64_t score(const ServerNode& n) {
    // Untried nodes (ewma 0) score lowest so every node gets probed.
    const int64_t lat = n.ewma_latency_us->load(std::memory_order_relaxed);
    const int64_t load = n.inflight->load(std::memory_order_relaxed) + 1;
    return lat * load / std::max(1, n.weight);
  }
};

// Locality-aware: weighted random over every node's expected quality,
// where weight ~ 1 / (ewma_latency x (1 + inflight) x error-deceleration)
// (parity: policy/locality_aware_load_balancer.h:41 — same signals and
// semantics: requests iterate toward lowest-expected-latency servers,
// errors collapse a node's share sharply, recovery re-earns it).
// Redesigned at altitude: the reference's partial-sum weight tree buys
// O(log n) selection for thousand-node clusters; at this runtime's
// cluster sizes an O(n) scan over the healthy subset is cheaper than the
// tree's bookkeeping, so the SAME weights feed a direct weighted pick.
// zone_la extension: constructed with this client's zone, the same
// latency/load/error weights additionally pay kZonePenalty when the
// member sits in a DIFFERENT non-empty zone — traffic prefers local
// replicas while remote ones stay warm enough to absorb a zone failure
// (locality-aware parity, locality made literal).
class LocalityAwareLB : public LoadBalancer {
 public:
  explicit LocalityAwareLB(std::string my_zone = "")
      : my_zone_(std::move(my_zone)) {}

  size_t select(const std::vector<size_t>& healthy,
                const std::vector<ServerNode>& nodes, uint64_t,
                int) override {
    if (healthy.size() == 1) {
      return healthy[0];
    }
    // Pass 1: per-node QUALITY (latency x load x error deceleration) for
    // nodes with history, tracking the mean so untried nodes (ewma 0)
    // enter at quality parity — every node gets probed without handing
    // newcomers the whole cluster.  Static weights multiply at the end
    // so a newcomer's configured share is respected too.
    int64_t quality[kMaxScan];
    const size_t n = std::min(healthy.size(), kMaxScan);
    int64_t tried_sum = 0;
    size_t tried = 0;
    for (size_t i = 0; i < n; ++i) {
      const ServerNode& node = nodes[healthy[i]];
      const int64_t lat =
          node.ewma_latency_us->load(std::memory_order_relaxed);
      if (lat == 0) {
        quality[i] = -1;  // untried: filled in pass 2
        continue;
      }
      const int64_t inflight =
          node.inflight->load(std::memory_order_relaxed);
      const int64_t fails =
          node.consecutive_failures->load(std::memory_order_relaxed);
      // Deceleration: each consecutive error quarters the share again;
      // one success resets fails and the node re-earns weight from its
      // (still-remembered) latency.
      int64_t q = kScale / (lat * (1 + inflight));
      q >>= std::min<int64_t>(fails * 2, 30);
      q = std::max<int64_t>(q, kMinWeight);
      quality[i] = q;
      tried_sum += q;
      ++tried;
    }
    const int64_t newcomer =
        tried == 0 ? kScale / 1000 : tried_sum / static_cast<int64_t>(tried);
    int64_t weights[kMaxScan];
    for (size_t i = 0; i < n; ++i) {
      int64_t q = quality[i] >= 0
                      ? quality[i]
                      : std::max<int64_t>(newcomer, kMinWeight);
      // Zone preference: penalize only a KNOWN-remote member (both
      // zones non-empty and different) — unlabeled members ride at par
      // so a partially-labeled fleet degrades to plain la, not to
      // starving the unlabeled half.
      const std::string& nz = nodes[healthy[i]].zone;
      if (!my_zone_.empty() && !nz.empty() && nz != my_zone_) {
        q = std::max<int64_t>(q / kZonePenalty, kMinWeight);
      }
      weights[i] = q * std::max(1, nodes[healthy[i]].weight);
    }
    return healthy[weighted_pick(weights, n)];
  }

 private:
  static constexpr size_t kMaxScan = 1024;  // bound the stack scan
  static constexpr int64_t kScale = 1ll << 40;
  static constexpr int64_t kMinWeight = 16;  // floor (min_weight parity)
  static constexpr int64_t kZonePenalty = 4;
  const std::string my_zone_;
};

// Routing-hint outcome vars (net/lb_hint.h): dashboards read the
// hit/veto split to judge whether cache-aware routing is actually
// landing on prefix owners or being load-vetoed back onto the ring.
struct LbHintVars {
  std::unique_ptr<PassiveStatus<long>> hit;
  std::unique_ptr<PassiveStatus<long>> veto;
  std::unique_ptr<PassiveStatus<long>> miss;
  LbHintVars() {
    hit = std::make_unique<PassiveStatus<long>>([] {
      return static_cast<long>(
          LbHintCounters::read(lb_hint_counters().hit));
    });
    hit->expose("lb_hint_hit_total",
                "cluster calls routed to their cache-affinity hint (the "
                "hinted member was healthy and under the c_hash_bl "
                "bounded-load bound)");
    veto = std::make_unique<PassiveStatus<long>>([] {
      return static_cast<long>(
          LbHintCounters::read(lb_hint_counters().veto));
    });
    veto->expose(
        "lb_hint_veto_total",
        "cluster calls whose cache-affinity hint was VETOED by the "
        "bounded-load check (hinted member over factor x mean in-flight) "
        "and fell back to the ring walk");
    miss = std::make_unique<PassiveStatus<long>>([] {
      return static_cast<long>(
          LbHintCounters::read(lb_hint_counters().miss));
    });
    miss->expose(
        "lb_hint_miss_total",
        "cluster calls whose cache-affinity hint named a member not in "
        "the healthy set (drained, quarantined, or gone) — routed by "
        "the plain ring walk");
  }
};

LbHintVars& lb_hint_vars() {
  static LbHintVars* v = new LbHintVars();
  return *v;
}

}  // namespace

LbHintCounters& lb_hint_counters() {
  static LbHintCounters* c = new LbHintCounters();
  return *c;
}

void cluster_ensure_registered() {
  zone_flag();
  chash_load_factor_flag();
  subset_size_flag();
  lb_hint_vars();
}

int64_t asym_ewma(int64_t prev, int64_t sample) {
  if (prev == 0) {
    return sample;
  }
  if (sample < prev) {
    return (prev + sample * 3) / 4;  // improvements take hold fast
  }
  return (prev * 7 + sample) / 8;  // degradations blend in slowly
}

size_t weighted_pick(const int64_t* weights, size_t n) {
  int64_t total = 0;
  for (size_t i = 0; i < n; ++i) {
    total += weights[i];
  }
  int64_t dice = static_cast<int64_t>(
      fast_rand_less_than(static_cast<uint64_t>(std::max<int64_t>(total,
                                                                  1))));
  for (size_t i = 0; i < n; ++i) {
    dice -= weights[i];
    if (dice < 0) {
      return i;
    }
  }
  return n - 1;
}

LoadBalancer* LoadBalancer::create(const std::string& name) {
  if (name == "rr" || name.empty()) {
    return new RoundRobinLB();
  }
  if (name == "random") {
    return new RandomLB();
  }
  if (name == "c_hash") {
    return new ConsistentHashLB();
  }
  if (name == "c_hash_bl") {
    chash_load_factor_flag();  // register before first /flags read
    return new ConsistentHashBoundedLB();
  }
  if (name == "wrr") {
    return new WeightedRoundRobinLB();
  }
  if (name == "p2c") {
    return new P2cEwmaLB();
  }
  if (name == "la") {
    return new LocalityAwareLB();
  }
  if (name == "zone_la") {
    Flag* f = zone_flag();
    return new LocalityAwareLB(f != nullptr ? f->string_value() : "");
  }
  return nullptr;
}

// ---- naming services ------------------------------------------------------

namespace {

int parse_server_list(const std::string& text,
                      std::vector<NsEntry>* out) {
  std::stringstream ss(text);
  std::string token;
  while (std::getline(ss, token, ',')) {
    // Trim whitespace/newlines.
    const size_t b = token.find_first_not_of(" \t\r\n");
    const size_t e = token.find_last_not_of(" \t\r\n");
    if (b == std::string::npos) {
      continue;
    }
    token = token.substr(b, e - b + 1);
    // Optional "host:port <weight> <zone>" columns (file-NS parity: the
    // weight feeds wrr/p2c, the zone feeds zone_la).
    NsEntry entry;
    size_t sp = token.find_first_of(" \t");
    if (sp != std::string::npos) {
      std::stringstream cols(token.substr(sp + 1));
      std::string w, z;
      cols >> w >> z;
      entry.weight = std::max(1, atoi(w.c_str()));
      entry.zone = z;
      token = token.substr(0, sp);
    }
    if (hostname2endpoint(token.c_str(), &entry.ep) == 0) {
      out->push_back(std::move(entry));
    } else {
      LOG(Warning) << "bad server '" << token << "' in list";
    }
  }
  return out->empty() ? -1 : 0;
}

class ListNS : public NamingService {
 public:
  int resolve(const std::string& param,
              std::vector<NsEntry>* out) override {
    return parse_server_list(param, out);
  }
};

// One server per line (or comma separated), re-read each refresh.
class FileNS : public NamingService {
 public:
  int resolve(const std::string& param,
              std::vector<NsEntry>* out) override {
    std::ifstream in(param);
    if (!in) {
      return -1;
    }
    std::stringstream buf;
    buf << in.rdbuf();
    std::string text = buf.str();
    for (char& c : text) {
      if (c == '\n') {
        c = ',';
      }
    }
    return parse_server_list(text, out);
  }
};

// dns://host:port — getaddrinfo resolution of EVERY address behind the
// name, re-resolved on each refresher cycle (parity: the http:// DNS
// naming service + details/naming_service_thread periodic re-resolve).
class DnsNS : public NamingService {
 public:
  int resolve(const std::string& param,
              std::vector<NsEntry>* out) override {
    const size_t colon = param.rfind(':');
    if (colon == std::string::npos) {
      return -1;
    }
    const std::string host = param.substr(0, colon);
    const std::string port = param.substr(colon + 1);
    addrinfo hints;
    memset(&hints, 0, sizeof(hints));
    hints.ai_family = AF_INET;
    hints.ai_socktype = SOCK_STREAM;
    addrinfo* res = nullptr;
    if (getaddrinfo(host.c_str(), port.c_str(), &hints, &res) != 0) {
      return -1;
    }
    for (addrinfo* p = res; p != nullptr; p = p->ai_next) {
      const auto* sa = reinterpret_cast<sockaddr_in*>(p->ai_addr);
      NsEntry entry;
      entry.ep.ip = sa->sin_addr.s_addr;
      entry.ep.port = ntohs(sa->sin_port);
      out->push_back(std::move(entry));
    }
    freeaddrinfo(res);
    return out->empty() ? -1 : 0;
  }
};

// naming://registry_host:port/service — the in-repo naming service
// (net/naming.h): members announced into the registry resolve with
// their zone/weight, and watch() long-polls the registry so membership
// deltas PUSH into the cluster channel instead of waiting a refresh
// tick.  One channel to the registry, shared by resolve and watch (the
// tstd connection multiplexes; a parked watch never blocks a resolve).
class RegistryNS : public NamingService {
 public:
  int resolve(const std::string& param,
              std::vector<NsEntry>* out) override {
    std::vector<NamingMember> members;
    {
      // A watch() answer already carried the full member view; the
      // refresh it triggers consumes it here (one-shot) instead of
      // paying a second Naming.Resolve round-trip per push.
      std::lock_guard<std::mutex> g(mu_);
      if (pushed_valid_) {
        members = std::move(pushed_view_);
        pushed_view_.clear();
        pushed_valid_ = false;
      }
    }
    if (members.empty()) {
      Channel* ch = channel(param);
      if (ch == nullptr) {
        return -1;
      }
      uint64_t version = 0;
      if (naming_resolve(ch, service_of(param), &members, &version) !=
          0) {
        return -1;
      }
    }
    for (const NamingMember& m : members) {
      NsEntry entry;
      if (hostname2endpoint(m.addr.c_str(), &entry.ep) != 0) {
        LOG(Warning) << "bad member addr '" << m.addr << "' in naming view";
        continue;
      }
      entry.weight = std::max<int>(m.weight, 1);
      entry.zone = m.zone;
      out->push_back(std::move(entry));
    }
    return out->empty() ? -1 : 0;
  }

  int watch(const std::string& param, uint64_t* version,
            int64_t park_budget_ms) override {
    Channel* ch = channel(param);
    if (ch == nullptr) {
      return -1;
    }
    const uint64_t before = version != nullptr ? *version : 0;
    std::vector<NamingMember> members;
    const int rc = naming_watch(ch, service_of(param), &members, version,
                                park_budget_ms, park_budget_ms + 2000);
    if (rc == 0 && version != nullptr && *version != before) {
      // Stash the pushed view for the refresh this answer triggers.
      std::lock_guard<std::mutex> g(mu_);
      pushed_view_ = std::move(members);
      pushed_valid_ = true;
    }
    return rc;
  }

  bool supports_watch() const override { return true; }

 private:
  static std::string addr_of(const std::string& param) {
    return param.substr(0, param.find('/'));
  }
  static std::string service_of(const std::string& param) {
    const size_t slash = param.find('/');
    return slash == std::string::npos ? "default" : param.substr(slash + 1);
  }
  Channel* channel(const std::string& param) {
    std::lock_guard<std::mutex> g(mu_);
    if (ch_ == nullptr) {
      auto ch = std::make_unique<Channel>();
      Channel::Options opts;
      opts.timeout_ms = 2000;
      if (ch->Init(addr_of(param), &opts) != 0) {
        return nullptr;
      }
      ch_ = std::move(ch);
    }
    return ch_.get();
  }
  std::mutex mu_;
  std::unique_ptr<Channel> ch_;
  // One-shot view handed from watch() to the resolve() it triggers.
  std::vector<NamingMember> pushed_view_;
  bool pushed_valid_ = false;
};

}  // namespace

std::unique_ptr<NamingService> NamingService::create(const std::string& url,
                                                     std::string* param) {
  if (url.rfind("list://", 0) == 0) {
    *param = url.substr(7);
    return std::make_unique<ListNS>();
  }
  if (url.rfind("file://", 0) == 0) {
    *param = url.substr(7);
    return std::make_unique<FileNS>();
  }
  if (url.rfind("dns://", 0) == 0) {
    *param = url.substr(6);
    return std::make_unique<DnsNS>();
  }
  if (url.rfind("naming://", 0) == 0) {
    *param = url.substr(9);  // "registry_host:port/service"
    return std::make_unique<RegistryNS>();
  }
  // Bare "host:port" degenerates to a one-server list.
  *param = url;
  return std::make_unique<ListNS>();
}

// ---- ClusterChannel -------------------------------------------------------

ClusterChannel::~ClusterChannel() {
  stopping_.store(true, std::memory_order_release);
  if (watcher_started_.load(std::memory_order_acquire)) {
    // Wake + join the naming watch fiber first (it may be parked inside
    // a long-poll RPC; its bounded park budget caps this wait).
    watch_wake_.value.fetch_add(1, std::memory_order_release);
    watch_wake_.wake_all();
    while (watch_done_.value.load(std::memory_order_acquire) == 0) {
      watch_done_.wait(0, -1);
    }
    while (!watcher_exited_.load(std::memory_order_acquire)) {
      sched_yield();
    }
  }
  if (refresher_started_.load(std::memory_order_acquire)) {
    // Wake the refresher out of its sleep and wait for it to exit — it
    // holds `this`, so destruction must not race it.
    refresh_wake_.value.fetch_add(1, std::memory_order_release);
    refresh_wake_.wake_all();
    while (refresh_done_.value.load(std::memory_order_acquire) == 0) {
      refresh_done_.wait(0, -1);
    }
    // The wake that satisfied us may still be INSIDE refresh_done_.wake_all
    // touching the Event; spin until the fiber's final store says it is
    // completely done with this object.
    while (!refresher_exited_.load(std::memory_order_acquire)) {
      sched_yield();
    }
  }
}

int ClusterChannel::Init(const std::string& naming_url,
                         const std::string& lb_name, const Options* opts) {
  if (opts != nullptr) {
    opts_ = *opts;
  }
  lb_.reset(LoadBalancer::create(lb_name));
  if (lb_ == nullptr) {
    return -1;
  }
  ns_ = NamingService::create(naming_url, &ns_param_);
  return refresh();
}

int ClusterChannel::refresh() {
  std::vector<NsEntry> eps;
  if (ns_->resolve(ns_param_, &eps) != 0) {
    return -1;
  }
  // Deterministic subsetting (fd-budget discipline): rendezvous-hash
  // every member against this client's seed and keep the top-k.  The
  // same (seed, member) pair always scores the same, so a member
  // add/remove perturbs the subset minimally and a plain refresh never
  // churns connections; different seeds (default: pid) spread the
  // fleet's clients evenly over the servers.
  int64_t subset = opts_.subset_size;
  if (subset == 0) {
    Flag* f = subset_size_flag();
    subset = f != nullptr ? f->int64_value() : 0;
  }
  if (subset > 0 && eps.size() > static_cast<size_t>(subset)) {
    // The seed is PRE-mixed: small consecutive seeds (pids) xor'd raw
    // into an avalanched endpoint hash barely perturb the final mix's
    // ordering, and every client would elect the same subset.
    const uint64_t seed = mix_u64(opts_.subset_seed != 0
                                      ? opts_.subset_seed
                                      : static_cast<uint64_t>(getpid()));
    std::stable_sort(eps.begin(), eps.end(),
                     [seed](const NsEntry& a, const NsEntry& b) {
                       return mix_u64(seed ^ EndPointHash()(a.ep)) >
                              mix_u64(seed ^ EndPointHash()(b.ep));
                     });
    eps.resize(static_cast<size_t>(subset));
  }
  // Preserve breaker state + channels of endpoints that survive.
  auto fresh = std::make_shared<Cluster>();
  {
    auto cur = cluster_.Read();
    const Cluster* old = cur->get();
    for (const auto& [ep, weight, zone] : eps) {
      ServerNode node;
      node.ep = ep;
      node.weight = weight;
      node.zone = zone;
      std::shared_ptr<Channel> ch;
      if (old != nullptr) {
        for (size_t i = 0; i < old->nodes.size(); ++i) {
          if (old->nodes[i].ep == ep) {
            node = old->nodes[i];
            node.weight = weight;  // refresh may re-weight...
            node.zone = zone;      // ...and re-label
            ch = old->channels[i];
            break;
          }
        }
      }
      if (ch == nullptr) {
        ch = std::make_shared<Channel>();
        Channel::Options copts;
        copts.timeout_ms = opts_.timeout_ms;
        copts.connection_type = opts_.connection_type;
        copts.auth = opts_.auth;
        copts.protocol = opts_.protocol;
        {
          std::lock_guard<std::mutex> qg(qos_mu_);
          copts.qos_tenant = opts_.qos_tenant;
          copts.qos_priority = opts_.qos_priority;
        }
        if (ch->Init(endpoint2str(ep), &copts) != 0) {
          continue;
        }
      }
      fresh->nodes.push_back(std::move(node));
      fresh->channels.push_back(std::move(ch));
    }
  }
  if (fresh->nodes.empty()) {
    return -1;
  }
  cluster_.Modify([&fresh](std::shared_ptr<Cluster>& c) {
    c = fresh;
    return true;
  });
  // Start the periodic refresher once.
  bool expect = false;
  if (refresher_started_.compare_exchange_strong(expect, true)) {
    fiber_init(0);
    fiber_start(nullptr, &ClusterChannel::refresh_fiber, this, 0);
  }
  // Push-based membership: when the NS can long-poll, a watch fiber
  // turns registry version bumps into immediate refreshes (the periodic
  // refresher stays as the poll fallback / health-check cadence).
  if (ns_->supports_watch()) {
    expect = false;
    if (watcher_started_.compare_exchange_strong(expect, true)) {
      if (fiber_start(nullptr, &ClusterChannel::watch_fiber, this, 0) !=
          0) {
        // Spawn failed: keep watcher_started_ TRUE and settle the join
        // state the destructor waits on.  Resetting the flag would let a
        // later refresh() (possibly racing the destructor) spawn a
        // watcher the destructor never joins — push degrades to the
        // periodic poll instead.
        watch_done_.value.store(1, std::memory_order_release);
        watch_done_.wake_all();
        watcher_exited_.store(true, std::memory_order_release);
      }
    }
  }
  return 0;
}

void ClusterChannel::watch_fiber(void* arg) {
  auto* self = static_cast<ClusterChannel*>(arg);
  uint64_t version = 0;
  while (!self->stopping_.load(std::memory_order_acquire)) {
    const uint64_t before = version;
    // Bounded park budget per round: a change still answers IMMEDIATELY
    // (the registry wakes the parked handler); the budget only caps how
    // long the destructor can be stuck behind an idle poll.
    const int rc = self->ns_->watch(self->ns_param_, &version, 1000);
    if (self->stopping_.load(std::memory_order_acquire)) {
      break;
    }
    if (rc == 0) {
      if (version != before) {
        self->refresh();  // push delivery: apply the delta NOW
      }
      continue;
    }
    // Registry unreachable (or watch unsupported after all): back off
    // briefly, interruptibly; the periodic refresher keeps polling.
    const uint32_t snap =
        self->watch_wake_.value.load(std::memory_order_acquire);
    self->watch_wake_.wait(snap, monotonic_time_us() + 500000);
  }
  self->watch_done_.value.store(1, std::memory_order_release);
  self->watch_done_.wake_all();
  // LAST access to *self (see ~ClusterChannel).
  self->watcher_exited_.store(true, std::memory_order_release);
}

void ClusterChannel::set_default_qos(const std::string& tenant,
                                     uint8_t priority) {
  std::string capped = tenant.size() > 64 ? tenant.substr(0, 64) : tenant;
  {
    std::lock_guard<std::mutex> qg(qos_mu_);
    opts_.qos_tenant = capped;
    opts_.qos_priority = priority;
  }
  std::shared_ptr<Cluster> cluster;
  {
    auto cur = cluster_.Read();
    cluster = *cur;
  }
  if (cluster != nullptr) {
    for (const auto& ch : cluster->channels) {
      ch->set_default_qos(capped, priority);
    }
  }
}

void ClusterChannel::refresh_fiber(void* arg) {
  auto* self = static_cast<ClusterChannel*>(arg);
  while (!self->stopping_.load(std::memory_order_acquire)) {
    // Interruptible sleep: the destructor bumps refresh_wake_ to end it.
    const uint32_t snap =
        self->refresh_wake_.value.load(std::memory_order_acquire);
    self->refresh_wake_.wait(
        snap, monotonic_time_us() + self->opts_.refresh_interval_ms * 1000);
    if (self->stopping_.load(std::memory_order_acquire)) {
      break;
    }
    self->refresh();       // PeriodicNamingService parity
    self->health_check();  // details/health_check.cpp parity
  }
  self->refresh_done_.value.store(1, std::memory_order_release);
  self->refresh_done_.wake_all();
  // LAST access to *self (see ~ClusterChannel).
  self->refresher_exited_.store(true, std::memory_order_release);
}

namespace {

struct ProbeCtx {
  std::shared_ptr<void> cluster_keepalive;
  std::shared_ptr<Channel> channel;
  std::shared_ptr<std::atomic<int64_t>> quarantined_until;
  std::shared_ptr<std::atomic<int>> fail_counter;
  std::string method;
  int64_t timeout_ms;
  std::shared_ptr<CountdownEvent> latch;
};

void probe_fiber(void* p) {
  std::unique_ptr<ProbeCtx> ctx(static_cast<ProbeCtx*>(p));
  Controller cntl;
  cntl.set_timeout_ms(ctx->timeout_ms);
  IOBuf req, resp;
  ctx->channel->CallMethod(ctx->method, req, &resp, &cntl);
  // ALLOWLIST of "the server definitely answered": success, or the
  // server-side errors a probe legitimately produces (no such method,
  // admission-limited, tenant-shed).  Everything else — including local
  // failures like fid exhaustion — must NOT revive the node.  A
  // kEOverloaded answer proves the TRANSPORT alive (the shed is QoS
  // policy, not node death), so the node revives and the next real call
  // re-judges it.
  // kEDraining joins the allowlist for the same reason as kEOverloaded:
  // a draining node's transport demonstrably works (and its successor
  // revives on this endpoint), so the breaker may open.
  const bool answered = !cntl.Failed() || cntl.error_code() == ENOENT ||
                        cntl.error_code() == kELimit ||
                        cntl.error_code() == kEOverloaded ||
                        cntl.error_code() == kEDraining ||
                        cntl.error_code() == ESHUTDOWN;
  if (answered) {
    ctx->quarantined_until->store(0, std::memory_order_relaxed);
    ctx->fail_counter->store(0, std::memory_order_relaxed);
  }
  ctx->latch->signal();
}

}  // namespace

void ClusterChannel::health_check() {
  if (opts_.health_check_method.empty()) {
    return;
  }
  std::shared_ptr<Cluster> cluster;
  {
    auto cur = cluster_.Read();
    cluster = *cur;
  }
  if (cluster == nullptr) {
    return;
  }
  // Probes fan out concurrently so N blackholed nodes cost one probe
  // timeout per tick, not N (and shutdown isn't stalled behind them).
  const int64_t now = monotonic_time_us();
  std::vector<ProbeCtx*> probes;
  for (size_t i = 0; i < cluster->nodes.size(); ++i) {
    ServerNode& node = cluster->nodes[i];
    if (node.quarantined_until_us->load(std::memory_order_relaxed) <= now) {
      continue;  // healthy (or already expired)
    }
    probes.push_back(new ProbeCtx{cluster, cluster->channels[i],
                                  node.quarantined_until_us,
                                  node.consecutive_failures,
                                  opts_.health_check_method,
                                  opts_.health_check_timeout_ms, nullptr});
  }
  if (probes.empty()) {
    return;
  }
  auto latch =
      std::make_shared<CountdownEvent>(static_cast<int>(probes.size()));
  for (ProbeCtx* p : probes) {
    p->latch = latch;
    if (fiber_start(nullptr, probe_fiber, p, 0) != 0) {
      latch->signal();
      delete p;
    }
  }
  // Sliced wait so a concurrent destructor (stopping_) isn't stalled a full
  // probe timeout behind blackholed nodes; probe fibers own their state via
  // shared_ptrs and finish safely after we stop waiting.
  const int64_t wait_deadline =
      monotonic_time_us() + opts_.health_check_timeout_ms * 1000 + 1000000;
  while (!stopping_.load(std::memory_order_acquire) &&
         monotonic_time_us() < wait_deadline) {
    if (latch->wait(monotonic_time_us() + 50000) == 0) {
      break;
    }
  }
}

size_t ClusterChannel::healthy_count() {
  auto cur = cluster_.Read();
  const Cluster* c = cur->get();
  if (c == nullptr) {
    return 0;
  }
  const int64_t now = monotonic_time_us();
  size_t n = 0;
  for (const ServerNode& node : c->nodes) {
    if (node.quarantined_until_us->load(std::memory_order_relaxed) <= now) {
      ++n;
    }
  }
  return n;
}

namespace {
struct AsyncCall {
  ClusterChannel* ch;
  std::string method;
  IOBuf request;
  IOBuf* response;
  Controller* cntl;
  Closure done;
  uint64_t hash_key;
  // The caller's ambient trace context, captured at submit: the retry
  // fiber has its own (empty) fiber-local storage, so without this the
  // attempt's client span would root a fresh trace instead of linking
  // under the caller's (rpcz propagation, ISSUE 4).
  uint64_t amb_trace = 0;
  uint64_t amb_span = 0;
  // Ambient deadline, same capture rationale (value-only: the caller's
  // cancel scope may die before this detached fiber runs).
  int64_t amb_deadline = 0;
  // Ambient routing hint (net/lb_hint.h), same capture rationale: the
  // retry fiber's thread has no hint installed.
  bool amb_hint_set = false;
  EndPoint amb_hint;
};
}  // namespace

namespace {
// EWMA latency feedback for p2c/la (OnComplete parity, controller.cpp:804).
void feed_latency(ServerNode& node, int64_t lat_us) {
  if (lat_us <= 0) {
    return;
  }
  const int64_t prev =
      node.ewma_latency_us->load(std::memory_order_relaxed);
  node.ewma_latency_us->store(asym_ewma(prev, lat_us),
                              std::memory_order_relaxed);
}
}  // namespace

namespace {
// One retry token in bucket units, and the bucket cap (100 banked
// retries — the SRE convention: the budget bounds a STORM, it never
// starves the occasional isolated retry).
constexpr int64_t kRetryTokenCost = 100;
constexpr int64_t kRetryTokenCap = 100 * kRetryTokenCost;
}  // namespace

void ClusterChannel::retry_budget_earn() {
  const int64_t pct = cluster_retry_budget_pct();
  if (pct <= 0) {
    return;  // budget off
  }
  // Relaxed CAS loop: the bucket is advisory rate-limiting state — no
  // data is published through it.
  int64_t cur = retry_tokens_.load(std::memory_order_relaxed);
  while (cur < kRetryTokenCap) {
    const int64_t next = std::min(cur + pct, kRetryTokenCap);
    if (retry_tokens_.compare_exchange_weak(cur, next,
                                            std::memory_order_relaxed)) {
      break;
    }
  }
}

bool ClusterChannel::retry_budget_take() {
  if (cluster_retry_budget_pct() <= 0) {
    return true;  // budget off: pre-budget retry semantics
  }
  // Relaxed: see retry_budget_earn.
  int64_t cur = retry_tokens_.load(std::memory_order_relaxed);
  while (cur >= kRetryTokenCost) {
    if (retry_tokens_.compare_exchange_weak(cur, cur - kRetryTokenCost,
                                            std::memory_order_relaxed)) {
      return true;
    }
  }
  return false;
}

void ClusterChannel::feed_cluster_latency(int64_t lat_us) {
  if (lat_us <= 0) {
    return;
  }
  // Relaxed: advisory smoothing state (hedge feasibility estimate).
  const int64_t prev = lat_ewma_us_.load(std::memory_order_relaxed);
  lat_ewma_us_.store(asym_ewma(prev, lat_us), std::memory_order_relaxed);
}

void ClusterChannel::feed_breaker(ServerNode& node, bool success) {
  if (success) {
    node.consecutive_failures->store(0, std::memory_order_relaxed);
    // Relaxed: advisory backoff state, no ordering carried.
    node.backoff_ms->store(0, std::memory_order_relaxed);
    return;
  }
  node.consecutive_failures->fetch_add(1, std::memory_order_relaxed);
  // Decorrelated jitter (AWS-style: window ~ U[base, min(cap, prev*3)]),
  // drawn from the FaultActor splitmix64 SIDE stream so a seeded chaos
  // schedule replays the identical backoff sequence.  Plain doubling
  // synchronized every client that watched the same node die — they all
  // re-probed the reviving node in lockstep, re-knocking it over.
  // Relaxed: advisory backoff state, no ordering carried.
  const int64_t prev = node.backoff_ms->load(std::memory_order_relaxed);
  const int64_t base = std::max<int64_t>(opts_.quarantine_base_ms, 1);
  const int64_t hi = std::min(opts_.quarantine_max_ms,
                              std::max(prev * 3, base));
  int64_t quarantine_ms = base;
  if (hi > base) {
    quarantine_ms +=
        static_cast<int64_t>(FaultActor::global().jitter_draw() %
                             static_cast<uint64_t>(hi - base + 1));
  }
  // Relaxed: see above.
  node.backoff_ms->store(quarantine_ms, std::memory_order_relaxed);
  node.quarantined_until_us->store(monotonic_time_us() + quarantine_ms * 1000,
                                   std::memory_order_relaxed);
}

namespace {

// Shared state of one hedged call; attempt fibers keep it alive past the
// caller (a losing attempt may still be in flight when the call returns).
struct HedgeCtx {
  std::shared_ptr<void> cluster_keepalive;
  std::string method;
  IOBuf request;
  IOBuf attachment;
  std::shared_ptr<Channel> channels[2];
  size_t node_idx[2] = {0, 0};
  Controller cntls[2];
  IOBuf responses[2];
  // An attempt's cntls[i]/responses[i] may only be read after done[i]
  // (release-stored when its fiber finished writing them).
  std::atomic<bool> done[2] = {{false}, {false}};
  // False when the attempt never ran (fiber spawn failed): its synthetic
  // EAGAIN must not shadow a real error from the other attempt.
  bool spawned[2] = {true, true};
  std::atomic<int> winner{-1};   // first successful attempt index
  std::atomic<int> failures{0};
  std::atomic<int> launched{1};
  Event ev;  // bumped on every attempt completion
  // Caller's ambient trace context (attempt fibers have empty fls).
  uint64_t amb_trace = 0;
  uint64_t amb_span = 0;
  // Caller's ambient deadline, re-installed in each attempt fiber so
  // the wire stamp carries the caller's REMAINING budget, not a fresh
  // full timeout.  Value-only: the caller's cancel scope is not
  // propagated — a losing attempt may outlive the serving request, and
  // the scope's lifetime is bounded by it (net/deadline.h).
  int64_t amb_deadline = 0;

  bool settled() const {
    return winner.load(std::memory_order_acquire) >= 0 ||
           failures.load(std::memory_order_acquire) >=
               launched.load(std::memory_order_acquire);
  }

  void on_attempt_done(int i) {
    done[i].store(true, std::memory_order_release);
    if (!cntls[i].Failed()) {
      int expect = -1;
      winner.compare_exchange_strong(expect, i);
    } else {
      failures.fetch_add(1, std::memory_order_acq_rel);
    }
    ev.value.fetch_add(1, std::memory_order_release);
    ev.wake_all();
  }
};

struct HedgeFiberArg {
  std::shared_ptr<HedgeCtx> ctx;
  int index;
};

void hedge_attempt_fiber(void* p) {
  std::unique_ptr<HedgeFiberArg> arg(static_cast<HedgeFiberArg*>(p));
  HedgeCtx* ctx = arg->ctx.get();
  const int i = arg->index;
  // Both racing attempts carry the caller's trace: their spans show up
  // side by side under one parent in /rpcz (hedges are exactly the kind
  // of tail behavior a timeline exists to expose).
  set_ambient_trace(ctx->amb_trace, ctx->amb_span);
  set_ambient_deadline(ctx->amb_deadline);
  ctx->channels[i]->CallMethod(ctx->method, ctx->request,
                               &ctx->responses[i], &ctx->cntls[i]);
  ctx->on_attempt_done(i);
}

void wait_settled(HedgeCtx* ctx, int64_t deadline_us) {
  while (!ctx->settled()) {
    const uint32_t snap = ctx->ev.value.load(std::memory_order_acquire);
    if (ctx->settled()) {
      break;
    }
    if (ctx->ev.wait(snap, deadline_us) == ETIMEDOUT) {
      break;
    }
  }
}

}  // namespace

// Hedged execution: fire the primary, and if it hasn't answered within
// backup_request_ms (or failed outright), race a backup on a different
// node; the first success wins and the loser's late response dies on its
// stale correlation id — the same guarantee that makes brpc's backup
// requests safe (channel.cpp:582-603).
std::atomic<int> test_fail_hedge_spawns{0};

void ClusterChannel::call_hedged(std::shared_ptr<Cluster> cluster,
                                 const std::string& method,
                                 const IOBuf& request, IOBuf* response,
                                 Controller* cntl, uint64_t hash_key) {
  const int64_t now = monotonic_time_us();
  std::vector<size_t> healthy;
  for (size_t i = 0; i < cluster->nodes.size(); ++i) {
    if (cluster->nodes[i].quarantined_until_us->load(
            std::memory_order_relaxed) <= now) {
      healthy.push_back(i);
    }
  }
  if (healthy.empty()) {
    for (size_t i = 0; i < cluster->nodes.size(); ++i) {
      healthy.push_back(i);
    }
  }
  // Reset per-call state on the caller's controller, preserving the
  // attachment (mirrors the retry path's contract).  The caller's own
  // timeout takes precedence over the channel default (as in the
  // reference, where the controller wins over ChannelOptions).
  const int64_t eff_timeout_ms = cntl->timeout_ms_or(opts_.timeout_ms);
  IOBuf attachment = cntl->request_attachment();
  cntl->Reset();
  cntl->request_attachment() = attachment;

  auto ctx = std::make_shared<HedgeCtx>();
  ctx->cluster_keepalive = cluster;
  ctx->method = method;
  ctx->request = request;  // zero-copy share
  ctx->attachment = attachment;
  get_ambient_trace(&ctx->amb_trace, &ctx->amb_span);
  ctx->amb_deadline = ambient_deadline();
  retry_budget_earn();  // the primary attempt funds the bucket

  auto arm = [&](int slot, size_t node_idx) {
    ctx->channels[slot] = cluster->channels[node_idx];
    ctx->node_idx[slot] = node_idx;
    ctx->cntls[slot].set_timeout_ms(eff_timeout_ms);
    ctx->cntls[slot].set_request_compress_type(cntl->request_compress_type());
    ctx->cntls[slot].set_enable_checksum(cntl->checksum_enabled());
    if (cntl->qos_set()) {
      // Per-call tag outranks the member channels' default on BOTH
      // racing attempts (the retry loop keeps the caller's controller,
      // so it propagates there for free).
      ctx->cntls[slot].set_qos(cntl->qos_tenant(), cntl->qos_priority());
    }
    ctx->cntls[slot].request_attachment() = ctx->attachment;
    auto* arg = new HedgeFiberArg{ctx, slot};
    bool inject = false;
    int cur = test_fail_hedge_spawns.load(std::memory_order_relaxed);
    while (cur > 0 &&
           !test_fail_hedge_spawns.compare_exchange_weak(cur, cur - 1)) {
    }
    inject = cur > 0;
    if (inject || fiber_start(nullptr, hedge_attempt_fiber, arg, 0) != 0) {
      // A failed spawn must still settle the slot, or wait_settled(-1)
      // blocks forever (mirrors run_fanout's spawn-failure path).
      delete arg;
      ctx->spawned[slot] = false;
      ctx->cntls[slot].SetFailed(EAGAIN, "fiber_start failed");
      ctx->on_attempt_done(slot);
    }
  };

  const size_t primary = lb_->select(healthy, cluster->nodes, hash_key, 0);
  arm(0, primary);
  wait_settled(ctx.get(), now + opts_.backup_request_ms * 1000);

  if (ctx->winner.load(std::memory_order_acquire) < 0) {
    // Slow or failed primary: race a backup on another node if one exists.
    std::vector<size_t> others;
    for (size_t i : healthy) {
      if (i != primary) {
        others.push_back(i);
      }
    }
    // Hedge governance (net/deadline.h): a backup is pure extra load
    // when the remaining budget cannot cover a typical attempt (the
    // cluster's observed smoothed latency), and it spends a retry
    // token like any other extra attempt.
    bool allow = !others.empty();
    if (allow) {
      // Relaxed: advisory estimate (see feed_cluster_latency).
      const int64_t p50 = lat_ewma_us_.load(std::memory_order_relaxed);
      int64_t remaining = INT64_MAX;
      if (eff_timeout_ms > 0) {
        remaining = now + eff_timeout_ms * 1000 - monotonic_time_us();
      }
      if (ctx->amb_deadline != 0) {
        remaining = std::min(remaining,
                             ctx->amb_deadline - monotonic_time_us());
      }
      if (p50 > 0 && remaining < p50) {
        allow = false;
        deadline_vars().hedge_suppressed << 1;
        if (timeline::enabled()) {
          timeline::record(
              timeline::kDeadline, 0,
              (timeline::kDeadlineHedgeSuppressed << 56) |
                  static_cast<uint64_t>(remaining > 0 ? remaining : 0));
        }
      } else if (!retry_budget_take()) {
        allow = false;
        deadline_vars().hedge_suppressed << 1;
        if (timeline::enabled()) {
          timeline::record(timeline::kDeadline, 0,
                           timeline::kDeadlineRetrySuppressed << 56);
        }
      }
    }
    if (allow) {
      ctx->launched.store(2, std::memory_order_release);
      arm(1, lb_->select(others, cluster->nodes, hash_key, 1));
    }
    wait_settled(ctx.get(), -1);
  }

  const int w = ctx->winner.load(std::memory_order_acquire);
  // Breaker feedback: judge only attempts that COMPLETED (done[i] is the
  // release barrier for their controllers; a still-flying loser is not
  // touched — its late completion only writes ctx, which the fibers keep
  // alive via shared_ptr).  A failed primary a backup rescued still counts
  // against the primary's node.
  for (int i = 0; i < 2; ++i) {
    if (ctx->channels[i] == nullptr ||
        !ctx->done[i].load(std::memory_order_acquire)) {
      continue;
    }
    if (ctx->cntls[i].Failed() &&
        (ctx->cntls[i].error_code() == kEDraining ||
         ctx->cntls[i].error_code() == kEDeadlineExpired ||
         ctx->cntls[i].error_code() == ECANCELED)) {
      // Graceful leave / expired budget / cancelled caller: the node is
      // healthy either way — quarantining it would punish it for the
      // caller's clock.
      continue;
    }
    feed_breaker(cluster->nodes[ctx->node_idx[i]], !ctx->cntls[i].Failed());
    if (!ctx->cntls[i].Failed()) {
      feed_latency(cluster->nodes[ctx->node_idx[i]],
                   ctx->cntls[i].latency_us());
      feed_cluster_latency(ctx->cntls[i].latency_us());
    }
  }
  if (w < 0) {
    // Prefer an attempt that actually ran; among those, the backup's
    // (fresher) error, matching the reference's last-error reporting.
    int chosen = ctx->done[1].load(std::memory_order_acquire) ? 1 : 0;
    if (!ctx->spawned[chosen] &&
        ctx->done[1 - chosen].load(std::memory_order_acquire) &&
        ctx->spawned[1 - chosen]) {
      chosen = 1 - chosen;
    }
    cntl->SetFailed(ctx->cntls[chosen].error_code(),
                    ctx->cntls[chosen].error_text());
  } else {
    *response = std::move(ctx->responses[w]);
    cntl->response_attachment() =
        std::move(ctx->cntls[w].response_attachment());
    cntl->set_latency_us(ctx->cntls[w].latency_us());
    // The winner's server stamps are the call's (net/wire_split.h).
    const Controller::CallState& won = ctx->cntls[w].call();
    cntl->call().srv = won.srv;
    cntl->call().srv_same_clock = won.srv_same_clock;
  }
}

void ClusterChannel::CallMethod(const std::string& method,
                                const IOBuf& request, IOBuf* response,
                                Controller* cntl, Closure done,
                                uint64_t hash_key) {
  if (done) {
    // Async: the retry loop must not block the caller — run it in a fiber.
    auto* call = new AsyncCall{this,     method, request, response,
                               cntl,     {},     hash_key};
    call->done = std::move(done);
    get_ambient_trace(&call->amb_trace, &call->amb_span);
    call->amb_deadline = ambient_deadline();
    call->amb_hint_set = lb_hint_get(&call->amb_hint);
    if (fiber_start(
            nullptr,
            [](void* arg) {
              std::unique_ptr<AsyncCall> c(static_cast<AsyncCall*>(arg));
              // Fresh fiber, empty fls: re-install the caller's trace
              // context (cleared with the fiber's fls at exit).
              set_ambient_trace(c->amb_trace, c->amb_span);
              set_ambient_deadline(c->amb_deadline);
              if (c->amb_hint_set) {
                lb_hint_set(c->amb_hint);
              }
              c->ch->CallMethod(c->method, c->request, c->response, c->cntl,
                                nullptr, c->hash_key);
              lb_hint_clear();
              c->done();
            },
            call, 0) != 0) {
      // Spawn failure must still complete the call (fiber_start does not
      // take ownership of arg on failure).
      std::unique_ptr<AsyncCall> c(call);
      cntl->SetFailed(EAGAIN, "fiber_start failed");
      c->done();
    }
    return;
  }
  std::shared_ptr<Cluster> cluster;
  {
    auto cur = cluster_.Read();
    cluster = *cur;
  }
  if (cluster == nullptr || cluster->nodes.empty()) {
    cntl->SetFailed(ENOENT, "no servers in cluster");
    if (done) {
      done();
    }
    return;
  }
  if (opts_.backup_request_ms > 0) {
    call_hedged(cluster, method, request, response, cntl, hash_key);
    if (done) {
      done();
    }
    return;
  }
  // Retry loop (sync under the hood; async wraps the final completion).
  // Parity: retries pick a different node and quarantined nodes are skipped
  // (circuit_breaker + cluster_recover semantics condensed).  Captured
  // before the first Reset: the caller's own timeout outranks the channel
  // default on every attempt.
  const int64_t eff_timeout_ms = cntl->timeout_ms_or(opts_.timeout_ms);
  const int attempts = 1 + opts_.max_retry;
  retry_budget_earn();  // this primary call funds the bucket
  std::vector<size_t> tried;
  for (int attempt = 0; attempt < attempts; ++attempt) {
    if (attempt > 0 && !retry_budget_take()) {
      // Retry-storm governor (net/deadline.h): the budget bounds attempt
      // amplification at ~(1 + pct/100)x under total downstream failure
      // — every layer retrying independently is how outages multiply.
      deadline_vars().retry_suppressed << 1;
      if (timeline::enabled()) {
        timeline::record(timeline::kDeadline, 0,
                         timeline::kDeadlineRetrySuppressed << 56);
      }
      break;
    }
    const int64_t now = monotonic_time_us();
    std::vector<size_t> healthy;
    for (size_t i = 0; i < cluster->nodes.size(); ++i) {
      const ServerNode& n = cluster->nodes[i];
      const bool quarantined =
          n.quarantined_until_us->load(std::memory_order_relaxed) > now;
      const bool already_tried =
          std::find(tried.begin(), tried.end(), i) != tried.end();
      if (!quarantined && !already_tried) {
        healthy.push_back(i);
      }
    }
    if (healthy.empty()) {
      // All quarantined/tried: fall back to every untried node (cluster
      // recovery — never fail purely because breakers are open).
      for (size_t i = 0; i < cluster->nodes.size(); ++i) {
        if (std::find(tried.begin(), tried.end(), i) == tried.end()) {
          healthy.push_back(i);
        }
      }
    }
    if (healthy.empty()) {
      break;  // genuinely nothing left
    }
    const size_t idx = lb_->select(healthy, cluster->nodes, hash_key, attempt);
    tried.push_back(idx);
    ServerNode& node = cluster->nodes[idx];

    // Reset per-attempt state but preserve the caller's attachment (shared
    // zero-copy, so re-attaching per retry is free) and the caller's own
    // timeout, which takes precedence over the channel default.
    IOBuf attachment = cntl->request_attachment();
    cntl->Reset();
    cntl->request_attachment() = std::move(attachment);
    cntl->set_timeout_ms(eff_timeout_ms);
    const bool last_attempt = attempt == attempts - 1;
    node.inflight->fetch_add(1, std::memory_order_relaxed);
    cluster->channels[idx]->CallMethod(method, request, response, cntl);
    node.inflight->fetch_sub(1, std::memory_order_relaxed);
    if (!cntl->Failed()) {
      feed_breaker(node, true);
      feed_latency(node, cntl->latency_us());
      feed_cluster_latency(cntl->latency_us());
      if (done) {
        done();
      }
      return;
    }
    if (cntl->error_code() == kEDeadlineExpired ||
        cntl->error_code() == ECANCELED) {
      // The caller's budget is just as dead on every other node (and a
      // cancelled caller wants nothing at all): retrying the chain is
      // pure wasted work (net/deadline.h).  The breaker stays closed —
      // the server is healthy, the clock ran out / the caller left.
      break;
    }
    // kEDraining (Server::Drain, concurrency_limiter.h) is immediate-
    // failover-WITHOUT-quarantine: the node is healthy, just leaving —
    // the tried-set exclusion already moves this call to a different
    // node, and leaving the breaker closed keeps the endpoint clean for
    // the hot-restart successor that revives on it.
    if (cntl->error_code() == kEDraining) {
      if (last_attempt) {
        break;
      }
      continue;
    }
    // Exponential (jittered) quarantine.  kEOverloaded (per-tenant
    // admission shed, net/qos.h) rides this same path BY DESIGN: the
    // node is alive but shedding, so the retry moves to a different node
    // immediately (the tried-set exclusion above never re-picks this
    // one) and the breaker backs traffic off it until the quarantine
    // window expires or a health probe answers.
    feed_breaker(node, false);
    if (last_attempt) {
      break;
    }
  }
  if (done) {
    done();
  }
}

}  // namespace trpc
