// Echo benchmark — the reference's headline workload
// (docs/cn/benchmark.md: multi-threaded sync echo; BASELINE.md).
//
// Usage: bench_echo [nfibers] [payload_bytes] [seconds] [single|pooled|short]
// Prints QPS, throughput and latency percentiles for sync echo over one
// pooled loopback connection.
#include <unistd.h>

#include <algorithm>
#include <atomic>
#include <cstdio>
#include <cstdlib>
#include <string>
#include <vector>

#include "base/flags.h"
#include "base/time.h"
#include "fiber/fiber.h"
#include "net/channel.h"
#include "net/server.h"
#include "stat/profiler.h"
#include "stat/variable.h"

using namespace trpc;

namespace {

struct WorkerArgs {
  Channel* ch;
  std::string payload;
  int64_t stop_us;
  std::atomic<long>* calls;
  std::atomic<long>* failures;
  std::vector<int64_t>* latencies;  // per-fiber, merged later
};

void bench_fiber(void* p) {
  WorkerArgs* a = static_cast<WorkerArgs*>(p);
  IOBuf req;
  req.append(a->payload);
  while (monotonic_time_us() < a->stop_us) {
    Controller cntl;
    cntl.set_timeout_ms(5000);
    IOBuf resp;
    const int64_t t0 = monotonic_time_us();
    a->ch->CallMethod("Echo.Echo", req, &resp, &cntl);
    const int64_t dt = monotonic_time_us() - t0;
    if (cntl.Failed() || resp.size() != a->payload.size()) {
      a->failures->fetch_add(1);
    } else {
      a->calls->fetch_add(1);
      a->latencies->push_back(dt);
    }
  }
}

}  // namespace

int main(int argc, char** argv) {
  const int nfibers = argc > 1 ? atoi(argv[1]) : 64;
  const size_t payload = argc > 2 ? atoi(argv[2]) : 1024;
  const int seconds = argc > 3 ? atoi(argv[3]) : 3;
  const char* conn_type = argc > 4 ? argv[4] : "single";

  // TRPC_BENCH_FLAGS="name=value,name=value": validated runtime flag
  // flips applied before any traffic, so a harness can measure the same
  // binary with a feature armed (e.g. rpcz_enabled=true against the
  // default for the span recorder's flag-ON overhead).
  if (const char* spec = getenv("TRPC_BENCH_FLAGS")) {
    std::string s(spec);
    size_t pos = 0;
    while (pos < s.size()) {
      size_t end = s.find(',', pos);
      if (end == std::string::npos) {
        end = s.size();
      }
      const std::string kv = s.substr(pos, end - pos);
      pos = end + 1;
      const size_t eq = kv.find('=');
      if (eq == std::string::npos || kv.empty()) {
        continue;
      }
      if (Flag::set(kv.substr(0, eq), kv.substr(eq + 1)) != 0) {
        fprintf(stderr, "bad TRPC_BENCH_FLAGS entry: %s\n", kv.c_str());
        return 1;
      }
    }
  }

  Server server;
  server.RegisterMethod("Echo.Echo", [](Controller*, const IOBuf& req,
                                        IOBuf* resp, Closure done) {
    resp->append(req);
    done();
  });
  if (server.Start(0) != 0) {
    fprintf(stderr, "server start failed\n");
    return 1;
  }
  Channel ch;
  Channel::Options copts;
  copts.connection_type = conn_type;
  if (ch.Init("127.0.0.1:" + std::to_string(server.port()), &copts) != 0) {
    fprintf(stderr, "bad connection type %s\n", conn_type);
    return 1;
  }

  std::atomic<long> calls{0}, failures{0};
  std::vector<std::vector<int64_t>> lat(nfibers);
  std::vector<WorkerArgs> args(nfibers);
  std::vector<fiber_t> fibers(nfibers);
  // BENCH_PROFILE=1: sample the whole run and dump hotspots to stderr
  // (the /hotspots SIGPROF profiler, usable standalone).
  const bool profiling = getenv("BENCH_PROFILE") != nullptr;
  if (profiling) {
    profiler_start(997);
  }
  const int64_t stop_us = monotonic_time_us() + seconds * 1000000LL;
  const int64_t t0 = monotonic_time_us();
  for (int i = 0; i < nfibers; ++i) {
    args[i] = WorkerArgs{&ch, std::string(payload, 'x'), stop_us, &calls,
                         &failures, &lat[i]};
    fiber_start(&fibers[i], bench_fiber, &args[i]);
  }
  for (auto f : fibers) {
    fiber_join(f);
  }
  const double secs = (monotonic_time_us() - t0) / 1e6;
  if (profiling) {
    fprintf(stderr, "%s\n", profiler_stop_and_dump(50).c_str());
  }
  // BENCH_DUMP_VARS=1: print the hot-path stat vars (write coalescing,
  // inline-write hit rate, dispatch batching, bulk wakes) to stderr.
  if (getenv("BENCH_DUMP_VARS") != nullptr) {
    for (auto& [name, value] : Variable::dump_exposed()) {
      if (name.rfind("socket_", 0) == 0 || name.rfind("messenger_", 0) == 0 ||
          name.rfind("fiber_bulk_", 0) == 0) {
        fprintf(stderr, "%s : %s\n", name.c_str(), value.c_str());
      }
    }
  }

  std::vector<int64_t> all;
  for (auto& v : lat) {
    all.insert(all.end(), v.begin(), v.end());
  }
  std::sort(all.begin(), all.end());
  auto pct = [&](double p) -> long {
    if (all.empty()) return 0;
    return all[std::min(all.size() - 1,
                        static_cast<size_t>(p * all.size()))];
  };
  const double qps = calls.load() / secs;
  printf("{\"fibers\": %d, \"conn\": \"%s\", \"payload\": %zu, \"qps\": %.0f, "
         "\"throughput_MBps\": %.1f, \"p50_us\": %ld, \"p99_us\": %ld, "
         "\"p999_us\": %ld, \"failures\": %ld}\n",
         nfibers, conn_type, payload, qps, qps * payload * 2 / 1e6, pct(0.5),
         pct(0.99), pct(0.999), failures.load());
  return 0;
}
