// Builtin introspection services (parity: src/brpc/builtin/ — registered at
// server start, server.cpp:501-604: /status /vars /connections /flags
// /index /version /health /list /protobufs /threads /memory /metrics ...).
#include <stdio.h>
#include <string.h>
#include <unistd.h>

#include <algorithm>
#include <atomic>
#include <cstdlib>
#include <filesystem>
#include <system_error>
#include <vector>
#include <string>

#include "base/flags.h"
#include "base/json.h"
#include "base/logging.h"
#include "base/proc.h"
#include "base/time.h"
#include "fiber/analysis.h"
#include "fiber/fiber.h"
#include "fiber/fid.h"
#include "net/fault.h"
#include "net/http_protocol.h"
#include "net/naming.h"
#include "net/server.h"
#include "net/socket.h"
#include "net/span.h"
#include "stat/capture.h"
#include "stat/heap_profiler.h"
#include "stat/slo.h"
#include "stat/profiler.h"
#include "stat/tuner.h"
#include "stat/variable.h"

namespace trpc {

std::atomic<int64_t> g_socket_count{0};

namespace {

// Defined at load time so /flags can list and flip it before any /dir
// request arrives (function-local statics in the registry make this
// initialization-order-safe).
Flag* dir_service_flag = Flag::define_bool(
    "enable_dir_service", false,
    "serve the /dir filesystem browser (reference: -enable_dir_service)");

std::string flags_text() {
  std::string out;
  for (Flag* f : Flag::all()) {
    out += f->name() + " = " + f->value_string();
    if (f->value_string() != f->default_value()) {
      out += " (default: " + f->default_value() + ")";
    }
    if (!f->reloadable()) {
      out += " [immutable]";
    }
    out += "  # " + f->description() + "\n";
  }
  return out;
}

}  // namespace

bool builtin_http_dispatch(Server* srv, const HttpRequest& req,
                           const IOBuf& payload, int* status,
                           std::string* body, std::string* content_type) {
  const std::string& path = req.path;
  *status = 200;
  if (path == "/health") {
    *body = "OK\n";
    return true;
  }
  if (path == "/version") {
    *body = "tpu-rpc/0.2.0\n";
    return true;
  }
  if (path == "/vars" || path == "/vars/") {
    const std::string* fmt = req.query("format");
    if (fmt != nullptr && *fmt == "json") {
      Json j = Json::object();
      for (auto& [name, value] : Variable::dump_exposed()) {
        double num = 0;
        if (parse_plain_number(value.c_str(), &num)) {
          j.set(name, Json::number(num));
        } else {
          j.set(name, Json::str(value));
        }
      }
      *body = j.dump();
      *content_type = "application/json";
      return true;
    }
    std::string out;
    for (auto& [name, value] : Variable::dump_exposed()) {
      out += name + " : " + value + "\n";
    }
    *body = std::move(out);
    return true;
  }
  if (path.rfind("/vars/", 0) == 0) {  // single variable
    const std::string want = path.substr(6);
    for (auto& [name, value] : Variable::dump_exposed()) {
      if (name == want) {
        *body = name + " : " + value + "\n";
        return true;
      }
    }
    *status = 404;
    *body = "no such var: " + want + "\n";
    return true;
  }
  if (path == "/status") {
    const std::string* fmt = req.query("format");
    if (fmt != nullptr && *fmt == "json") {
      Json j = Json::object();
      j.set("port", Json::number(srv->port()));
      j.set("uptime_s",
            Json::number((monotonic_time_us() - srv->start_time_us()) /
                         1000000.0));
      j.set("requests_served",
            Json::number(static_cast<double>(srv->requests_served.load())));
      j.set("in_flight", Json::number(srv->in_flight.load()));
      Json methods = Json::array();
      srv->for_each_method([&methods](const std::string& name) {
        methods.push_back(Json::str(name));
      });
      j.set("methods", std::move(methods));
      *body = j.dump();
      *content_type = "application/json";
      return true;
    }
    const int64_t up_us = monotonic_time_us() - srv->start_time_us();
    std::string out = "server port " + std::to_string(srv->port()) +
                      "\nuptime_s " + std::to_string(up_us / 1000000) +
                      "\nrequests_served " +
                      std::to_string(srv->requests_served.load()) +
                      "\nin_flight " + std::to_string(srv->in_flight.load()) +
                      "\nmethods:\n";
    srv->for_each_method(
        [&out](const std::string& name) { out += "  " + name + "\n"; });
    *body = std::move(out);
    return true;
  }
  if (path == "/brpc_metrics" || path == "/metrics") {
    *body = Variable::dump_prometheus();
    return true;
  }
  if (path == "/connections") {
    *body = "live_sockets " +
            std::to_string(g_socket_count.load(std::memory_order_relaxed)) +
            "\n";
    return true;
  }
  // ---- round-2 additions -------------------------------------------------
  if (path == "/flags" || path == "/flags/") {
    // ?format=json serves the introspection dump the tuner and tools
    // consume: {name, type, value, default, reloadable, min?, max?} —
    // bounds from the declared validators (base/flags.h set_int_range),
    // same body as trpc_flags_dump / observe.py flags().
    const std::string* fmt = req.query("format");
    if (fmt != nullptr && *fmt == "json") {
      *body = Flag::dump_json();
      *content_type = "application/json";
      return true;
    }
    *body = flags_text();
    return true;
  }
  if (path.rfind("/flags/", 0) == 0) {
    const std::string name = path.substr(7);
    Flag* f = Flag::find(name);
    if (f == nullptr) {
      *status = 404;
      *body = "no such flag: " + name + "\n";
      return true;
    }
    // ?setvalue=v mutates (reference: /flags/<name>?setvalue=... with a
    // registered validator making the flip safe).
    const std::string* setv = req.query("setvalue");
    if (setv != nullptr) {
      const int rc = f->set_from_string(*setv);
      if (rc == 0) {
        *body = name + " = " + f->value_string() + "\n";
      } else {
        *status = rc == -3 ? 403 : 400;
        *body = (rc == -3 ? std::string("flag is immutable: ")
                          : std::string("bad value for ")) +
                name + "\n";
      }
      return true;
    }
    *body = name + " = " + f->value_string() + "  # " + f->description() +
            "\n";
    return true;
  }
  if (path == "/faults") {
    // Live fault-injection control (net/fault.h).  ?set=<spec> installs
    // the process-wide TRANSPORT schedule (via the fault_schedule flag,
    // so /flags stays in sync); ?server=<spec> installs THIS server's
    // dispatch/accept schedule; ?reset=1 restarts both deterministic
    // sequences (counters + logs; schedules kept).  GET renders state +
    // the injected-fault log.
    fault_register_flag();
    // Validate BOTH specs before applying EITHER: a 400 must mean
    // "nothing changed", never "half the request armed".
    const std::string* setv = req.query("set");
    const std::string* srvv = req.query("server");
    if (setv != nullptr && !FaultActor::global().parse_ok(*setv)) {
      *status = 400;
      *body = "bad fault schedule: " + *setv + "\n";
      return true;
    }
    if (srvv != nullptr && !srv->faults().parse_ok(*srvv)) {
      *status = 400;
      *body = "bad server fault schedule: " + *srvv + "\n";
      return true;
    }
    if (setv != nullptr && Flag::set("fault_schedule", *setv) != 0) {
      *status = 400;
      *body = "bad fault schedule: " + *setv + "\n";
      return true;
    }
    if (srvv != nullptr && srv->SetFaults(*srvv) != 0) {
      *status = 400;
      *body = "bad server fault schedule: " + *srvv + "\n";
      return true;
    }
    const std::string* rst = req.query("reset");
    if (rst != nullptr && *rst != "0") {
      FaultActor::global().reset_counters();
      srv->faults().reset_counters();
    }
    FaultActor& g = FaultActor::global();
    *body = "transport_schedule " + (g.active() ? g.spec() : "(off)") +
            "\ntransport_decisions " + std::to_string(g.decisions()) +
            "\ntransport_injected " + std::to_string(g.injected()) +
            "\nserver_schedule " +
            (srv->faults().active() ? srv->faults().spec() : "(off)") +
            "\nserver_decisions " +
            std::to_string(srv->faults().decisions()) +
            "\nserver_injected " +
            std::to_string(srv->faults().injected()) + "\nlog:\n" +
            g.log_text() + srv->faults().log_text();
    return true;
  }
  if (path == "/rpcz") {
    uint64_t want_trace = 0;
    const std::string* tq = req.query("trace_id");
    if (tq != nullptr) {
      want_trace = strtoull(tq->c_str(), nullptr, 16);
    }
    const std::string* fmt = req.query("format");
    if (fmt != nullptr && *fmt == "json") {
      // Structured spans for tools/trace_stitch.py (and anything else
      // programmatic).  Served even while collection is off: the ring
      // may hold spans from an earlier enabled window, and a stitcher
      // fanning out to N nodes needs a parseable body from each.
      // Capped well below the max ring size: recent_spans deep-copies
      // under the same mutex submit_span takes on every RPC completion,
      // so an unbounded dump would stall live traffic from the very
      // tool meant to debug it.
      size_t limit = 200;
      const std::string* lq = req.query("limit");
      if (lq != nullptr) {
        const long v = atol(lq->c_str());
        if (v > 0 && v <= (1 << 16)) {
          limit = static_cast<size_t>(v);
        }
      }
      *body = rpcz_dump_json(limit, want_trace);
      *content_type = "application/json";
      return true;
    }
    if (!rpcz_enabled()) {
      *body =
          "rpcz is off; enable with /flags/rpcz_enabled?setvalue=true\n";
      return true;
    }
    char line[512];
    std::string out =
        "trace_id         span_id          parent           side   latency_us"
        " err  method (annotations)\n";
    for (const Span& s : recent_spans(200, want_trace)) {
      snprintf(line, sizeof(line),
               "%016llx %016llx %016llx %-6s %10lld %4d  %s",
               static_cast<unsigned long long>(s.trace_id),
               static_cast<unsigned long long>(s.span_id),
               static_cast<unsigned long long>(s.parent_span_id),
               s.server_side ? "server" : "client",
               static_cast<long long>(s.end_us - s.start_us), s.error_code,
               s.method.c_str());
      out += line;
      for (const auto& [ts, text] : s.annotations) {
        snprintf(line, sizeof(line), " [+%lldus %s]",
                 static_cast<long long>(ts - s.start_us), text.c_str());
        out += line;
      }
      out += "\n";
    }
    *body = std::move(out);
    return true;
  }
  if (path == "/hotspots") {
    // CPU profile: SIGPROF sampling for ?seconds=N (default 2, cap 30),
    // rendered as a flat symbolized profile (hotspots_service parity).
    int seconds = 2;
    const std::string* sq = req.query("seconds");
    if (sq != nullptr) {
      seconds = atoi(sq->c_str());
    }
    if (seconds < 1) {
      seconds = 1;
    }
    if (seconds > 30) {
      seconds = 30;
    }
    *body = profile_cpu_for(seconds);
    return true;
  }
  if (path == "/contention") {
    *body = contention_dump();
    return true;
  }
  if (path == "/capture") {
    // Traffic capture (stat/capture.h): arrival-process summary +
    // per-tenant baseline over the records held while the reloadable
    // trpc_capture flag was on (flip it via
    // /flags/trpc_capture?setvalue=true).  Served even while capture is
    // off — the reservoir may hold an earlier enabled window.
    // ?records=N embeds the newest N records (max 65536);
    // ?dump=<path> writes the binary capture file server-side and
    // answers {"dumped": N}; ?reset=1 clears the window.
    const std::string* dq = req.query("dump");
    if (dq != nullptr && !dq->empty()) {
      const int64_t n = capture::dump_file(*dq);
      if (n < 0) {
        *status = 500;
        *body = "cannot write " + *dq + "\n";
        return true;
      }
      *body = "{\"dumped\": " + std::to_string(n) + "}";
      *content_type = "application/json";
      return true;
    }
    const std::string* rq = req.query("reset");
    if (rq != nullptr && *rq == "1") {
      capture::reset();
      *body = "{\"reset\": true}";
      *content_type = "application/json";
      return true;
    }
    size_t records = 0;
    const std::string* nq = req.query("records");
    if (nq != nullptr) {
      const long v = atol(nq->c_str());
      if (v > 0) {
        records = std::min(static_cast<size_t>(v),
                           static_cast<size_t>(1 << 16));
      }
    }
    *body = capture::dump_json(records);
    *content_type = "application/json";
    return true;
  }
  if (path == "/tuner") {
    // Self-tuning controller (stat/tuner.h): status, live rule table,
    // sampled inputs and the structured decision journal, recorded
    // while the reloadable trpc_tuner flag is on (flip it via
    // /flags/trpc_tuner?setvalue=true).  Served even while tuning is
    // off — the journal may hold decisions from an earlier enabled
    // window.  ?limit=N caps journal entries (default 128, max 512).
    size_t limit = 128;
    const std::string* lq = req.query("limit");
    if (lq != nullptr) {
      const long v = atol(lq->c_str());
      if (v > 0) {
        limit = std::min(static_cast<size_t>(v),
                         static_cast<size_t>(512));
      }
    }
    *body = tuner::dump_json(limit);
    *content_type = "application/json";
    return true;
  }
  if (path == "/slo") {
    // Per-tenant SLO attainment + burn rates (stat/slo.h), recorded
    // while the reloadable trpc_slo flag is on.  Served even with no
    // engine installed — the shape stays machine-readable either way.
    auto slo = srv != nullptr ? srv->slo_engine() : nullptr;
    if (slo != nullptr) {
      *body = slo->dump_json();
    } else {
      *body = std::string("{\"enabled\":") +
              (slo::enabled() ? "true" : "false") +
              ",\"tenants\":[]}";
    }
    *content_type = "application/json";
    return true;
  }
  if (path == "/fleet") {
    // Fleet-wide merged view over the LOCAL naming registry: per-tenant
    // rate/p50/p99/error-rate/budget-remaining/burn-rate from merged
    // digests (octave-wise sample pooling — never averaged node p99s).
    // ?service=<name> selects the service (default "fleet").
    const std::string* sq = req.query("service");
    *body = fleet_dump_json(sq != nullptr ? *sq : "fleet");
    *content_type = "application/json";
    return true;
  }
  if (path == "/analysis") {
    // Runtime invariant checkers (fiber/analysis.h): lock-order
    // inversions + blocking-in-dispatch violations recorded while the
    // reloadable trpc_analysis flag is on (flip it via
    // /flags/trpc_analysis?setvalue=true).
    *body = analysis::report();
    return true;
  }
  if (path == "/pprof/profile") {
    // gperftools-protocol CPU profile: external pprof tooling attaches
    // with `pprof http://host:port/pprof/profile` (pprof_service.h:26).
    int seconds = 10;
    const std::string* sq = req.query("seconds");
    if (sq != nullptr) {
      seconds = atoi(sq->c_str());
    }
    seconds = std::min(std::max(seconds, 1), 60);
    *body = profile_cpu_pprof(seconds);
    if (body->empty()) {
      *status = 503;
      *body = "another profile is already running\n";
      return true;
    }
    *content_type = "application/octet-stream";
    return true;
  }
  if (path == "/pprof/symbol") {
    // GET: capability probe.  POST: "0xA+0xB" → "0xA\tname" lines.
    if (req.verb == "POST") {
      *body = pprof_symbolize_post(payload.to_string());
    } else {
      *body = "num_symbols: 1\n";
    }
    return true;
  }
  if (path == "/pprof/cmdline") {
    FILE* f = fopen("/proc/self/cmdline", "r");
    if (f != nullptr) {
      char buf[4096];
      const size_t n = fread(buf, 1, sizeof(buf), f);
      fclose(f);
      for (size_t i = 0; i < n; ++i) {
        body->push_back(buf[i] == '\0' ? '\n' : buf[i]);
      }
    }
    return true;
  }
  if (path == "/pprof/heap") {
    // First call enables the sampler (no tcmalloc in the image — the
    // runtime's own new/delete sampler, heap_profiler.h); later calls
    // dump the live profile accumulated since.
    if (!heap_profiler_running()) {
      heap_profiler_start();
      *body =
          "heap sampling enabled; re-query after the workload to get the "
          "live profile\n";
      return true;
    }
    *body = heap_profiler_dump();
    return true;
  }
  if (path == "/fibers" || path == "/bthreads") {
    // ?stacks=1 additionally unwinds each parked fiber's suspension
    // point (TaskTracer parity: where a stuck fiber IS, not just its
    // entry symbol).
    const std::string* sv = req.query("stacks");
    *body = fiber_dump_all(200, sv != nullptr && *sv != "0");
    return true;
  }
  if (path == "/threads") {
    *body = "fiber_workers " + std::to_string(fiber_worker_count()) +
            "\nos_threads " + std::to_string(proc_status_kb("Threads:")) +
            "\n";
    // Per-tag worker groups (bthread_tag parity), provisioned tags only.
    for (int t = 1; t < kMaxFiberTags; ++t) {
      const int n = fiber_worker_count_tag(t);
      if (n > 0) {
        *body += "fiber_workers_tag" + std::to_string(t) + " " +
                 std::to_string(n) + "\n";
      }
    }
    return true;
  }
  if (path == "/memory") {
    *body = "vm_rss_kb " + std::to_string(proc_status_kb("VmRSS:")) +
            "\nvm_size_kb " + std::to_string(proc_status_kb("VmSize:")) +
            "\nvm_hwm_kb " + std::to_string(proc_status_kb("VmHWM:")) + "\n";
    return true;
  }
  if (path == "/list" || path == "/protobufs") {
    // Method inventory (the pb-less analogue of /protobufs).
    std::string out;
    srv->for_each_method(
        [&out](const std::string& name) { out += name + "\n"; });
    *body = std::move(out);
    return true;
  }
  if (path == "/sockets") {
    *body = Socket::DumpAll(500);
    // ?hot=1 appends per-socket hot-path state (queued-write flag,
    // writer role, pending events) — the wedge-forensics view.
    const std::string* hot = req.query("hot");
    if (hot != nullptr && *hot == "1") {
      *body += "\n" + Socket::DumpHotState();
    }
    return true;
  }
  if (path == "/ids") {
    *body = fid_dump_all(500);
    return true;
  }
  if (path == "/vlog") {
    // The reference's /vlog lists VLOG sites; the analogue here is the
    // runtime log threshold, flippable like /flags (?setlevel=0..4).
    static const char* kNames[] = {"debug", "info", "warning", "error",
                                   "fatal"};
    const std::string* lv = req.query("setlevel");
    if (lv != nullptr) {
      char* end = nullptr;
      const long v = strtol(lv->c_str(), &end, 10);
      if (end == lv->c_str() || *end != '\0' || v < 0 || v > 4) {
        *status = 400;
        *body = "setlevel must be 0(debug)..4(fatal)\n";
        return true;
      }
      log_min_level().store(static_cast<int>(v),
                            std::memory_order_relaxed);
    }
    const int cur = log_min_level().load(std::memory_order_relaxed);
    *body = "min_log_level " + std::to_string(cur) + " (" +
            kNames[cur < 0 || cur > 4 ? 1 : cur] + ")\n";
    return true;
  }
  if (path == "/dir" || path.rfind("/dir/", 0) == 0) {
    // Filesystem browser.  Opt-in like the reference (DirService only
    // registers behind -enable_dir_service, server.cpp:119, default
    // false) because it serves ANY path; flip live via
    // /flags/enable_dir_service?setvalue=true.
    if (!dir_service_flag->bool_value()) {
      *status = 403;
      *body =
          "disabled; enable with /flags/enable_dir_service?setvalue=true\n";
      return true;
    }
    std::string target =
        path.size() > 4 ? path.substr(4) : std::string("/");
    std::error_code ec;
    if (std::filesystem::is_directory(target, ec)) {
      std::string out;
      std::vector<std::string> rows;
      for (const auto& entry :
           std::filesystem::directory_iterator(target, ec)) {
        std::string row = entry.path().filename().string();
        if (entry.is_directory(ec)) {
          row += "/";
        } else {
          std::error_code size_ec;
          const auto sz = entry.file_size(size_ec);
          // Dangling symlinks / proc pseudo-files have no stat-able
          // size; print "?" instead of uintmax_t(-1).
          row += size_ec ? "  ?" : "  " + std::to_string(sz);
        }
        rows.push_back(std::move(row));
      }
      std::sort(rows.begin(), rows.end());
      for (const auto& r : rows) {
        out += r + "\n";
      }
      *body = std::move(out);
    } else if (std::filesystem::is_regular_file(target, ec)) {
      FILE* f = fopen(target.c_str(), "rb");
      if (f == nullptr) {
        *status = 403;
        *body = "cannot open " + target + "\n";
        return true;
      }
      char buf[8192];
      size_t n;
      constexpr size_t kMaxFile = 4u << 20;
      while ((n = fread(buf, 1, sizeof(buf), f)) > 0) {
        body->append(buf, n);
        if (body->size() > kMaxFile) {
          body->resize(kMaxFile);
          body->append("\n... (truncated at 4MB)\n");
          break;
        }
      }
      fclose(f);
      *content_type = "application/octet-stream";
    } else {
      *status = 404;
      *body = "no such path: " + target + "\n";
    }
    return true;
  }
  if (path == "/index" || path == "/") {
    *body =
        "/health\n/version\n/status\n/vars\n/vars/<name>\n/brpc_metrics\n"
        "/connections\n/flags[?format=json]\n/flags/<name>[?setvalue=v]\n"
        "/threads\n"
        "/memory\n/list\n/protobufs\n/index\n"
        "/rpcz[?trace_id=hex&format=json&limit=N]\n"
        "/capture[?records=N&dump=path&reset=1]\n"
        "/tuner[?limit=N]\n"
        "/slo\n/fleet[?service=name]\n"
        "/faults[?set=spec&server=spec&reset=1]\n"
        "/hotspots[?seconds=N]\n/contention\n/analysis\n/fibers\n"
        "/sockets\n/ids\n"
        "/vlog[?setlevel=N]\n/dir/<path>\n"
        "/pprof/profile[?seconds=N]\n/pprof/symbol\n/pprof/cmdline\n"
        "/pprof/heap\n";
    return true;
  }
  (void)content_type;
  return false;
}

}  // namespace trpc
