// Process-level default variables (parity: bvar/default_variables.cpp —
// cpu, rss, fds, threads read from /proc and exposed in every /vars dump).
#include <stdio.h>
#include <string.h>
#include <sys/resource.h>
#include <unistd.h>

#include <atomic>
#include <memory>

#include "base/proc.h"
#include "base/time.h"
#include "stat/variable.h"

namespace trpc {

namespace {

// CPU: utime+stime deltas from /proc/self/stat, reported as percent of one
// core over the interval since the previous dump (pull-based).
double cpu_percent() {
  // Atomics: concurrent dumps (/vars + a metrics scrape) race otherwise.
  static std::atomic<long> last_ticks{0};
  static std::atomic<int64_t> last_us{0};
  FILE* f = fopen("/proc/self/stat", "r");
  if (f == nullptr) {
    return 0.0;
  }
  long utime = 0;
  long stime = 0;
  // Field 2 (comm) may contain spaces; skip to the closing paren.
  char buf[1024];
  if (fgets(buf, sizeof(buf), f) != nullptr) {
    const char* p = strrchr(buf, ')');
    if (p != nullptr) {
      // fields 3..15: state ppid pgrp session tty tpgid flags minflt
      // cminflt majflt cmajflt utime stime
      sscanf(p + 2, "%*c %*d %*d %*d %*d %*d %*u %*u %*u %*u %*u %ld %ld",
             &utime, &stime);
    }
  }
  fclose(f);
  const long ticks = utime + stime;
  const int64_t now = monotonic_time_us();
  const long prev_ticks = last_ticks.exchange(ticks);
  const int64_t prev_us = last_us.exchange(now);
  double pct = 0.0;
  if (prev_us != 0 && now > prev_us) {
    const double dt_s = (now - prev_us) / 1e6;
    const long hz = sysconf(_SC_CLK_TCK);
    pct = 100.0 * (ticks - prev_ticks) / (hz > 0 ? hz : 100) / dt_s;
  }
  return pct;
}

// Pages the process touched for the first time (or again after the kernel
// took them back) that needed no I/O: what a fresh host buffer costs.
long faults_minor() {
  struct rusage ru;
  return getrusage(RUSAGE_SELF, &ru) == 0 ? ru.ru_minflt : 0;
}

struct DefaultVars {
  PassiveStatus<long> rss{[] { return proc_status_kb("VmRSS:"); }};
  PassiveStatus<long> vsz{[] { return proc_status_kb("VmSize:"); }};
  PassiveStatus<long> threads{[] { return proc_status_kb("Threads:"); }};
  PassiveStatus<long> fds{[] { return proc_fd_count(); }};
  PassiveStatus<double> cpu{[] { return cpu_percent(); }};
  PassiveStatus<long> faults{[] { return faults_minor(); }};
  PassiveStatus<long> io_uring{
      [] { return static_cast<long>(kernel_supports("io_uring")); }};

  DefaultVars() {
    rss.expose("process_memory_rss_kb", "resident set size (VmRSS)");
    vsz.expose("process_memory_vsz_kb", "virtual size (VmSize)");
    threads.expose("process_threads", "OS thread count");
    fds.expose("process_fd_count", "open file descriptors");
    cpu.expose("process_cpu_percent",
               "CPU use since the previous dump, percent of one core");
    faults.expose("process_faults_minor",
                  "minor page faults of the process since it started");
    io_uring.expose(
        "kernel_io_uring_supported",
        "1 when the running kernel answers io_uring_setup (>= 5.1); 0 "
        "when it returns ENOSYS — the runtime capability gate for the "
        "ROADMAP io_uring data-plane backend");
  }
};

}  // namespace

// Called once from Server::Start (cheap, idempotent) so every serving
// process exports its process vars like the reference does implicitly.
void expose_default_variables() {
  static DefaultVars* v = new DefaultVars();  // leaked with the registry
  (void)v;
}

}  // namespace trpc
