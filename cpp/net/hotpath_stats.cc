#include "net/hotpath_stats.h"

#include "fiber/fiber.h"
#include "stat/variable.h"

namespace trpc {

namespace {

// Bulk-wake counters live in the fiber layer (scheduler.cc) because net/
// sits above fiber/; they surface here as pull-based vars so every
// hot-path series shares one /vars namespace.
struct BulkWakeVars {
  PassiveStatus<long> batches{[] {
    uint64_t b = 0, f = 0, m = 0;
    fiber_bulk_wake_stats(&b, &f, &m);
    return static_cast<long>(b);
  }};
  PassiveStatus<long> fibers{[] {
    uint64_t b = 0, f = 0, m = 0;
    fiber_bulk_wake_stats(&b, &f, &m);
    return static_cast<long>(f);
  }};
  PassiveStatus<long> max{[] {
    uint64_t b = 0, f = 0, m = 0;
    fiber_bulk_wake_stats(&b, &f, &m);
    return static_cast<long>(m);
  }};
};

}  // namespace

HotPathVars::HotPathVars() {
  write_coalesce_drains.expose(
      "socket_write_coalesce_drains",
      "write-queue drain sweeps (one coalesced writev each)");
  write_coalesce_nodes.expose(
      "socket_write_coalesce_nodes",
      "queued Writes absorbed into coalesced drains");
  write_coalesce_max.expose(
      "socket_write_coalesce_max",
      "high-water queued Writes absorbed by one drain");
  write_coalesce_batch.expose(
      "socket_write_coalesce_batch",
      "coalesced-drain batch size (1-in-16 sampled)");
  inline_write_attempts.expose(
      "socket_inline_write_attempts",
      "Socket::Write calls that tried the wait-free inline flush");
  inline_write_hits.expose(
      "socket_inline_write_hits",
      "inline flushes that drained the whole queue on the caller");
  dispatch_batches.expose(
      "messenger_dispatch_batches",
      "readable sweeps that cut at least one message");
  dispatch_msgs.expose("messenger_dispatch_messages",
                       "messages cut from readable sweeps");
  dispatch_inline.expose(
      "messenger_dispatch_inline",
      "messages run inline on the dispatch fiber (first-of-batch)");
  dispatch_max.expose("messenger_dispatch_max",
                      "high-water messages cut in one readable sweep");
  dispatch_batch.expose("messenger_dispatch_batch",
                        "dispatch batch size (1-in-16 sampled)");
  probe_rounds.expose("messenger_probe_rounds",
                      "full multi-protocol probe sweeps");
  probe_stall_skips.expose(
      "messenger_probe_stall_skips",
      "probe sweeps elided by the per-socket prefix-length memo");
  stripe_tx_chunks.expose(
      "stripe_tx_chunks",
      "large-message stripe chunk frames sent (heads included)");
  stripe_rx_chunks.expose(
      "stripe_rx_chunks",
      "large-message stripe chunk frames received (heads included)");
  stripe_tx_bytes.expose(
      "stripe_tx_bytes",
      "striped payload bytes sent (whole message bodies; the tuner's "
      "sender-side throughput signal for the stripe knobs)");
  stripe_rx_bytes.expose(
      "stripe_rx_bytes",
      "striped payload bytes landed at receivers (per-chunk sizes; the "
      "tuner's hill-climb target for stripe chunk/rail geometry)");
  stripe_reassembled.expose(
      "stripe_reassembled",
      "striped messages fully reassembled and dispatched");
  stripe_expired.expose(
      "stripe_expired",
      "stripe reassemblies dropped by timeout or abandonment");
  cut_budget_yields.expose(
      "messenger_cut_budget_yields",
      "read sweeps that yielded their worker after exhausting the "
      "per-sweep cut budget (bulk transfers sharing with small RPCs)");
  rma_tx_msgs.expose("rma_tx_msgs",
                     "one-sided transfers sent (control frames queued "
                     "after the last chunk write landed)");
  rma_tx_chunks.expose(
      "rma_tx_chunks",
      "chunks written one-sided into peer registered regions");
  rma_tx_bytes.expose("rma_tx_bytes",
                      "payload bytes moved by one-sided writes (no "
                      "ring/socket copy)");
  rma_rx_msgs.expose("rma_rx_msgs",
                     "rma control frames resolved into complete "
                     "payloads and dispatched");
  rma_window_full.expose(
      "rma_window_full",
      "one-sided sends that fell back to the striped copy path because "
      "no window span was free");
  rma_rejected.expose(
      "rma_rejected",
      "rma control frames dropped whole (incomplete completion bitmap, "
      "bad bounds, or an unknown/unbound region)");
  rma_direct_tx_bytes.expose(
      "rma_direct_tx_bytes",
      "of rma_tx_bytes, the response bytes put straight into the "
      "caller's bound landing range (no window span, no landing copy)");
  rma_direct_fallback.expose(
      "rma_direct_fallback",
      "landing binds refused because the range overlapped another live "
      "bind or the region's descriptor table was full: the call landed "
      "by the copy path");
}

HotPathVars& hotpath_vars() {
  // Leaked with the registry: worker threads outlive static destruction.
  static HotPathVars* v = new HotPathVars();
  return *v;
}

void expose_hotpath_variables() {
  hotpath_vars();
  static BulkWakeVars* bw = [] {
    auto* b = new BulkWakeVars();
    b->batches.expose("fiber_bulk_wake_batches",
                      "ready_to_run_batch publications (one ParkingLot "
                      "signal per batch)");
    b->fibers.expose("fiber_bulk_wake_fibers",
                     "fibers published through the bulk-wake path");
    b->max.expose("fiber_bulk_wake_max",
                  "largest single bulk-wake batch observed");
    return b;
  }();
  (void)bw;
}

}  // namespace trpc
