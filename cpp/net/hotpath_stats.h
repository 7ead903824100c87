// Hot-path instrumentation for the small-RPC fast path: write coalescing,
// inline writes, batched message dispatch and bulk fiber wakeups.
//
// Parity: the reference instruments the same seams with bvars
// (socket.cpp's "connection_count"-family and input_messenger's batch
// counters); here one struct owns every hot-path var so the builtin /vars
// endpoint shows the whole picture at once.  Counters are thread-local-
// combining Adders (one relaxed CAS per event); the batch-size histograms
// go through LatencyRecorder (octave percentile sketch) on a 1-in-16
// sample so the recorder mutex stays off the hot path.
#pragma once

#include <cstdint>

#include "stat/latency_recorder.h"
#include "stat/reducer.h"

namespace trpc {

struct HotPathVars {
  // Write side: one "drain" = one KeepWrite/inline sweep of the MPSC
  // write queue into a single coalesced buffer (→ one writev/doorbell).
  Adder write_coalesce_drains;
  Adder write_coalesce_nodes;   // queued Writes absorbed by those drains
  Maxer write_coalesce_max;     // high-water nodes in one drain
  LatencyRecorder write_coalesce_batch;  // sampled batch-size quantiles

  // Inline-write fast path: Socket::Write flushed the whole queue on the
  // caller, no KeepWrite fiber, no wakeup.  hit/attempt = how often the
  // small-RPC path stays wait-free.
  Adder inline_write_attempts;
  Adder inline_write_hits;

  // Read side: one "batch" = the messages cut from one readable sweep;
  // the first runs inline on the dispatch fiber, the rest bulk-enqueue.
  Adder dispatch_batches;
  Adder dispatch_msgs;
  Adder dispatch_inline;        // messages run inline (first-of-batch)
  Maxer dispatch_max;
  LatencyRecorder dispatch_batch;  // sampled batch-size quantiles

  // Protocol probing: rounds = full multi-protocol probe sweeps,
  // stall_skips = sweeps elided because no new bytes arrived since the
  // last inconclusive probe (the per-socket prefix-length memo).
  Adder probe_rounds;
  Adder probe_stall_skips;

  // Large-message striping (net/stripe.h).  All four stay EXACTLY zero
  // on sub-threshold traffic — that invariant is what proves small RPCs
  // bypass the stripe layer entirely.
  Adder stripe_tx_chunks;    // chunk frames sent (head included)
  Adder stripe_rx_chunks;    // chunk frames received (head included)
  Adder stripe_tx_bytes;     // striped payload bytes sent (whole bodies)
  Adder stripe_rx_bytes;     // striped payload bytes landed (chunk sizes)
  Adder stripe_reassembled;  // messages fully reassembled and dispatched
  Adder stripe_expired;      // reassemblies dropped by timeout/abandon

  // Read sweeps that yielded mid-drain (trpc_messenger_cut_budget): how
  // often a bulk transfer handed its worker back to small-RPC dispatch.
  Adder cut_budget_yields;

  // One-sided RMA plane (net/rma.h).  Like the stripe vars, every one
  // of these stays EXACTLY zero on sub-threshold traffic with no bound
  // landing — the proof that small RPCs never touch the rma layer.
  Adder rma_tx_msgs;      // one-sided transfers sent (control frames)
  Adder rma_tx_chunks;    // chunks written into peer regions
  Adder rma_tx_bytes;     // payload bytes moved one-sided
  Adder rma_rx_msgs;      // control frames resolved and dispatched
  Adder rma_window_full;  // sends that fell back (no window span free)
  Adder rma_rejected;     // control frames dropped whole (incomplete
                          // bitmap, bad bounds, unknown region)
  Adder rma_direct_tx_bytes;  // of rma_tx_bytes, put into a caller's
                              // bound landing range
  Adder rma_direct_fallback;  // binds refused (range overlaps a live
                              // bind, or no free descriptor): copy path

  HotPathVars();
};

// Process-wide instance (registered in /vars on first use).
HotPathVars& hotpath_vars();

// Idempotent: force registration so /vars shows the zeroed series even
// before traffic (called from Server::Start like the process vars).
void expose_hotpath_variables();

// 1-in-N sampling helper for the histogram recorders (TLS counter).
inline bool hotpath_sample16() {
  static thread_local uint32_t n = 0;
  return (++n & 15u) == 0;
}

}  // namespace trpc
