// One-sided RMA plane — registered-memory put with completion bitmaps.
//
// Parity: brpc's RDMA one-sided verbs (rdma/rdma_endpoint + block_pool
// RegisterMemory) and fabric-lib's (arXiv 2510.27656) transfer engine:
// large payloads are WRITTEN by the sender straight into memory the
// receiver registered in advance, and the byte-stream transport carries
// only a tiny completion control message.  "RPC Considered Harmful"
// (arXiv 1805.08430) names the defect this removes: receiver-side copy
// orchestration — the shm path used to move one 64MB body through THREE
// memcpys (producer→ring, ring→IOBuf, IOBuf→landing block); the rma path
// moves it through ONE (sender→registered region), fanned out over
// parallel rail fibers, when the receiver reads it where it landed (a
// server handler's request, a response into a caller's RmaBuffer).  A
// response for a PRIVATE caller buffer (batch plane, plain memory) is
// TWO: sender→connection window, then window→caller buffer (rma_land),
// each cut over the same rails.
//
// Model:
//  - A REGION is pinned memory under an rkey.  Exportable regions are
//    shm-backed (rma_alloc) and carry a fixed header: the peer maps
//    /trpc_rma_<pid>_<ordinal> and writes at offset.  rma_reg pins
//    arbitrary caller memory locally (no export — such regions can be
//    landing targets for the receiver-side copy path only).
//  - Every rma-capable connection (shm rings, ici rings — Transport::rma)
//    owns a WINDOW: an exportable region whose data area is a 64-slot
//    arena the PEER allocates spans from (CAS on a slot bitmap shared in
//    the region header; the receiver frees slots when the payload's last
//    IOBuf reference drops — end-to-end backpressure, window-full sends
//    fall back to the striped copy path).
//  - A transfer cuts the body into chunks written CONCURRENTLY by
//    trpc_{shm,ici}_rails rail fibers (per-rail FIFO: each rail owns a
//    contiguous chunk range written in order).  Each chunk write is
//    followed by a release-fenced bit set in the span's chunk bitmap, and
//    the control message is sent only after every rail joined — so a
//    receiver that observes the control frame either finds EVERY bit set
//    (acquire loads) and takes the whole payload, or drops the message
//    whole.  Torn reads are impossible; faulted (dropped/truncated)
//    chunks leave their bit clear and fail the CALL whole-or-nothing.
//  - The batch plane's registered resp_bufs become genuine remote-write
//    targets: when a caller's landing buffer lives in an rma_alloc'd
//    region and is at least kRmaDirectFloor bytes, the REQUEST advertises
//    {rkey, cap, offset, descriptor} (meta tail-group 6) and the server
//    puts the response straight into the caller's buffer (control offset
//    kRmaDirectOff; completion bitmap in the descriptor the call holds in
//    the region header's table), with zero receiver-side copies.  Every
//    in-flight call holds its own descriptor, so a window of calls into
//    disjoint ranges of one region all land in place.
#pragma once

#include <atomic>
#include <cstddef>
#include <cstdint>
#include <memory>
#include <mutex>

#include "base/iobuf.h"
#include "net/deadline.h"
#include "net/protocol.h"

namespace trpc {

class Socket;

// Control-frame rma_off value meaning "the payload landed at the bound
// offset of the named region's data area, completion bitmap in the
// bind's descriptor of the REGION header" (the direct-to-caller-buffer
// path).  Window spans use their byte offset inside the window's data
// area instead.
constexpr uint64_t kRmaDirectOff = UINT64_MAX;

// Smallest response put straight into a caller's bound landing range
// over a connection with a one-sided session; under it the response
// rides one frame of the ring and is copied out.  Direct beats the
// in-band frame on the shm ring at every size tools/rma_floor_probe.py
// measured, from 147,456 B up on a TPU v5e host and from 4 KB up on a
// build host (PERF.md §7); the floor is one minimum rma chunk, which
// keeps the descriptor table of a region (one a floor of data) small.
constexpr uint64_t kRmaDirectFloor = 65536;

// Refcounted mapping of one region's shm object.  The registry, peer
// caches and every wrapped-payload consumer co-own it: neither a dying
// connection nor rma_free can munmap under a live reader.
struct RmaMapping {
  char* base = nullptr;
  size_t len = 0;
  bool owned = false;  // false: alias of another mapping (never unmapped)
  ~RmaMapping();
};

// Per-connection one-sided state, returned by Transport::rma().  The
// owning conn (ShmConn / IciConn) creates it at establishment, publishes
// local_rkey in the shared segment, and points peer_rkey_slot at the
// segment word where the PEER publishes its window.
struct RmaSession {
  uint64_t local_rkey = 0;  // our receive window (we own the region)
  // Segment word the peer publishes its window rkey into; acquire-read
  // at first send (may still be 0 while the peer bootstraps).
  std::atomic<uint64_t>* peer_rkey_slot = nullptr;

  // Lazily-resolved peer window (sender side), guarded by mu.  The
  // geometry is a TRUSTED snapshot validated at map time (the live
  // header is peer-writable; see rma.cc RmaGeom).
  std::mutex mu;
  uint64_t peer_rkey = 0;
  std::shared_ptr<RmaMapping> peer_map;
  uint64_t peer_data_len = 0;
  uint32_t peer_data_off = 0;
  uint32_t peer_slot_bytes = 0;
  uint32_t peer_nslots = 0;

  ~RmaSession();  // releases the local window region (deferred munmap)
};

// Creates a session with a fresh local window region sized by the
// reloadable trpc_rma_window_bytes flag.  nullptr when the flag is 0
// (one-sided plane disabled) or the region could not be created — the
// connection then simply has no rma capability.
std::shared_ptr<RmaSession> rma_session_create();

// -- region registry -------------------------------------------------------

// Allocates an exportable (shm-backed) region and returns its DATA
// pointer (len usable bytes, page-aligned); *rkey_out names it for peers.
// Its header holds a table of one completion descriptor per
// kRmaDirectFloor of data (at least 1, at most 1024): as many calls may
// land in it at once.  nullptr on failure.
void* rma_alloc(size_t len, uint64_t* rkey_out);
// Unlinks the shm name and drops the registry reference; the munmap is
// deferred by the mapping refcount until the last wrapped-payload
// consumer drops (use-after-free guard).  `data` is the rma_alloc return.
void rma_free(void* data);
// Pins arbitrary caller memory under an rkey (local-only: not peer-
// mappable; landing lookups resolve it, remote puts cannot target it).
// Returns 0 on failure.
uint64_t rma_reg(const void* buf, size_t len);
// Unpins.  Returns 0, or -1 when the rkey is unknown.
int rma_unreg(uint64_t rkey);
// True (filling *rkey/*off) when [buf, buf+len) lies inside one live
// EXPORTABLE region's data area.
bool rma_exportable(const void* buf, size_t len, uint64_t* rkey,
                    uint64_t* off);
// Live regions (tests, /vars).
size_t rma_region_count();
// Window spans currently ALLOCATED across this process's receive
// windows (set bits in the shared slot bitmaps).  A peer's in-flight
// one-sided put holds its span until the payload's last IOBuf reference
// drops, so Server::Drain polls this to zero before tearing the process
// down — handing the listeners off while a span is live would let the
// successor's client observe a half-written window.
size_t rma_spans_in_use();
// Co-owning reference to the exportable region containing [buf, buf+len)
// (net/kvstore.h serves KV-block bytes zero-copy out of registered
// pages; the returned mapping refcount defers rma_free's munmap past
// any in-flight reader).  Fills *rkey/*off like rma_exportable.
// nullptr when the range is not inside one live exportable region.
std::shared_ptr<RmaMapping> rma_pin_exportable(const void* buf, size_t len,
                                               uint64_t* rkey,
                                               uint64_t* off);

// -- landing binds (batch plane) ------------------------------------------

// True when a body of n bytes may be put straight into a bound landing:
// at or over kRmaDirectFloor, or stripe-eligible (net/stripe.h).
bool rma_direct_eligible(uint64_t n);

// Binds cid → a free completion descriptor of the exportable region
// holding [buf, buf+cap) so the request can advertise the range as the
// response's remote-write target.  The buffer may sit at ANY offset
// inside the region's data area (collective pulls land shards
// mid-buffer; a KV window lands a record a row); the offset and the
// descriptor are recorded locally and advertised, and resolve trusts
// only the LOCAL record.  Any number of calls may be bound at once to
// disjoint ranges of one region, up to its descriptor table; a range
// bound again takes the descriptor it had last (so a late put of a
// timed-out call into it fails the new call's token check, whole).
// False, and the call lands by the copy path, when the buffer is not
// inside an exportable region, or — counted in rma_direct_fallback —
// when the range overlaps another live bind or no descriptor is free.
bool rma_landing_bind(uint64_t cid, void* buf, size_t cap);
// Frees cid's descriptor; completes an rma_free deferred by the bind.
void rma_landing_unbind(uint64_t cid);
// The bound rkey for cid (0 = none); *max_out = usable bytes,
// *off_out = byte offset of the landing inside the region's data area,
// *desc_out = the descriptor the call completes in.
uint64_t rma_landing_rkey(uint64_t cid, uint64_t* max_out,
                          uint64_t* off_out = nullptr,
                          uint32_t* desc_out = nullptr);

// -- send (channel.cc / server.cc) ----------------------------------------

// The client's half of a direct landing: when the socket has an rma
// session, binds cid's landing buffer (rma_landing_bind) and stamps
// meta's response-advertisement fields (tail-group 6: rkey, cap, and in
// rma_resp_off the offset below bit 48, the descriptor + 1 above it).
// True when bound — the caller then rma_landing_unbind(cid)s before the
// call's id recycles.  No lookup at all without a session (tcp).
bool rma_bind_response(SocketId sid, uint64_t cid, void* buf, size_t cap,
                       RpcMeta* meta);

// Attempts the one-sided path for meta+body on `primary`: a request, a
// response, or a stream's DATA frame (net/stream.cc; its correlation_id
// is the transfer's token, as a call's is).
//   0  sent: body consumed, chunks written into the peer region, control
//      frame queued on the primary socket.
//   1  not applicable (below threshold with no usable bound landing, no
//      session, descriptor path preferred, window full): body untouched
//      — caller falls back to the stripe/frame path.
//  -1  hard failure (control write failed / fault reset): the call fails.
// target_rkey (from the request's advertisement) routes a response
// direct-to-region when the body fits target_max and is direct-eligible
// — written at the advertised offset into the region's data area,
// completing in the advertised descriptor (target_off as
// rma_bind_response encodes it); otherwise, over the threshold, the
// connection window is used.
// tok (net/deadline.h): the rail writers poll it between chunks — a
// cancelled request / expired budget stops the transfer within one
// chunk (remaining chunks never written, their bits never set, the
// control frame never sent, so the receiver's whole-or-nothing admit
// drops nothing partial; an abandoned window span is reclaimed by the
// scavenger).  Cancelled sends return -1.
int rma_try_send(SocketId primary, RpcMeta* meta, IOBuf* body,
                 uint64_t target_rkey, uint64_t target_max,
                 uint64_t target_off = 0,
                 const DeadlineToken& tok = DeadlineToken{});

// -- receive (messenger hook) ---------------------------------------------

// Resolves an rma control frame IN PLACE: validates the named region
// against the socket's session (or the cid's landing bind), checks the
// release-fenced completion bitmap and per-chunk CRCs, and swaps the
// out-of-band payload into msg->payload (window spans wrap zero-copy
// with a slot-freeing deleter; direct transfers wrap the caller's own
// buffer).  False: drop the message whole — the call times out (the
// messenger closes a stream whose chunk this was), no partial bytes ever
// dispatch.
bool rma_resolve(InputMessage* msg, Socket* sock);

// Rails configured for a mode (trpc_shm_rails / trpc_ici_rails).
int rma_rails_for(int socket_mode);

// Copies the first n bytes of a received payload into dst (n <=
// resp.size()): the batch plane's landing copy into a caller buffer that
// is not in place.  A window span (rma_resolve's zero-copy wrap) of more
// than one chunk is cut over its connection's rails the way the sender
// cut the put — contiguous chunk ranges, all but the last on their own
// fibers, a bounded join — so it returns only when every byte is in dst;
// anything else is one copy_to on the caller.  Returns the rails the copy
// ran on (1: the caller alone).  Fiber- and pthread-safe.
uint32_t rma_land(const IOBuf& resp, void* dst, size_t n);

// -- span scavenger --------------------------------------------------------

// Reclaims receive-window slots whose control frame never arrived (the
// documented span-leak-on-dropped-control degradation): a slot that has
// stayed allocated for longer than trpc_rma_span_scavenge_ms WITHOUT its
// span ever being admitted by rma_resolve is leaked — the sender's
// control frame was dropped (chaos) or its connection died mid-handoff —
// and is cleared back into the window.  Admitted spans are exempt for as
// long as any payload reference holds them, so a long-lived zero-copy
// consumer is never scavenged.  Runs lazily: piggybacked (rate-limited)
// on rma_resolve, from rma_spans_in_use (the drain quiesce poll), and
// callable directly.  Reclaims are counted by the rma_span_scavenged
// var.  The timeout must exceed the slowest legitimate write+control
// latency: a still-writing sender whose span is scavenged out from
// under it degrades to a failed call (token/bitmap/CRC verification
// rejects the stale transfer), never a torn admit — the same inherent
// shared-memory race class as the documented RmaBuffer reuse contract.
// `now_us` 0 reads the clock.  Returns slots reclaimed by THIS pass.
size_t rma_scavenge(int64_t now_us = 0);

// -- readiness maps (producer-stamped chunk-ready bitmaps) -----------------
//
// A ready map tracks which granularity-sized chunks of a producer's
// buffer have been filled, with the SAME release-fence discipline as
// the RMA completion bitmaps above: the producer stamps a range with a
// release fetch_or AFTER writing the bytes, and any consumer that
// observes the bit with an acquire load is guaranteed to see the
// producer's bytes.  Maps are process-local (the collective serve
// handlers and push loops run in the producer's process); the handle
// is an opaque non-zero token safe to pass through the C API.
//
// Used by the overlap-aware collective executor (net/collective.h):
// transfers whose compiled input dependency covers [off, off+len) fire
// as soon as the range is stamped instead of waiting for a
// whole-buffer barrier.

// Registers [base, base+len) with the given chunk granularity
// (bytes > 0; the final chunk may be short).  Returns a non-zero
// handle, or 0 on invalid arguments.
uint64_t rma_ready_create(const void* base, uint64_t len,
                          uint64_t granularity);

// Marks [off, off+len) ready.  `off` must be chunk-aligned and `len` a
// multiple of the granularity (or reach exactly to the end of the
// buffer); release-fenced against the producer's preceding writes.
// Stamping is monotonic — re-stamping a range is a no-op.  Wakes all
// range waiters.  Returns 0, or -1 on bad handle / misaligned or
// out-of-range span.
int rma_ready_stamp(uint64_t handle, uint64_t off, uint64_t len);

// True (1) when every chunk overlapping [off, off+len) is stamped;
// acquire-fenced so a true answer publishes the producer's bytes.
// 0 when not yet ready, -1 on bad handle / out-of-range span.
int rma_ready_test(uint64_t handle, uint64_t off, uint64_t len);

// Blocks until rma_ready_test(handle, off, len) would return 1, or the
// absolute deadline (monotonic µs; -1 = no deadline) passes.
// Fiber- and pthread-safe (fiber Event underneath).  Returns 0 ready,
// ETIMEDOUT on deadline, EINVAL on bad handle / span.
int rma_ready_wait(uint64_t handle, uint64_t off, uint64_t len,
                   int64_t deadline_us);

// Bytes stamped ready so far (monotonic; for stats/tests).
uint64_t rma_ready_bytes(uint64_t handle);

// Unregisters the map.  Pending waiters wake and observe EINVAL.
void rma_ready_destroy(uint64_t handle);

// Live map count (quiescence checks in tests).
size_t rma_ready_maps();

}  // namespace trpc
