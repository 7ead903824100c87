// Large-message striping — the multi-rail data path for payloads above a
// reloadable threshold.
//
// Parity: fabric-lib (arxiv 2510.27656) stripes LLM-scale point-to-point
// transfers across multiple rails/QPs to saturate links, and brpc's
// pooled-connection matrix exists for exactly the per-payload-exclusive-
// connection reason; this layer combines the two: one logical
// request/response is cut into K chunk frames issued CONCURRENTLY across
// the pooled connection set (per-rail FIFO preserved, cross-rail order
// free), and the receiver scatters each chunk straight into a single
// preallocated contiguous landing buffer via offset-addressed writes,
// with the per-chunk memcpy fanned out over worker fibers instead of
// serializing on the parse fiber.
//
// Wire shape (net/protocol.h): the HEAD frame is a normal
// kRequest/kResponse whose meta carries {stripe_id, stripe_total} and
// whose payload is chunk 0; the remaining chunks ride kStripe frames
// addressed by stripe_id + stripe_offset, each individually
// crc32c-checksummed when the call asked for checksums.  Sub-threshold
// messages never touch any of this — same wait-free inline-write path,
// byte-identical frames.
//
// Failure semantics: a dropped/truncated chunk either kills its
// connection (parser-level corruption) or simply never lands; the
// reassembly entry expires after trpc_stripe_reassembly_timeout_ms and
// the CALL fails as a whole (client timeout), never with a partial
// payload.  A rail whose socket died at send time retries its chunk on
// the primary connection; only a primary failure fails the send.
#pragma once

#include <cstdint>
#include <vector>

#include "base/iobuf.h"
#include "net/deadline.h"
#include "net/protocol.h"

namespace trpc {

// -- sending ---------------------------------------------------------------

// True when a payload of n bytes should be striped: the reloadable
// trpc_stripe_threshold flag is nonzero, n exceeds it, and n fits a
// single landing block (< 3GB; larger bodies fall back to one frame).
bool stripe_eligible(uint64_t n);

// Chunk size currently configured (trpc_stripe_chunk_bytes).
uint64_t stripe_chunk_bytes();

// Rails to spread chunks over (trpc_stripe_rails, including the primary).
int stripe_rails();

// Nonzero random stripe id for a REQUEST.  (Responses reuse the call's
// correlation id, which is unique in the client process doing the
// reassembly — and lets a registered caller buffer catch chunks that
// arrive before the head frame.)
uint64_t stripe_make_id();

// The one striping decision, shared by client (channel.cc) and server
// (server.cc): eligible size, no stream-establishment piggyback on the
// frame, and not an ICI ring — ICI payloads ride sender-owned zero-copy
// descriptors over a 32-slot SQ (already multi-slot pipelining), and
// chunking would trade descriptors for per-chunk landing copies.  The
// socket-mode probe runs only for above-threshold bodies.
bool stripe_should(SocketId primary, uint64_t stream_id,
                   uint64_t body_bytes);

// Single-frame fallback shared by both sides: whole-body crc32c when
// meta.has_checksum, pack, write on primary.  Returns 0 when accepted.
int stripe_frame_send(SocketId primary, RpcMeta&& meta, IOBuf&& body);

// Sends meta+body as head + kStripe chunks.  rails lists the candidate
// connections (may include primary; may be empty = primary only); chunks
// round-robin over them, and any chunk whose rail is dead reroutes to
// the primary.  meta's stripe fields are filled here; with
// meta.has_checksum each frame carries the crc32c of ITS OWN payload
// (verified per frame by the receiving parser).  Returns 0 when every
// frame was accepted by a write queue.  tok (net/deadline.h): polled
// between chunk frames — a cancelled caller / expired budget stops
// cutting, the receiver's partial reassembly expires whole (reassembly
// timeout), and the skipped bytes count as cancel_saved_bytes.
int stripe_send(SocketId primary, const std::vector<SocketId>& rails,
                RpcMeta&& meta, IOBuf&& body, uint64_t stripe_id,
                const DeadlineToken& tok = DeadlineToken{});

// -- receiving (messenger hooks) ------------------------------------------

// A parsed HEAD frame (kRequest/kResponse with stripe_id != 0).
void stripe_on_head(InputMessage&& msg);
// A parsed kStripe chunk frame.
void stripe_on_chunk(InputMessage&& msg);

// Rails a reassembled REQUEST arrived over, published to the server so
// its response stripes back across the same connections.  Carried via
// InputMessage::ctx.
struct StripeArrival {
  std::vector<SocketId> rails;
};

// -- caller-buffer landing (Python batch plane) ---------------------------

// Registers a caller-owned buffer as the landing destination for the
// striped RESPONSE of call `cid`: chunks memcpy straight into it (no
// arena bounce, no extra copy at the Python boundary).  The one-sided
// landing of the same buffer is bound apart (rma_bind_response,
// net/rma.h).  The buffer must stay valid until
// stripe_unregister_landing(cid) returns.
void stripe_register_landing(uint64_t cid, void* buf, size_t cap);
// Idempotent.  Blocks (bounded: at most one in-flight chunk memcpy per
// lander fiber) until no lander can touch the buffer again.
void stripe_unregister_landing(uint64_t cid);

// -- maintenance / introspection ------------------------------------------

// Expires reassembly entries older than trpc_stripe_reassembly_timeout_ms
// (also run lazily from the receive hooks, ~1/s).
void stripe_gc(int64_t now_us);
// Live (incomplete) reassemblies — tests and /vars.
size_t stripe_pending_reassemblies();

}  // namespace trpc
