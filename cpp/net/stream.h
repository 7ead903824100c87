// Streaming RPC — ordered byte-chunk streams with credit flow control.
//
// Parity: brpc streaming (/root/reference/src/brpc/stream.h:106-150,
// stream.cpp: Create :78, ExecutionQueue consumer :109/:582, credit-window
// AppendIfNotFull :326, feedback frames via streaming_rpc_meta.proto).
// Re-designed: a stream is a pooled versioned object bound to an existing
// connection; frames ride the tstd protocol (meta.type = kStreamFrame) and
// are consumed through a per-stream ExecutionQueue so handlers see chunks
// in order; ACK frames reopen the writer's window, writers park on an
// Event when credit runs out.  A chunk over the large-message threshold,
// on a connection with a one-sided session (net/rma.h), is put into the
// peer's receive window and its frame carries the descriptor alone; the
// frame keeps its place in the connection's order and the credit gate
// counts the chunk as before (`stream_one_sided_bytes`).
//
// The window (upstream's max_buf_size, docs/en/streaming_rpc.md): a chunk
// is admitted whenever the window is NOT EXHAUSTED, whatever its size
// (AppendIfNotFull: produced < consumed + window), so a chunk of any
// width goes on a window of any width and the bytes written and not yet
// given back stay under window + one chunk.  The credit is signed and
// exact: an ACK returns what was consumed.  Bytes are given back when the
// consumer has USED them: after on_message returns, or, for a consumer
// that only takes delivery there (the C ABI's queue), when it says so
// with StreamConsumed.  So what lies unread at a receiver never passes
// window + one chunk either; `stream_unread_high_water_bytes` holds the
// most any stream of the process has held.
//
// Establishment piggybacks on a normal RPC (like the reference):
//   client: StreamCreate(&sid, &cntl, opts); channel.CallMethod(...);
//   server handler: StreamAccept(&sid, cntl, opts); ... done();
// After the response returns, both sides may StreamWrite / receive
// on_message callbacks.  Each side must StreamClose its own id.
#pragma once

#include <cstdint>
#include <functional>
#include <vector>

#include "base/iobuf.h"
#include "net/controller.h"

namespace trpc {

using StreamId = uint64_t;  // version<<32 | slot

struct StreamOptions {
  // Called in arrival order (serialized per stream), from a fiber.
  std::function<void(StreamId, IOBuf&&)> on_message;
  // Peer closed (or connection died).
  std::function<void(StreamId)> on_closed;
  int64_t window_bytes = 2 * 1024 * 1024;  // receive window we grant
  // on_message only takes delivery (queues the chunk for an application
  // that reads later): its return gives no bytes back, StreamConsumed
  // does, when the application has taken them.
  bool credit_on_consumed = false;
};

// Client side: create a local stream and attach it to `cntl` so the next
// CallMethod on that controller offers it to the server.
int StreamCreate(StreamId* out, Controller* cntl, const StreamOptions& opts);

// Server side: accept the stream offered by the current request (fails if
// the request carries none).  Must be called before done().
int StreamAccept(StreamId* out, Controller* cntl, const StreamOptions& opts);

// Batch establishment (StreamIds parity, ref stream.h:114): one RPC
// offers `count` streams at once; the server accepts ALL of them in one
// call.  All share `opts` (each still gets its own window/queue).  The
// batch accepts/fails atomically: a mid-batch allocation failure
// destroys the partial set and returns ENOMEM.
int StreamCreateBatch(std::vector<StreamId>* out, int count,
                      Controller* cntl, const StreamOptions& opts);
int StreamAcceptBatch(std::vector<StreamId>* out, Controller* cntl,
                      const StreamOptions& opts);

// Ordered write; parks the calling fiber while the peer's window is
// exhausted (admitted as soon as any of it is open, whatever the chunk's
// size).  Returns 0, EINVAL (gone), EPIPE (closed/conn dead).
int StreamWrite(StreamId id, IOBuf&& data);

// A stream opened with credit_on_consumed: the application has taken
// `bytes` of what on_message delivered; they go back to the writer (an
// ACK frame once half the window has gathered).  EINVAL when gone.
int StreamConsumed(StreamId id, size_t bytes);

// Graceful close: sends CLOSE (best effort) and destroys the local id.
int StreamClose(StreamId id);

// Park until the peer closes the stream (or it dies).  0 on close.
int StreamWait(StreamId id, int64_t deadline_us = -1);

// True while the id refers to a live stream.
bool StreamExists(StreamId id);

// -- internal (messenger hook) -------------------------------------------
struct InputMessage;
void stream_on_frame(InputMessage&& msg);
// A data frame of `stream_id` (this end's id) arrived without its body: the
// one-sided transfer its descriptor names did not verify (net/rma.h).  A
// unary call times such a message out; a stream cannot time ONE chunk out
// and keep its order, so the stream closes at both ends, behind the chunks
// that arrived before.
void stream_on_chunk_lost(uint64_t stream_id);
// Bind the client stream to the server's accepted id (response path).
// `peer_window` is the receive window the peer advertised — it becomes our
// send credit (windows are exchanged at establishment, like the stream
// settings in streaming_rpc_meta.proto).
void stream_on_accept_response(uint64_t local_sid, uint64_t peer_sid,
                               uint64_t socket_id, uint64_t peer_window);
// The receive window a local stream grants (advertised to the peer).
uint64_t stream_recv_window(StreamId id);
// The most bytes this stream has held received and not yet given back
// (in its consume queue, inside on_message, or delivered and unread).
uint64_t stream_unread_high_water(StreamId id);
// Remaining send credit (the peer's advertised window minus unacked
// writes).  0 for unknown/unestablished ids.  The inference scheduler
// caps per-request token budgets with this so a batch write can never
// park the shared decode loop on one slow reader.
uint64_t stream_send_window(StreamId id);
// Invoked by Socket::SetFailed (registered failure observer): closes
// every stream bound to the dead connection so readers get on_closed
// promptly instead of wedging until a write probes the socket.
void stream_on_connection_failed(uint64_t socket_id);

}  // namespace trpc
