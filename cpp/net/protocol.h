// Protocol registry + the default framed protocol ("tstd").
//
// Parity: brpc's Protocol vtable + registry (/root/reference/src/brpc/
// protocol.h:77-186) and the baidu_std wire format (policy/
// baidu_rpc_protocol.cpp: 12-byte "PRPC" header + pb RpcMeta).  Re-designed
// wire: magic "TRP1" | meta_len u32 | payload_len u64, meta is a hand-rolled
// little-endian TLV (no protobuf dependency in the runtime) carrying type,
// correlation id, method, error code/text, attachment split.
#pragma once

#include <cstdint>
#include <memory>
#include <string>
#include <utility>
#include <vector>

#include "base/iobuf.h"
#include "net/wire_split.h"

namespace trpc {

class Socket;
using SocketId = uint64_t;

enum class ParseError : int {
  kOk = 0,
  kNotEnoughData = 1,   // keep bytes, wait for more
  kTryOtherProtocol = 2,
  kCorrupted = 3,       // kill the connection
};

struct RpcMeta {
  enum Type : uint8_t {
    kRequest = 0,
    kResponse = 1,
    kStreamFrame = 2,
    // Connection-scoped credential, sent as the FIRST frame (auth.h).
    kAuth = 3,
    // Large-message striping (net/stripe.h): one chunk of a payload that
    // was cut into K concurrent frames.  correlation_id carries the
    // stripe id; the chunk lands at stripe_offset of a stripe_total-byte
    // reassembly buffer.  May arrive on ANY connection between the two
    // processes (multi-rail), in any order.
    kStripe = 4,
    // Cascading-cancel control frame (net/deadline.h): correlation_id
    // names the in-flight REQUEST to cancel on the receiving server —
    // its cancel scope fans out to every downstream call and transfer
    // the handler started.  Empty payload; never answered (the caller
    // already gave up on the call).
    kCancel = 5,
  };
  // Stream flags (parity: streaming_rpc_meta.proto frame types).
  enum StreamFlags : uint8_t {
    kStreamData = 0,
    kStreamClose = 1,
    kStreamAck = 2,  // ack_bytes reopens the sender's credit window
  };
  Type type = kRequest;
  uint64_t correlation_id = 0;
  int32_t error_code = 0;
  uint32_t attachment_size = 0;  // trailing bytes of payload
  // Streaming: a request/response carrying stream_id offers/accepts a
  // stream (stream settings piggyback, baidu_rpc_protocol.cpp:633 parity);
  // a kStreamFrame addresses the RECEIVER's stream id.
  uint64_t stream_id = 0;
  uint8_t stream_flags = 0;
  uint64_t ack_bytes = 0;
  // Batch stream establishment (StreamIds parity, ref stream.h:114):
  // further (stream_id, window) offers/acceptances beyond the first,
  // index-aligned between request and response.  Optional wire tail.
  std::vector<std::pair<uint64_t, uint64_t>> extra_streams;
  // rpcz trace context (span.h parity: trace_id/span_id/parent propagate
  // inside the meta like the reference's RpcMeta).  Optional wire tail —
  // absent (zero) when the peer predates it or rpcz is off.
  uint64_t trace_id = 0;
  uint64_t span_id = 0;
  uint64_t parent_span_id = 0;
  // Negotiated per call (policy/gzip_compress.* + crc32c_checksum.*
  // parity): payload compression id and crc32c over the on-wire payload
  // (0 = unchecked).  Ride the optional tail with the trace context.
  uint8_t compress_type = 0;
  bool has_checksum = false;  // presence flag: a zero CRC is still a CRC
  uint32_t checksum = 0;
  // Large-message striping (net/stripe.h).  On a HEAD frame
  // (kRequest/kResponse): stripe_id != 0 announces that only the first
  // chunk rides this frame and stripe_total payload bytes follow across
  // kStripe frames sharing the id.  On a kStripe chunk: the payload
  // lands at [stripe_offset, stripe_offset+len) of the reassembly
  // buffer.  Zero everywhere on the (sub-threshold) hot path — the
  // fourth optional wire-tail group, absent from small frames.
  uint64_t stripe_id = 0;
  uint64_t stripe_offset = 0;
  uint64_t stripe_total = 0;
  // QoS tag (net/qos.h): priority class (0 = highest lane; also the
  // default, so untagged traffic rides the top lane when lanes are on)
  // and the tenant the request bills to (per-tenant weighted-fair
  // dequeue + admission control).  Fifth optional wire-tail group —
  // absent (zero/empty) on untagged traffic, so the default hot path
  // never pays for it.
  uint8_t qos_priority = 0;
  std::string qos_tenant;
  // One-sided RMA (net/rma.h).  On a control frame (kRequest/kResponse,
  // or a kStreamFrame of kStreamData whose correlation_id names the
  // transfer, with rma_rkey != 0 and an EMPTY payload): the body landed
  // out-of-band — rma_len bytes written by the sender into the named
  // registered region at rma_off of its data area (kRmaDirectOff = the
  // region's own data start, completion bitmap in the region header),
  // in rma_chunk-sized chunks whose release-fenced completion bits the
  // receiver verifies before dispatch.  rma_resp_rkey/rma_resp_max on a
  // REQUEST advertise the caller's registered landing region so the
  // response can be put straight into the caller's buffer, rma_resp_off
  // bytes into its data area (collective pulls land a shard mid-region;
  // 0 = the region start, the batch-plane shape).  Sixth optional
  // wire-tail group — all-zero (absent) on every non-rma frame.
  uint64_t rma_rkey = 0;
  uint64_t rma_off = 0;
  uint64_t rma_len = 0;
  uint32_t rma_chunk = 0;
  uint64_t rma_resp_rkey = 0;
  uint64_t rma_resp_max = 0;
  uint64_t rma_resp_off = 0;
  // End-to-end deadline (net/deadline.h): the caller's REMAINING budget
  // in µs at send time (relative, so clock skew between hosts never
  // corrupts it; the receiver anchors it to its own arrival clock).
  // Seventh optional wire-tail group — zero (absent) when the caller
  // has no deadline, so unset traffic stays byte-identical.
  uint64_t deadline_us = 0;
  // Server phase stamps (net/wire_split.h), on a kResponse only: the
  // SERVER's monotonic clock when the request was whole, when its
  // handler was entered and when the handler's done() ran.  All zero
  // (absent) from a peer that predates them.  On the wire 16 bytes
  // (arrival u64, then handler - arrival and done - handler as u32 us,
  // saturating at 71 minutes): alone after error_text when no other
  // tail group rides the frame, else as the eighth group.
  SrvStamps srv;
  std::string method;
  std::string error_text;

  // Back to defaults, RETAINING string/vector capacity (the pooled
  // InputMessage reuse path; a fresh `= RpcMeta{}` would free it).
  void reset() {
    type = kRequest;
    correlation_id = 0;
    error_code = 0;
    attachment_size = 0;
    stream_id = 0;
    stream_flags = 0;
    ack_bytes = 0;
    extra_streams.clear();
    trace_id = 0;
    span_id = 0;
    parent_span_id = 0;
    compress_type = 0;
    has_checksum = false;
    checksum = 0;
    stripe_id = 0;
    stripe_offset = 0;
    stripe_total = 0;
    qos_priority = 0;
    qos_tenant.clear();
    rma_rkey = 0;
    rma_off = 0;
    rma_len = 0;
    rma_chunk = 0;
    rma_resp_rkey = 0;
    rma_resp_max = 0;
    rma_resp_off = 0;
    deadline_us = 0;
    srv = {};
    method.clear();
    error_text.clear();
  }
};

struct InputMessage {
  RpcMeta meta;
  IOBuf payload;  // body (+ attachment tail per meta.attachment_size)
  SocketId socket = 0;
  // Arrival clock of a kRequest: monotonic_time_us() when the request
  // was cut from the connection and whole (tstd_parse, after the
  // payload's checksum; a striped or one-sided request when its
  // reassembly is complete).  The one anchor of everything that counts
  // from arrival: the server's absolute deadline is arrival_us +
  // deadline_us (so time queued in a QoS lane counts against the
  // budget), the per-method rpc_server_<method>_queue_us, the stamps a
  // response carries back, and capture's queue time.  0 on every other
  // frame type, and on a message no parser cut.
  int64_t arrival_us = 0;
  // Protocol-private context (the reference subclasses InputMessageBase per
  // protocol; an opaque pointer is the condensed seam).  HTTP stores its
  // parsed HttpRequest here.
  std::shared_ptr<void> ctx;
};

struct Protocol {
  const char* name;
  // Cuts ONE complete message off `source` (or reports NotEnoughData).
  // `sock` may be null (protocol unit tests); parsers use it only for
  // incremental state (Socket::parse_state).
  ParseError (*parse)(IOBuf* source, InputMessage* out, Socket* sock);
  // Server side: handle a request message (runs in its own fiber).
  void (*process_request)(InputMessage&& msg);
  // Client side: handle a response message.
  void (*process_response)(InputMessage&& msg);
  // True for protocols WITHOUT correlation ids (HTTP/1.1): messages on one
  // connection are processed in order in the read fiber so responses stay
  // FIFO; tstd dispatches each message to its own fiber instead.
  bool process_in_order = false;
};

// Registry (parity: RegisterProtocol, protocol.h:186).  Index is pinned on
// the socket after first successful parse.
int register_protocol(const Protocol& p);
const Protocol* protocol_at(int index);
int protocol_count();

// The default framed protocol; registered on first use by Server/Channel.
const Protocol& tstd_protocol();

// Helpers shared by server/channel: build one framed message.
void tstd_pack(IOBuf* out, const RpcMeta& meta, const IOBuf& payload);

}  // namespace trpc
