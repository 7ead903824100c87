#include "net/protocol.h"

#include "base/compress.h"
#include "base/time.h"

#include <cstring>
#include <mutex>
#include <vector>

#include "base/logging.h"
#include "net/socket.h"

namespace trpc {

namespace {

// Fixed-capacity registry: entries are address-stable for the lifetime of
// the process, so hot-path Protocol* caches can never dangle on a
// concurrent registration (a growing vector would reallocate).
constexpr int kMaxProtocols = 16;
std::mutex& proto_mu() {
  static std::mutex* mu = new std::mutex();
  return *mu;
}
Protocol g_protocols[kMaxProtocols];
std::atomic<int> g_proto_count{0};

// -- little-endian scalar helpers ----------------------------------------

void put_u32(std::string* s, uint32_t v) {
  char b[4];
  memcpy(b, &v, 4);
  s->append(b, 4);
}

void put_u64(std::string* s, uint64_t v) {
  char b[8];
  memcpy(b, &v, 8);
  s->append(b, 8);
}

uint32_t get_u32(const char* p) {
  uint32_t v;
  memcpy(&v, p, 4);
  return v;
}

uint64_t get_u64(const char* p) {
  uint64_t v;
  memcpy(&v, p, 8);
  return v;
}

constexpr char kMagic[4] = {'T', 'R', 'P', '1'};
constexpr size_t kHeaderLen = 4 + 4 + 8;  // magic | meta_len | payload_len

// Server phase stamps (RpcMeta::srv): arrival u64, then the two
// differences as u32 us.  Shorter than the trace group on purpose: a
// tail of exactly this length can be no older group, and a decoder that
// predates the stamps skips a tail under 24 bytes whole.
constexpr size_t kSrvStampBytes = 8 + 4 + 4;

uint32_t saturate_u32(int64_t v) {
  return v <= 0 ? 0
                : v >= 0xffffffffll ? 0xffffffffu : static_cast<uint32_t>(v);
}

void put_srv_stamps(std::string* s, const SrvStamps& srv) {
  put_u64(s, static_cast<uint64_t>(srv.arrival_us));
  put_u32(s, saturate_u32(srv.handler_us - srv.arrival_us));
  put_u32(s, saturate_u32(srv.done_us - srv.handler_us));
}

std::string encode_meta(const RpcMeta& m) {
  std::string s;
  s.push_back(static_cast<char>(m.type));
  put_u64(&s, m.correlation_id);
  put_u32(&s, static_cast<uint32_t>(m.error_code));
  put_u32(&s, m.attachment_size);
  put_u64(&s, m.stream_id);
  s.push_back(static_cast<char>(m.stream_flags));
  put_u64(&s, m.ack_bytes);
  put_u32(&s, static_cast<uint32_t>(m.method.size()));
  s.append(m.method);
  put_u32(&s, static_cast<uint32_t>(m.error_text.size()));
  s.append(m.error_text);
  // Optional tail, only when any of its fields is active: decoders treat
  // it as length-gated (they only look past error_text when bytes
  // remain), so presence/absence are both wire-compatible — and the
  // streaming hot path never pays for it.  Layout: trace(24B), then
  // compress+checksum(6B), then batch streams(4B+), then stripe(24B),
  // then qos(3B+), then rma(52B), then deadline(8B), then the server's
  // phase stamps(16B); each later group implies every earlier one.
  // The stamps ride every response, so where they are the ONLY active
  // group they go alone, as a 16-byte tail (kSrvStampBytes): a plain
  // response pays 16 bytes for them, not the 145 of the whole tail.
  const bool has_srv = m.srv.arrival_us != 0;
  bool has_deadline = m.deadline_us != 0;
  bool has_rma = m.rma_rkey != 0 || m.rma_resp_rkey != 0 || has_deadline;
  bool has_qos = m.qos_priority != 0 || !m.qos_tenant.empty() || has_rma;
  bool has_stripe = m.stripe_id != 0 || has_qos;
  bool has_streams = !m.extra_streams.empty() || has_stripe;
  bool has_comp = m.compress_type != 0 || m.has_checksum || has_streams;
  if (has_srv) {
    if (m.trace_id == 0 && !has_comp) {
      put_srv_stamps(&s, m.srv);
      return s;
    }
    has_deadline = has_rma = has_qos = has_stripe = has_streams = has_comp =
        true;
  }
  if (m.trace_id != 0 || has_comp) {
    // tail-group 1 (trace): trace/span/parent ids, 24B.
    put_u64(&s, m.trace_id);
    put_u64(&s, m.span_id);
    put_u64(&s, m.parent_span_id);
    if (has_comp) {
      // tail-group 2 (compress): compress id + checksum presence/value, 6B.
      s.push_back(static_cast<char>(m.compress_type));
      s.push_back(m.has_checksum ? 1 : 0);
      put_u32(&s, m.checksum);
      if (has_streams) {
        // tail-group 3 (streams): batch stream offers (count + pairs).
        put_u32(&s, static_cast<uint32_t>(m.extra_streams.size()));
        for (const auto& [sid, window] : m.extra_streams) {
          put_u64(&s, sid);
          put_u64(&s, window);
        }
        if (has_stripe) {
          // tail-group 4 (stripe): large-message striping (net/stripe.h).
          put_u64(&s, m.stripe_id);
          put_u64(&s, m.stripe_offset);
          put_u64(&s, m.stripe_total);
          if (has_qos) {
            // tail-group 5 (qos): QoS tag (net/qos.h).  Tenant clamps to
            // the decoder's 64-byte cap HERE — the single choke point —
            // so an over-long name set through any surface (e.g. the
            // public Channel::Options field) truncates instead of
            // producing a frame the peer rejects as corrupt.
            s.push_back(static_cast<char>(m.qos_priority));
            const uint16_t tlen = static_cast<uint16_t>(
                m.qos_tenant.size() > 64 ? 64 : m.qos_tenant.size());
            s.push_back(static_cast<char>(tlen & 0xff));
            s.push_back(static_cast<char>(tlen >> 8));
            s.append(m.qos_tenant.data(), tlen);
            if (has_rma) {
              // tail-group 6 (rma): one-sided transfer descriptor +
              // response-landing advertisement (net/rma.h), 52B.
              put_u64(&s, m.rma_rkey);
              put_u64(&s, m.rma_off);
              put_u64(&s, m.rma_len);
              put_u32(&s, m.rma_chunk);
              put_u64(&s, m.rma_resp_rkey);
              put_u64(&s, m.rma_resp_max);
              put_u64(&s, m.rma_resp_off);
              if (has_deadline) {
                // tail-group 7 (deadline): remaining budget µs, 8B
                // (net/deadline.h).
                put_u64(&s, m.deadline_us);
                if (has_srv) {
                  // tail-group 8 (srv_stamps): the server's phase
                  // stamps, 16B.
                  put_srv_stamps(&s, m.srv);
                }
              }
            }
          }
        }
      }
    }
  }
  return s;
}

// The stamps are untrusted like every wire value: an arrival the two
// differences could overflow from reads as absent.
void get_srv_stamps(const char* p, SrvStamps* srv) {
  const uint64_t arrival = get_u64(p);
  if (arrival == 0 || arrival > (1ull << 62)) {
    return;
  }
  srv->arrival_us = static_cast<int64_t>(arrival);
  srv->handler_us = srv->arrival_us + get_u32(p + 8);
  srv->done_us = srv->handler_us + get_u32(p + 12);
}

bool decode_meta(const std::string& s, RpcMeta* m) {
  const char* p = s.data();
  const char* end = p + s.size();
  if (end - p < 1 + 8 + 4 + 4 + 8 + 1 + 8 + 4) {
    return false;
  }
  m->type = static_cast<RpcMeta::Type>(*p++);
  m->correlation_id = get_u64(p);
  p += 8;
  m->error_code = static_cast<int32_t>(get_u32(p));
  p += 4;
  m->attachment_size = get_u32(p);
  p += 4;
  m->stream_id = get_u64(p);
  p += 8;
  m->stream_flags = static_cast<uint8_t>(*p++);
  m->ack_bytes = get_u64(p);
  p += 8;
  const uint32_t mlen = get_u32(p);
  p += 4;
  // 64-bit arithmetic: mlen near UINT32_MAX must not wrap the bound check.
  if (static_cast<uint64_t>(end - p) < static_cast<uint64_t>(mlen) + 4) {
    return false;
  }
  m->method.assign(p, mlen);
  p += mlen;
  const uint32_t elen = get_u32(p);
  p += 4;
  if (static_cast<uint64_t>(end - p) < static_cast<uint64_t>(elen)) {
    return false;
  }
  m->error_text.assign(p, elen);
  p += elen;
  if (end - p == static_cast<ptrdiff_t>(kSrvStampBytes)) {
    // The server's phase stamps alone (no older encoder emits a tail
    // under 24 bytes).
    get_srv_stamps(p, &m->srv);
    return true;
  }
  if (end - p >= 24) {  // tail-group 1 (trace)
    m->trace_id = get_u64(p);
    m->span_id = get_u64(p + 8);
    m->parent_span_id = get_u64(p + 16);
    p += 24;
    if (end - p >= 6) {  // tail-group 2 (compress)
      m->compress_type = static_cast<uint8_t>(*p++);
      m->has_checksum = *p++ != 0;
      m->checksum = get_u32(p);
      p += 4;
      if (end - p >= 4) {  // tail-group 3 (streams)
        const uint32_t count = get_u32(p);
        p += 4;
        if (count > 256 ||
            static_cast<uint64_t>(end - p) < count * 16ull) {
          return false;
        }
        m->extra_streams.reserve(count);
        for (uint32_t i = 0; i < count; ++i) {
          m->extra_streams.emplace_back(get_u64(p), get_u64(p + 8));
          p += 16;
        }
        if (end - p >= 24) {  // tail-group 4 (stripe)
          m->stripe_id = get_u64(p);
          m->stripe_offset = get_u64(p + 8);
          m->stripe_total = get_u64(p + 16);
          p += 24;
          if (end - p >= 3) {  // tail-group 5 (qos)
            m->qos_priority = static_cast<uint8_t>(*p++);
            const uint16_t tlen =
                static_cast<uint16_t>(static_cast<uint8_t>(p[0])) |
                (static_cast<uint16_t>(static_cast<uint8_t>(p[1])) << 8);
            p += 2;
            if (tlen > 64 ||
                static_cast<uint64_t>(end - p) < static_cast<uint64_t>(tlen)) {
              return false;
            }
            m->qos_tenant.assign(p, tlen);
            p += tlen;
            if (end - p >= 44) {  // tail-group 6 (rma)
              m->rma_rkey = get_u64(p);
              m->rma_off = get_u64(p + 8);
              m->rma_len = get_u64(p + 16);
              m->rma_chunk = get_u32(p + 24);
              m->rma_resp_rkey = get_u64(p + 28);
              m->rma_resp_max = get_u64(p + 36);
              if (end - p >= 52) {
                m->rma_resp_off = get_u64(p + 44);
                p += 52;
                if (end - p >= 8) {  // tail-group 7 (deadline)
                  m->deadline_us = get_u64(p);
                  p += 8;
                  if (end - p >=
                      static_cast<ptrdiff_t>(kSrvStampBytes)) {
                    // tail-group 8 (srv_stamps)
                    get_srv_stamps(p, &m->srv);
                    p += kSrvStampBytes;
                  }
                }
              } else {
                // Previous-version frame (44B group, pre-rma_resp_off):
                // the descriptor is intact, the landing offset defaults
                // to the region start — mixed-version one-sided traffic
                // keeps working across a rolling upgrade.
                m->rma_resp_off = 0;
                p += 44;
              }
            }
          }
        }
      }
    }
  }
  return true;
}

ParseError tstd_parse(IOBuf* source, InputMessage* out, Socket* sock) {
  // Reject a wrong magic as soon as the available prefix disagrees, so the
  // messenger can offer the bytes to other protocols without waiting.
  char header[kHeaderLen];
  const size_t avail = source->copy_to(header, kHeaderLen);
  if (memcmp(header, kMagic, std::min<size_t>(avail, 4)) != 0) {
    return ParseError::kTryOtherProtocol;
  }
  if (avail < kHeaderLen) {
    return ParseError::kNotEnoughData;
  }
  const uint32_t meta_len = get_u32(header + 4);
  const uint64_t payload_len = get_u64(header + 8);
  if (meta_len > 64 * 1024 * 1024 || payload_len > (1ull << 40)) {
    return ParseError::kCorrupted;
  }
  if (source->size() < kHeaderLen + meta_len + payload_len) {
    // Bulk-read hint: the frame length is known, so the messenger can
    // read the remainder into a few LARGE blocks (one readv iovec each)
    // instead of 8KB slivers — under gVisor-style kernels the per-iovec
    // cost is what caps large-message goodput.
    if (sock != nullptr) {
      sock->read_block_hint =
          kHeaderLen + meta_len + payload_len - source->size();
    }
    return ParseError::kNotEnoughData;
  }
  if (sock != nullptr) {
    sock->read_block_hint = 0;
  }
  source->pop_front(kHeaderLen);
  std::string meta_bytes;
  {
    IOBuf meta_buf;
    source->cutn(&meta_buf, meta_len);
    meta_bytes = meta_buf.to_string();
  }
  if (!decode_meta(meta_bytes, &out->meta)) {
    return ParseError::kCorrupted;
  }
  source->cutn(&out->payload, payload_len);
  if (out->meta.has_checksum &&
      crc32c(out->payload) != out->meta.checksum) {
    // The transport delivered different bytes than were sent: the
    // connection's framing can no longer be trusted.
    return ParseError::kCorrupted;
  }
  if (out->meta.type == RpcMeta::kRequest) {
    // The request is cut and whole: every request's arrival, on OUR
    // clock (InputMessage::arrival_us).  Queueing behind this point
    // (QoS lanes, dispatch backlog) counts against a deadline's budget
    // and in the method's queue time.
    out->arrival_us = monotonic_time_us();
  }
  return ParseError::kOk;
}

}  // namespace

void tstd_pack(IOBuf* out, const RpcMeta& meta, const IOBuf& payload) {
  const std::string meta_bytes = encode_meta(meta);
  std::string header;
  header.append(kMagic, 4);
  put_u32(&header, static_cast<uint32_t>(meta_bytes.size()));
  put_u64(&header, payload.size());
  out->append(header);
  out->append(meta_bytes);
  out->append(payload);  // zero-copy block share
}

int register_protocol(const Protocol& p) {
  std::lock_guard<std::mutex> g(proto_mu());
  const int n = g_proto_count.load(std::memory_order_relaxed);
  if (n >= kMaxProtocols) {
    return -1;
  }
  g_protocols[n] = p;
  g_proto_count.store(n + 1, std::memory_order_release);
  return n;
}

const Protocol* protocol_at(int index) {
  if (index < 0 || index >= g_proto_count.load(std::memory_order_acquire)) {
    return nullptr;
  }
  return &g_protocols[index];
}

int protocol_count() {
  return g_proto_count.load(std::memory_order_acquire);
}

// process_request / process_response are installed by server.cc/channel.cc.
void tstd_process_request(InputMessage&& msg);
void tstd_process_response(InputMessage&& msg);

const Protocol& tstd_protocol() {
  static Protocol p = {"tstd", tstd_parse, tstd_process_request,
                       tstd_process_response};
  static int registered = register_protocol(p);
  (void)registered;
  return p;
}

}  // namespace trpc
