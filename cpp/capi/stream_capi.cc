// C ABI for the streaming plane (net/stream.h) — ordered byte-chunk
// streams with credit flow control, surfaced to Python as
// brpc_tpu/rpc/stream.py.
//
// A handle wraps a queue-backed CStream: the C++ on_message callback
// (consume fiber) enqueues chunks and notifies; trpc_stream_read blocks
// the calling pthread on a plain condition variable (ctypes releases the
// GIL), so Python readers never touch fiber primitives.  The handle is a
// heap shared_ptr holder — the stream's callbacks keep their own
// reference, so a destroy racing a late consume batch can never free the
// queue under the consumer.
//
// The window holds at this boundary: the queue keeps each chunk as the
// IOBuf it arrived in, and a chunk's bytes go back to the writer when
// trpc_stream_read has copied them out (StreamConsumed), not when the
// consume fiber queued them.  Deferring the ACK to the read was chosen
// over holding the consume fiber until the queue has room: a held fiber
// is a parked stack per stream with unread chunks, and the inference
// front door reads 100k token streams through this same handle; the
// deferred ACK costs one atomic add a read and is sent from the reader's
// thread.  So a reader that stops reading stops its writer after window
// + one chunk, and that is also the most this queue ever holds.
//
// What crosses the boundary by copy is counted: one copy out of the frame
// (or, for a chunk that came one-sided, out of the connection's receive
// window: rma_land, over the rails) on a read
// (`stream_capi_read_copy_bytes`), and on a write one copy in for
// trpc_stream_write (`stream_capi_write_copy_bytes`) and none for
// trpc_stream_write_user, which wraps the caller's memory.
#include <chrono>
#include <condition_variable>
#include <cstring>
#include <deque>
#include <memory>
#include <mutex>
#include <string>

#include "base/iobuf.h"
#include "capi/capi_util.h"
#include "fiber/fiber.h"
#include "net/channel.h"
#include "net/controller.h"
#include "net/rma.h"
#include "net/server.h"
#include "net/stream.h"
#include "stat/reducer.h"

using namespace trpc;

namespace trpc {
Controller* trpc_internal_pending_controller(void* call_handle);
}

namespace {

struct CStream {
  StreamId sid = 0;
  std::mutex mu;
  std::condition_variable cv;
  std::deque<IOBuf> chunks;
  bool closed = false;
  bool destroyed = false;  // the handle is gone: nobody will read
};

struct StreamCapiVars {
  Adder read_copy_bytes;
  Adder write_copy_bytes;
  StreamCapiVars() {
    read_copy_bytes.expose("stream_capi_read_copy_bytes",
                           "bytes trpc_stream_read copied out of a "
                           "chunk's frame into the caller's buffer");
    write_copy_bytes.expose("stream_capi_write_copy_bytes",
                            "bytes trpc_stream_write copied into a chunk "
                            "(trpc_stream_write_user copies none)");
  }
};

// Leaked with the registry, as the other capi counters are.
StreamCapiVars& g_vars = *new StreamCapiVars();

using CStreamPtr = std::shared_ptr<CStream>;

// The handle Python holds: a heap shared_ptr (callbacks hold siblings).
CStreamPtr& of(void* h) { return *static_cast<CStreamPtr*>(h); }

StreamOptions options_for(const CStreamPtr& cs, int64_t window_bytes) {
  StreamOptions opts;
  if (window_bytes > 0) {
    opts.window_bytes = window_bytes;
  }
  opts.credit_on_consumed = true;  // given back by trpc_stream_read
  opts.on_message = [cs](StreamId, IOBuf&& chunk) {
    {
      std::lock_guard<std::mutex> g(cs->mu);
      if (cs->destroyed) {
        return;  // left with the consume fiber, which drops it
      }
      cs->chunks.push_back(std::move(chunk));
    }
    cs->cv.notify_all();
  };
  opts.on_closed = [cs](StreamId) {
    {
      std::lock_guard<std::mutex> g(cs->mu);
      cs->closed = true;
    }
    cs->cv.notify_all();
  };
  return opts;
}

// Waits for the next chunk: cs->mu held through `g`.  True when one is
// queued or the stream closed, false on timeout (timeout_ms < 0 waits
// forever, 0 does not wait).
bool wait_next(const CStreamPtr& cs, std::unique_lock<std::mutex>& g,
               int64_t timeout_ms) {
  auto ready = [&cs] { return !cs->chunks.empty() || cs->closed; };
  if (timeout_ms < 0) {
    cs->cv.wait(g, ready);
    return true;
  }
  return cs->cv.wait_for(g, std::chrono::milliseconds(timeout_ms), ready);
}

}  // namespace

extern "C" {

// Client side: offer a stream on `method`'s request and return the
// established stream handle.  The RPC runs synchronously; *resp_iobuf
// (a trpc_iobuf handle) receives the response body.  On failure returns
// NULL with *err_code / err_buf filled (the offered stream is destroyed
// by the failed-call path).  tenant/priority override the channel's QoS
// default when tenant is non-empty.
void* trpc_stream_open(void* ch, const char* method, const char* req,
                       size_t req_len, int64_t timeout_ms,
                       int64_t window_bytes, const char* tenant,
                       int priority, void* resp_iobuf, int* err_code,
                       char* err_buf, size_t err_buf_len) {
  ScopedPthreadWait pin;  // sync CallMethod parks; see trpc_channel_call
  auto cs = std::make_shared<CStream>();
  Controller cntl;
  if (timeout_ms > 0) {
    cntl.set_timeout_ms(timeout_ms);
  }
  if (tenant != nullptr && tenant[0] != '\0') {
    cntl.set_qos(tenant, static_cast<uint8_t>(priority));
  }
  StreamId sid = 0;
  if (StreamCreate(&sid, &cntl, options_for(cs, window_bytes)) != 0) {
    if (err_code != nullptr) {
      *err_code = ENOMEM;
    }
    return nullptr;
  }
  cs->sid = sid;
  IOBuf request;
  if (req != nullptr && req_len > 0) {
    request.append(req, req_len);
  }
  static_cast<Channel*>(ch)->CallMethod(
      method, request, static_cast<IOBuf*>(resp_iobuf), &cntl);
  if (cntl.Failed()) {
    if (err_code != nullptr) {
      *err_code = cntl.error_code() != 0 ? cntl.error_code() : -1;
    }
    if (err_buf != nullptr && err_buf_len > 0) {
      strncpy(err_buf, cntl.error_text().c_str(), err_buf_len - 1);
      err_buf[err_buf_len - 1] = '\0';
    }
    // The failed-call path already closed the offered stream; the
    // callbacks' shared_ptr unwinds with the stream options.
    return nullptr;
  }
  if (err_code != nullptr) {
    *err_code = 0;
  }
  return new CStreamPtr(std::move(cs));
}

// Server side: accept the stream offered by the request behind an
// in-flight call handle (brpc_tpu server thunk).  Must be called BEFORE
// trpc_call_respond.  NULL when the request offered no stream.
void* trpc_call_stream_accept(void* call_handle, int64_t window_bytes) {
  Controller* cntl = trpc_internal_pending_controller(call_handle);
  auto cs = std::make_shared<CStream>();
  StreamId sid = 0;
  if (StreamAccept(&sid, cntl, options_for(cs, window_bytes)) != 0) {
    return nullptr;
  }
  cs->sid = sid;
  return new CStreamPtr(std::move(cs));
}

// Blocking read of ONE chunk, copied once, out of the frame (or the window
// span) it arrived in into `buf`: returns the chunk's length (always <=
// `cap` — the chunk is copied whole or not at all), -1 when the stream is
// closed and drained, -2 on timeout (timeout_ms < 0 waits forever), -3
// when the next chunk is LARGER than `cap`.  A -3 chunk stays queued and
// nothing is consumed:
// query trpc_stream_next_len and retry with a buffer that fits — silent
// truncation would desynchronize framed readers (e.g. fixed-size
// TokenRecord streams) without any error.  The chunk's bytes go back to
// the writer's window here, once they are out.
long trpc_stream_read(void* h, char* buf, size_t cap, int64_t timeout_ms) {
  const CStreamPtr& cs = of(h);
  std::unique_lock<std::mutex> g(cs->mu);
  if (!wait_next(cs, g, timeout_ms)) {
    return -2;
  }
  if (cs->chunks.empty()) {
    return -1;  // closed and drained
  }
  if (cs->chunks.front().size() > cap) {
    return -3;  // caller's buffer too small; chunk left queued
  }
  IOBuf chunk = std::move(cs->chunks.front());
  cs->chunks.pop_front();
  g.unlock();
  const size_t n = chunk.size();
  if (buf != nullptr && n > 0) {
    // As a unary response lands (batch_capi.cc): a chunk that came as a
    // one-sided window span is copied out over the connection's rails,
    // anything else by the one copy_to.
    rma_land(chunk, buf, n);
    g_vars.read_copy_bytes << static_cast<int64_t>(n);
  }
  chunk.clear();
  StreamConsumed(cs->sid, n);  // EINVAL once closed: nobody to tell
  return static_cast<long>(n);
}

// Length of the next chunk (bytes), waiting up to timeout_ms for one
// (< 0 forever, 0 not at all): -1 when the stream is closed and drained,
// -2 when none came in time.  Consumes nothing.  Pairs with a -3 read
// (resize and retry without losing the chunk) and sizes a reader's
// buffer before the chunk is copied.
long trpc_stream_next_len(void* h, int64_t timeout_ms) {
  const CStreamPtr& cs = of(h);
  std::unique_lock<std::mutex> g(cs->mu);
  if (!wait_next(cs, g, timeout_ms)) {
    return -2;
  }
  return cs->chunks.empty() ? -1
                            : static_cast<long>(cs->chunks.front().size());
}

// Ordered write of a copy of `data`; parks while the peer's credit window
// is exhausted.  Returns 0, EPIPE (closed / connection dead), EINVAL
// (gone).
int trpc_stream_write(void* h, const char* data, size_t len) {
  ScopedPthreadWait pin;  // StreamWrite parks on the credit window
  const CStreamPtr& cs = of(h);
  IOBuf chunk;
  if (data != nullptr && len > 0) {
    chunk.append(data, len);
    g_vars.write_copy_bytes << static_cast<int64_t>(len);
  }
  return StreamWrite(cs->sid, std::move(chunk));
}

// The same with the caller's memory wrapped, not copied: `deleter(data,
// ctx)` runs once, on whatever thread drops the frame's last reference
// (after the transport has written it, or at once when the write fails);
// until then `data` must stay as it is.
int trpc_stream_write_user(void* h, void* data, size_t len,
                           void (*deleter)(void*, void*), void* ctx) {
  ScopedPthreadWait pin;
  const CStreamPtr& cs = of(h);
  IOBuf chunk;
  chunk.append_user_data(data, len, deleter, ctx);
  return StreamWrite(cs->sid, std::move(chunk));
}

// Graceful close of the local end.  Buffered chunks stay readable; reads
// return -1 once drained.  Idempotent.
int trpc_stream_close(void* h) {
  const CStreamPtr& cs = of(h);
  {
    std::lock_guard<std::mutex> g(cs->mu);
    if (cs->closed && !StreamExists(cs->sid)) {
      return 0;
    }
  }
  return StreamClose(cs->sid);
}

// Close (if still open) and free the handle.  The stream's callbacks
// hold their own reference, so a consume batch mid-delivery finishes
// against live memory: the queue outlives the handle until the stream's
// slot is taken again, so what lies in it unread is dropped here, not
// then (a chunk that came one-sided holds slots of the connection's
// receive window until it is dropped).
void trpc_stream_destroy(void* h) {
  if (h == nullptr) {
    return;
  }
  trpc_stream_close(h);
  std::deque<IOBuf> unread;
  {
    const CStreamPtr& cs = of(h);
    std::lock_guard<std::mutex> g(cs->mu);
    cs->destroyed = true;
    unread.swap(cs->chunks);
  }
  delete static_cast<CStreamPtr*>(h);
}

unsigned long long trpc_stream_id(void* h) {
  return static_cast<unsigned long long>(of(h)->sid);
}

// Chunks currently buffered client-side (observability / tests).
size_t trpc_stream_pending(void* h) {
  const CStreamPtr& cs = of(h);
  std::lock_guard<std::mutex> g(cs->mu);
  return cs->chunks.size();
}

// The most bytes this end has held received and unread (0 once the
// stream is gone): under its window plus one chunk, by the credit gate.
unsigned long long trpc_stream_unread_high_water(void* h) {
  return static_cast<unsigned long long>(
      stream_unread_high_water(of(h)->sid));
}

// Registers a NATIVE stream echo on `method`: the handler accepts the
// stream the request offers, granting the window it was granted, and each
// chunk that arrives is written back on the same stream by moving its
// IOBuf: no copy, no Python callback, no GIL.  The write parks on the
// client's window inside the consume fiber, so the chunks behind it wait
// in the stream's queue, their bytes are not given back, and the client's
// writes park in turn: back-pressure runs end to end.  A request that
// offers no stream fails with EINVAL.
int trpc_server_register_stream_echo(void* srv, const char* method) {
  return static_cast<Server*>(srv)->RegisterMethod(
      method, [](Controller* cntl, const IOBuf&, IOBuf*, Closure done) {
        StreamOptions opts;
        if (cntl->call().peer_stream_window > 0) {
          opts.window_bytes =
              static_cast<int64_t>(cntl->call().peer_stream_window);
        }
        opts.on_message = [](StreamId sid, IOBuf&& chunk) {
          StreamWrite(sid, std::move(chunk));  // EPIPE: on_closed follows
        };
        opts.on_closed = [](StreamId sid) { StreamClose(sid); };
        StreamId sid = 0;
        if (StreamAccept(&sid, cntl, opts) != 0) {
          cntl->SetFailed(EINVAL, "the request offered no stream");
        }
        done();
      });
}

}  // extern "C"
