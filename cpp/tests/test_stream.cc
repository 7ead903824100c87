// Streaming RPC tests (parity: test/brpc_streaming_rpc_unittest.cpp model —
// establish over a normal RPC, ordered chunks, flow control, close), and a
// wide chunk's way through the connection's one-sided window (net/rma.h):
// order across the two ways, what a transfer that does not verify does to
// the stream, and what a close leaves allocated.
#include <unistd.h>

#include <atomic>
#include <mutex>
#include <string>
#include <vector>

#include "base/time.h"
#include "fiber/fiber.h"
#include "fiber/sync.h"
#include "net/channel.h"
#include "net/fault.h"
#include "net/rma.h"
#include "net/server.h"
#include "net/stream.h"
#include "stat/reducer.h"
#include "tests/test_util.h"

using namespace trpc;

namespace {

Server* g_server = nullptr;
int g_port = 0;

// Server-side stream state for assertions.
std::atomic<int64_t> g_srv_bytes{0};
std::atomic<int> g_srv_chunks{0};
std::atomic<int> g_srv_closed{0};
std::atomic<uint64_t> g_srv_last_seq{0};
std::atomic<bool> g_srv_order_ok{true};
std::atomic<int64_t> g_consume_delay_us{0};

void start_once() {
  if (g_server != nullptr) {
    return;
  }
  g_server = new Server();
  g_server->RegisterMethod(
      "Stream.Open", [](Controller* cntl, const IOBuf&, IOBuf* resp,
                        Closure done) {
        StreamOptions opts;
        opts.on_message = [](StreamId, IOBuf&& chunk) {
          if (g_consume_delay_us.load() > 0) {
            fiber_sleep_us(g_consume_delay_us.load());
          }
          // First 8 bytes carry a sequence number.
          uint64_t seq = 0;
          chunk.copy_to(&seq, 8);
          const uint64_t last = g_srv_last_seq.exchange(seq);
          if (seq != last + 1) {
            g_srv_order_ok.store(false);
          }
          g_srv_bytes.fetch_add(chunk.size());
          g_srv_chunks.fetch_add(1);
        };
        opts.on_closed = [](StreamId sid) {
          g_srv_closed.fetch_add(1);
          StreamClose(sid);
        };
        StreamId sid = 0;
        if (StreamAccept(&sid, cntl, opts) != 0) {
          resp->append("no-stream");
          done();
          return;
        }
        resp->append("accepted");
        done();
      });
  EXPECT_EQ(g_server->Start(0), 0);
  g_port = g_server->port();
}

}  // namespace

TEST_CASE(stream_establish_write_close) {
  start_once();
  g_srv_bytes = 0;
  g_srv_chunks = 0;
  g_srv_last_seq = 0;
  g_srv_order_ok = true;

  Channel ch;
  EXPECT_EQ(ch.Init("127.0.0.1:" + std::to_string(g_port)), 0);
  Controller cntl;
  StreamId sid = 0;
  EXPECT_EQ(StreamCreate(&sid, &cntl, StreamOptions{}), 0);
  IOBuf req, resp;
  req.append("open");
  ch.CallMethod("Stream.Open", req, &resp, &cntl);
  EXPECT(!cntl.Failed());
  EXPECT(resp.to_string() == "accepted");

  // Write 100 ordered chunks from a fiber.
  static StreamId s_sid;
  s_sid = sid;
  fiber_t writer;
  fiber_start(&writer, [](void*) {
    for (uint64_t seq = 1; seq <= 100; ++seq) {
      IOBuf chunk;
      chunk.append(&seq, 8);
      chunk.append(std::string(1000, 'd'));
      EXPECT_EQ(StreamWrite(s_sid, std::move(chunk)), 0);
    }
    StreamClose(s_sid);
  }, nullptr);
  fiber_join(writer);

  const int64_t deadline = monotonic_time_us() + 5000000;
  while ((g_srv_chunks.load() < 100 || g_srv_closed.load() < 1) &&
         monotonic_time_us() < deadline) {
    usleep(10000);
  }
  EXPECT_EQ(g_srv_chunks.load(), 100);
  EXPECT_EQ(g_srv_bytes.load(), 100 * 1008);
  EXPECT(g_srv_order_ok.load());     // strict arrival order
  EXPECT_EQ(g_srv_closed.load(), 1);  // close propagated
  EXPECT(!StreamExists(sid));
}

TEST_CASE(flow_control_backpressure) {
  start_once();
  g_srv_bytes = 0;
  g_srv_chunks = 0;
  g_srv_last_seq = 0;
  g_srv_order_ok = true;
  g_srv_closed = 0;
  g_consume_delay_us = 20000;  // slow consumer: 20ms/chunk

  Channel ch;
  EXPECT_EQ(ch.Init("127.0.0.1:" + std::to_string(g_port)), 0);
  Controller cntl;
  StreamId sid = 0;
  StreamOptions copts;
  copts.window_bytes = 256 * 1024;
  EXPECT_EQ(StreamCreate(&sid, &cntl, copts), 0);
  IOBuf req, resp;
  req.append("open");
  ch.CallMethod("Stream.Open", req, &resp, &cntl);
  EXPECT(!cntl.Failed());

  // 40 chunks of 64KB = 2.5MB >> default 2MB server window with a slow
  // consumer: the writer MUST be throttled (not instant).
  static StreamId s_sid2;
  s_sid2 = sid;
  static std::atomic<int64_t> write_time_us{0};
  fiber_t writer;
  fiber_start(&writer, [](void*) {
    const int64_t t0 = monotonic_time_us();
    for (uint64_t seq = 1; seq <= 40; ++seq) {
      IOBuf chunk;
      chunk.append(&seq, 8);
      chunk.append(std::string(64 * 1024 - 8, 'f'));
      EXPECT_EQ(StreamWrite(s_sid2, std::move(chunk)), 0);
    }
    write_time_us.store(monotonic_time_us() - t0);
    StreamClose(s_sid2);
  }, nullptr);
  fiber_join(writer);

  const int64_t deadline = monotonic_time_us() + 10000000;
  while (g_srv_chunks.load() < 40 && monotonic_time_us() < deadline) {
    usleep(10000);
  }
  EXPECT_EQ(g_srv_chunks.load(), 40);
  EXPECT(g_srv_order_ok.load());
  // 40 chunks × 20ms consume = 800ms total; a writer outpacing a 2MB window
  // (32 chunks) must have been blocked for a good fraction of that.
  EXPECT(write_time_us.load() > 100000);
  g_consume_delay_us = 0;
}

TEST_CASE(chunk_wider_than_the_window_goes_and_the_next_waits_for_it) {
  // Upstream's admission (AppendIfNotFull): a 4 MB chunk on the 2 MB
  // default window is admitted at once, because the window is not
  // exhausted; it overruns the credit, so the second is held until the
  // first has been consumed (its on_message returned) and acknowledged.
  start_once();
  g_srv_bytes = 0;
  g_srv_chunks = 0;
  g_srv_last_seq = 0;
  g_srv_order_ok = true;
  g_consume_delay_us = 300000;  // the first chunk is "in use" for 0.3 s

  Channel ch;
  EXPECT_EQ(ch.Init("127.0.0.1:" + std::to_string(g_port)), 0);
  Controller cntl;
  StreamId sid = 0;
  EXPECT_EQ(StreamCreate(&sid, &cntl, StreamOptions{}), 0);
  IOBuf req, resp;
  req.append("open");
  ch.CallMethod("Stream.Open", req, &resp, &cntl);
  EXPECT(!cntl.Failed());
  EXPECT_EQ(stream_send_window(sid), 2u * 1024 * 1024);

  static StreamId s_sid;
  s_sid = sid;
  static std::atomic<int64_t> first_us{0};
  static std::atomic<int64_t> second_us{0};
  static std::atomic<uint64_t> credit_between{1};
  fiber_t writer;
  fiber_start(&writer, [](void*) {
    const size_t kChunk = 4u * 1024 * 1024;
    for (uint64_t seq = 1; seq <= 2; ++seq) {
      IOBuf chunk;
      chunk.append(&seq, 8);
      chunk.append(std::string(kChunk - 8, 'w'));
      const int64_t t0 = monotonic_time_us();
      EXPECT_EQ(StreamWrite(s_sid, std::move(chunk)), 0);
      (seq == 1 ? first_us : second_us).store(monotonic_time_us() - t0);
      if (seq == 1) {
        credit_between.store(stream_send_window(s_sid));
      }
    }
  }, nullptr);
  fiber_join(writer);
  const int64_t deadline = monotonic_time_us() + 5000000;
  while (g_srv_chunks.load() < 2 && monotonic_time_us() < deadline) {
    usleep(10000);
  }
  EXPECT_EQ(g_srv_chunks.load(), 2);
  EXPECT_EQ(g_srv_bytes.load(), 2 * 4 * 1024 * 1024);
  EXPECT(g_srv_order_ok.load());
  EXPECT(first_us.load() < 150000);    // admitted without a wait
  EXPECT_EQ(credit_between.load(), 0u);  // overrun: reads as none left
  EXPECT(second_us.load() > 200000);   // held until the first was consumed
  g_consume_delay_us = 0;
  StreamClose(sid);
}

namespace {
std::atomic<uint64_t> g_held_sid{0};
std::atomic<int64_t> g_held_bytes{0};
}  // namespace

TEST_CASE(a_consumer_that_only_takes_delivery_holds_the_writer) {
  // credit_on_consumed: on_message queues and returns, and nothing goes
  // back to the writer until StreamConsumed.  The writer stops after
  // window + less than one chunk, that is what the receiver holds unread,
  // and giving the bytes back starts it again.
  Server srv;
  srv.RegisterMethod(
      "Stream.Hold", [](Controller* cntl, const IOBuf&, IOBuf* resp,
                        Closure done) {
        StreamOptions opts;
        opts.window_bytes = 256 * 1024;
        opts.credit_on_consumed = true;
        opts.on_message = [](StreamId, IOBuf&& chunk) {
          g_held_bytes.fetch_add(static_cast<int64_t>(chunk.size()));
        };
        opts.on_closed = [](StreamId sid) { StreamClose(sid); };
        StreamId sid = 0;
        EXPECT_EQ(StreamAccept(&sid, cntl, opts), 0);
        g_held_sid.store(sid);
        resp->append("accepted");
        done();
      });
  EXPECT_EQ(srv.Start(0), 0);
  Channel ch;
  EXPECT_EQ(ch.Init("127.0.0.1:" + std::to_string(srv.port())), 0);
  Controller cntl;
  StreamId sid = 0;
  EXPECT_EQ(StreamCreate(&sid, &cntl, StreamOptions{}), 0);
  IOBuf req, resp;
  req.append("open");
  ch.CallMethod("Stream.Hold", req, &resp, &cntl);
  EXPECT(!cntl.Failed());

  static StreamId s_sid;
  s_sid = sid;
  static std::atomic<int> written{0};
  written = 0;
  const int64_t kChunk = 100 * 1024;
  fiber_t writer;
  fiber_start(&writer, [](void*) {
    for (int i = 0; i < 8; ++i) {
      IOBuf chunk;
      chunk.append(std::string(100 * 1024, 'h'));
      if (StreamWrite(s_sid, std::move(chunk)) != 0) {
        return;
      }
      written.fetch_add(1);
    }
  }, nullptr);
  // 256 KB of window admit three chunks of 100 KB (the third overruns).
  int64_t deadline = monotonic_time_us() + 3000000;
  while (g_held_bytes.load() < 3 * kChunk && monotonic_time_us() < deadline) {
    usleep(10000);
  }
  usleep(300000);  // a fourth would have come by now
  EXPECT_EQ(written.load(), 3);
  EXPECT_EQ(g_held_bytes.load(), 3 * kChunk);
  const StreamId held = g_held_sid.load();
  EXPECT_EQ(stream_unread_high_water(held),
            static_cast<uint64_t>(3 * kChunk));
  EXPECT(stream_unread_high_water(held) <
         static_cast<uint64_t>(256 * 1024 + kChunk));
  // The application takes them: the writer goes on, and stops again.
  EXPECT_EQ(StreamConsumed(held, static_cast<size_t>(3 * kChunk)), 0);
  deadline = monotonic_time_us() + 3000000;
  while (written.load() < 6 && monotonic_time_us() < deadline) {
    usleep(10000);
  }
  usleep(300000);
  EXPECT_EQ(written.load(), 6);
  EXPECT_EQ(StreamConsumed(held, static_cast<size_t>(5 * kChunk)), 0);
  fiber_join(writer);
  EXPECT_EQ(written.load(), 8);
  EXPECT_EQ(stream_unread_high_water(held),
            static_cast<uint64_t>(3 * kChunk));
  EXPECT_EQ(StreamConsumed(0, 1), EINVAL);
  StreamClose(sid);
  srv.Stop();
  srv.Join();
}

TEST_CASE(write_without_stream_fails) {
  EXPECT_EQ(StreamWrite(0, IOBuf()), EINVAL);
  EXPECT_EQ(StreamWrite((0xdeadull << 33) | 1, IOBuf()), EINVAL);
  EXPECT_EQ(StreamClose(0), EINVAL);
  EXPECT_EQ(StreamWait(0), 0);
}

TEST_CASE(accept_without_offer_fails) {
  start_once();
  Channel ch;
  EXPECT_EQ(ch.Init("127.0.0.1:" + std::to_string(g_port)), 0);
  // Register a method that tries to accept when nothing was offered.
  // (Covered implicitly: call Stream.Open WITHOUT StreamCreate.)
  Controller cntl;
  IOBuf req, resp;
  req.append("open");
  ch.CallMethod("Stream.Open", req, &resp, &cntl);
  EXPECT(!cntl.Failed());
  EXPECT(resp.to_string() == "no-stream");
}

namespace {
// Per-stream tallies for the batch case (indexed by arrival marker).
std::atomic<int> g_batch_counts[3];
std::atomic<int> g_batch_accepted{0};
}  // namespace

TEST_CASE(stream_batch_create_accept) {
  // One RPC establishes THREE streams (StreamIds parity); each relays
  // its own ordered chunks, and windows are per stream.  A dedicated
  // server: methods cannot register on the running shared one.
  Server srv;
  srv.RegisterMethod(
      "Stream.OpenBatch", [](Controller* cntl, const IOBuf&, IOBuf* resp,
                             Closure done) {
        StreamOptions opts;
        opts.on_message = [](StreamId, IOBuf&& chunk) {
          uint8_t lane = 0;
          chunk.copy_to(&lane, 1);
          if (lane < 3) {
            g_batch_counts[lane].fetch_add(1);
          }
        };
        opts.on_closed = [](StreamId sid) { StreamClose(sid); };
        std::vector<StreamId> sids;
        if (StreamAcceptBatch(&sids, cntl, opts) != 0) {
          resp->append("no-stream");
          done();
          return;
        }
        g_batch_accepted.store(static_cast<int>(sids.size()));
        resp->append("accepted " + std::to_string(sids.size()));
        done();
      });
  EXPECT_EQ(srv.Start(0), 0);

  Channel ch;
  EXPECT_EQ(ch.Init("127.0.0.1:" + std::to_string(srv.port())), 0);
  Controller cntl;
  std::vector<StreamId> sids;
  EXPECT_EQ(StreamCreateBatch(&sids, 3, &cntl, StreamOptions{}), 0);
  EXPECT_EQ(sids.size(), 3u);
  IOBuf req, resp;
  req.append("open");
  ch.CallMethod("Stream.OpenBatch", req, &resp, &cntl);
  EXPECT(!cntl.Failed());
  EXPECT(resp.to_string() == "accepted 3");
  EXPECT_EQ(g_batch_accepted.load(), 3);

  // Each lane writes chunks tagged with its index.
  static std::vector<StreamId> s_sids;
  s_sids = sids;
  fiber_t writers[3];
  for (int lane = 0; lane < 3; ++lane) {
    fiber_start(&writers[lane], [](void* arg) {
      const int lane = static_cast<int>(reinterpret_cast<intptr_t>(arg));
      for (int i = 0; i < 10 + lane; ++i) {
        IOBuf chunk;
        const uint8_t tag = static_cast<uint8_t>(lane);
        chunk.append(&tag, 1);
        chunk.append("payload");
        EXPECT_EQ(StreamWrite(s_sids[lane], std::move(chunk)), 0);
      }
      StreamClose(s_sids[lane]);
    }, reinterpret_cast<void*>(static_cast<intptr_t>(lane)));
  }
  for (auto& w : writers) {
    fiber_join(w);
  }
  const int64_t deadline = monotonic_time_us() + 5000000;
  while ((g_batch_counts[0].load() < 10 || g_batch_counts[1].load() < 11 ||
          g_batch_counts[2].load() < 12) &&
         monotonic_time_us() < deadline) {
    usleep(10000);
  }
  EXPECT_EQ(g_batch_counts[0].load(), 10);
  EXPECT_EQ(g_batch_counts[1].load(), 11);
  EXPECT_EQ(g_batch_counts[2].load(), 12);
  srv.Stop();
  srv.Join();
}

namespace {
Closure g_parked_done;  // released at test end so Stop/Join can drain
}

TEST_CASE(failed_call_closes_offered_streams) {
  // A timed-out call must close its offered streams (all lanes), or
  // batch writers park in the establishment wait forever.
  Server srv;
  srv.RegisterMethod("Stream.Never",
                     [](Controller*, const IOBuf&, IOBuf*, Closure done) {
                       g_parked_done = std::move(done);  // never answers
                     });
  EXPECT_EQ(srv.Start(0), 0);
  Channel ch;
  EXPECT_EQ(ch.Init("127.0.0.1:" + std::to_string(srv.port())), 0);
  Controller cntl;
  cntl.set_timeout_ms(200);
  std::vector<StreamId> sids;
  EXPECT_EQ(StreamCreateBatch(&sids, 2, &cntl, StreamOptions{}), 0);
  IOBuf req, resp;
  req.append("open");
  ch.CallMethod("Stream.Never", req, &resp, &cntl);
  EXPECT(cntl.Failed());
  EXPECT(!StreamExists(sids[0]));
  EXPECT(!StreamExists(sids[1]));
  IOBuf c;
  c.append("x");
  EXPECT_EQ(StreamWrite(sids[0], std::move(c)), EINVAL);
  if (g_parked_done) {
    g_parked_done();  // let the server drain
  }
  srv.Stop();
  srv.Join();
}

TEST_CASE(unaccepted_batch_offers_close_promptly) {
  // A handler that uses plain StreamAccept (or none at all) must not
  // leave the client's extra offers hanging: they close on response and
  // writers get EPIPE instead of a 10s establishment park.
  start_once();  // Stream.Open accepts exactly ONE stream
  Channel ch;
  EXPECT_EQ(ch.Init("127.0.0.1:" + std::to_string(g_port)), 0);
  Controller cntl;
  std::vector<StreamId> sids;
  EXPECT_EQ(StreamCreateBatch(&sids, 3, &cntl, StreamOptions{}), 0);
  IOBuf req, resp;
  req.append("open");
  ch.CallMethod("Stream.Open", req, &resp, &cntl);
  EXPECT(!cntl.Failed());
  // First lane established and usable...
  IOBuf chunk;
  uint64_t seq = g_srv_last_seq.load() + 1;
  chunk.append(&seq, 8);
  EXPECT_EQ(StreamWrite(sids[0], std::move(chunk)), 0);
  // ...lanes 1-2 were never accepted: closed-and-destroyed with the
  // response (EINVAL = id gone), not a 10s establishment park.
  const int64_t t0 = monotonic_time_us();
  IOBuf c1, c2;
  c1.append("x");
  c2.append("x");
  EXPECT(!StreamExists(sids[1]));
  EXPECT(!StreamExists(sids[2]));
  EXPECT_EQ(StreamWrite(sids[1], std::move(c1)), EINVAL);
  EXPECT_EQ(StreamWrite(sids[2], std::move(c2)), EINVAL);
  EXPECT(monotonic_time_us() - t0 < 2000000);
  StreamClose(sids[0]);
}

// ---- a wide chunk rides the connection's one-sided window -----------------

namespace {

int64_t exposed(const char* name) {
  std::string out;
  EXPECT(Variable::read_exposed(name, &out));
  return strtoll(out.c_str(), nullptr, 10);
}

// A chunk of `n` bytes that says which it is: its sequence number, then a
// pattern salted with it, so a chunk out of place or put at a wrong offset
// differs.
IOBuf numbered_chunk(uint64_t seq, size_t n) {
  std::string s(n, 0);
  for (size_t i = 0; i < n; ++i) {
    s[i] = static_cast<char>(((i + seq) * 2654435761u) >> 13);
  }
  memcpy(&s[0], &seq, std::min<size_t>(8, n));
  IOBuf chunk;
  chunk.append(s);
  return chunk;
}

// What the keeping server saw.
std::mutex g_kept_mu;
std::vector<IOBuf> g_kept;            // guarded by g_kept_mu
std::atomic<int> g_kept_chunks{0};
std::atomic<bool> g_kept_exact{true};
std::atomic<int> g_kept_closed{0};
std::atomic<bool> g_keep_parked{false};  // on_message parks while set
std::atomic<uint64_t> g_keep_sid{0};

// Stream.Keep: accepts with a 32 MB window and only takes delivery: every
// chunk is checked against numbered_chunk(arrival order) and kept, so a
// chunk that came as a window span holds its span until the test lets go.
Server* keeping_server() {
  static Server* srv = [] {
    auto* s = new Server();
    s->RegisterMethod(
        "Stream.Keep", [](Controller* cntl, const IOBuf&, IOBuf* resp,
                          Closure done) {
          StreamOptions opts;
          opts.window_bytes = 32 << 20;
          opts.credit_on_consumed = true;
          opts.on_message = [](StreamId, IOBuf&& chunk) {
            while (g_keep_parked.load()) {
              fiber_sleep_us(1000);
            }
            // Calls are serialized per stream: the count is the order.
            const uint64_t seq =
                static_cast<uint64_t>(g_kept_chunks.load()) + 1;
            const std::string want =
                numbered_chunk(seq, chunk.size()).to_string();
            if (!chunk.equals(want.data(), want.size())) {
              g_kept_exact.store(false);
            }
            {
              std::lock_guard<std::mutex> g(g_kept_mu);
              g_kept.push_back(std::move(chunk));
            }
            g_kept_chunks.fetch_add(1);
          };
          opts.on_closed = [](StreamId sid) {
            g_kept_closed.fetch_add(1);
            StreamClose(sid);
          };
          StreamId sid = 0;
          EXPECT_EQ(StreamAccept(&sid, cntl, opts), 0);
          g_keep_sid.store(sid);
          resp->append("accepted");
          done();
        });
    EXPECT_EQ(s->Start(0), 0);
    return s;
  }();
  return srv;
}

void forget_kept() {
  std::lock_guard<std::mutex> g(g_kept_mu);
  g_kept.clear();
}

// Opens a stream to Stream.Keep over the shm ring; the connection is new,
// so its windows are sized by the flags as they are now.
StreamId open_kept(Channel* ch, bool checksum) {
  g_kept_chunks = 0;
  g_kept_exact = true;
  g_kept_closed = 0;
  Channel::Options opts;
  opts.use_shm = true;
  opts.timeout_ms = 20000;
  EXPECT_EQ(ch->Init("127.0.0.1:" + std::to_string(keeping_server()->port()),
                     &opts),
            0);
  Controller cntl;
  cntl.set_enable_checksum(checksum);
  StreamId sid = 0;
  EXPECT_EQ(StreamCreate(&sid, &cntl, StreamOptions{}), 0);
  IOBuf req, resp;
  req.append("open");
  ch->CallMethod("Stream.Keep", req, &resp, &cntl);
  EXPECT(!cntl.Failed());
  EXPECT(ch->transport_name() == "shm_ring");
  return sid;
}

bool wait_until(const std::function<bool()>& cond, int64_t timeout_us) {
  const int64_t deadline = monotonic_time_us() + timeout_us;
  while (!cond() && monotonic_time_us() < deadline) {
    usleep(5000);
  }
  return cond();
}

struct FaultGuard {
  ~FaultGuard() { FaultActor::global().set(""); }
};

}  // namespace

TEST_CASE(wide_chunks_ride_the_window_and_keep_their_place_among_narrow_ones) {
  Channel ch;
  const StreamId sid = open_kept(&ch, /*checksum=*/false);
  const int64_t one_sided0 = exposed("stream_one_sided_bytes");
  const int64_t written0 = exposed("stream_bytes_written");
  const int64_t rma0 = exposed("rma_tx_bytes");
  const int64_t full0 = exposed("rma_window_full");
  const size_t widths[] = {4u << 20, 1024,       1u << 20, 4u << 20,
                           3u << 20, 1024,       2u << 20, (2u << 20) + 8,
                           8,        4u << 20};
  int64_t all = 0, wide = 0;
  uint64_t seq = 0;
  for (size_t n : widths) {
    EXPECT_EQ(StreamWrite(sid, numbered_chunk(++seq, n)), 0);
    all += static_cast<int64_t>(n);
    wide += n > (2u << 20) ? static_cast<int64_t>(n) : 0;  // over, not at
  }
  EXPECT(wait_until([&] { return g_kept_chunks.load() == 10; }, 5000000));
  EXPECT(g_kept_exact.load());
  EXPECT_EQ(exposed("stream_bytes_written") - written0, all);
  EXPECT_EQ(exposed("stream_one_sided_bytes") - one_sided0, wide);
  EXPECT_EQ(exposed("rma_tx_bytes") - rma0, wide);
  EXPECT_EQ(exposed("rma_window_full") - full0, 0);
  // Five spans lie kept, in 4 MB slots: a span header and 4 MB are two,
  // the 3 MB and the 2 MB + 8 chunks one each.
  EXPECT_EQ(rma_spans_in_use(), 8u);
  EXPECT_EQ(stream_unread_high_water(g_keep_sid.load()),
            static_cast<uint64_t>(all));
  forget_kept();  // each span's deleter runs with its chunk
  EXPECT_EQ(rma_spans_in_use(), 0u);
  StreamClose(sid);
  EXPECT(wait_until([] { return g_kept_closed.load() == 1; }, 3000000));
}

TEST_CASE(a_checksummed_stream_refuses_a_corrupted_span_and_closes) {
  // The call that opens the stream asks for checksums: an in-band frame
  // carries its payload's, a one-sided transfer one a chunk in the span's
  // header.  One flipped byte in the window fails rma_resolve; a unary
  // call would time out alone, a stream closes: the chunk behind the
  // refused one is never delivered in its place.
  Channel ch;
  const StreamId sid = open_kept(&ch, /*checksum=*/true);
  EXPECT_EQ(StreamWrite(sid, numbered_chunk(1, 1024)), 0);
  EXPECT_EQ(StreamWrite(sid, numbered_chunk(2, 4u << 20)), 0);
  EXPECT(wait_until([] { return g_kept_chunks.load() == 2; }, 5000000));
  EXPECT(g_kept_exact.load());
  const int64_t rejected0 = exposed("rma_rejected");
  {
    FaultGuard guard;
    // The first decision after this is a rail's first chunk: the frames
    // of a quiet connection are all written.
    EXPECT_EQ(FaultActor::global().set("seed=3;corrupt=1.0;max=1"), 0);
    EXPECT_EQ(StreamWrite(sid, numbered_chunk(3, 4u << 20)), 0);
    EXPECT(wait_until(
        [&] { return exposed("rma_rejected") == rejected0 + 1; }, 5000000));
  }
  EXPECT(wait_until([] { return g_kept_closed.load() == 1; }, 3000000));
  // The writer learns: the refusing end sent a CLOSE.
  EXPECT(wait_until(
      [&] { return StreamWrite(sid, numbered_chunk(4, 1024)) == EPIPE; },
      3000000));
  EXPECT_EQ(g_kept_chunks.load(), 2);
  EXPECT(g_kept_exact.load());
  forget_kept();
  EXPECT_EQ(rma_spans_in_use(), 0u);  // the refused span was given back
  StreamClose(sid);
}

TEST_CASE(a_close_drops_the_spans_still_queued_behind_the_consumer) {
  // The consumer is inside on_message with the first chunk while two more
  // wait in the stream's queue, each a span of the receive window.  The
  // receiving end closes: the queue stops, the queued chunks are dropped
  // and every span's deleter runs; nothing of the window stays allocated.
  Channel ch;
  const StreamId sid = open_kept(&ch, /*checksum=*/false);
  g_keep_parked = true;
  for (uint64_t seq = 1; seq <= 3; ++seq) {
    EXPECT_EQ(StreamWrite(sid, numbered_chunk(seq, 4u << 20)), 0);
  }
  const StreamId kept = g_keep_sid.load();
  EXPECT(wait_until(
      [&] { return stream_unread_high_water(kept) == 3u * (4u << 20); },
      5000000));
  EXPECT_EQ(rma_spans_in_use(), 6u);
  EXPECT_EQ(StreamClose(kept), 0);
  g_keep_parked = false;
  // The one the consumer had in hand is the application's to let go of.
  EXPECT(wait_until([] { return rma_spans_in_use() == 2; }, 5000000));
  EXPECT_EQ(g_kept_chunks.load(), 1);
  forget_kept();
  EXPECT_EQ(rma_spans_in_use(), 0u);
  // The writer's end reads the CLOSE.
  EXPECT(wait_until(
      [&] { return StreamWrite(sid, numbered_chunk(9, 8)) != 0; }, 3000000));
  StreamClose(sid);
}

TEST_MAIN
