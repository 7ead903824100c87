// Collectives as RPC — group put schedules over the RMA fabric +
// array-resharding service (ISSUE 13 tentpole).
//
// No brpc parity: the reference stops at point-to-point channels.  This
// layer expresses all-gather / reduce-scatter / all-to-all — and generic
// array redistribution between arbitrary shardings — as *planned sets of
// one-sided RMA puts* over the shm/ICI mesh: every transfer in a
// TransferSchedule is a Coll.Put RPC whose MB-scale body rides the PR 10
// one-sided plane (multi-rail chunked puts into the peer's registered
// window, completion-bitmap + per-chunk CRC verification), and the RPC
// response is the tiny per-put control/ack frame.  Chunking follows T3
// (arXiv 2401.16677): each transfer is cut into trpc_coll_chunk_bytes
// chunks issued trpc_coll_inflight deep, so member i's step k+1 puts
// overlap member j's step k verification — there is no global barrier,
// only the data dependencies the ring schedules impose.  The resharding
// planner applies the portable-collectives decomposition of
// "Memory-efficient array redistribution" (arXiv 2112.01075): the
// redistribution factors into a put set that moves ONLY the bytes whose
// owner changes, reusing locally-resident ranges instead of re-fetching
// them — strictly fewer bytes than a naive full-exchange whenever the
// shardings overlap.
//
// Model:
//  - A GROUP is an ordered member list (explicit, or snapshotted from a
//    naming:// view at Init: members sorted by address so every process
//    derives the same rank order; kEDraining members have withdrawn and
//    are excluded by construction).  The naming VERSION is part of the
//    snapshot: an epoch change mid-schedule fails the current step
//    whole-or-nothing (kECollEpoch) — membership never changes under a
//    running schedule.
//  - A TransferSchedule is compiled deterministically from (op, nmembers,
//    shard bytes) — or from source/target shardings for reshard — so
//    every member compiles the identical plan and no coordinator exists.
//    Steps are the unit of fault atomicity: a dropped/corrupted chunk
//    (whole-or-nothing per put, inherited from the RMA/stripe planes)
//    fails that step for the WHOLE group — the executor aborts its peers
//    (Coll.Abort) and run() fails; a failed run's recv/accumulator
//    buffers are undefined-by-contract, and no step that REPORTED
//    success ever contains torn bytes (a shard is complete only when
//    every chunk landed whole).
//  - Execution is symmetric: every member calls run() with its rank's
//    buffers.  Receives land through the Coll.Put handler, which places
//    each chunk at its offset in the registered destination buffer (or
//    element-wise u32-adds it, for reduce steps) and wakes the local
//    executor's per-step countdown.
//
// The resharding *service* (Reshard.Plan / Reshard.Execute) attaches to
// any Server like the KV registry.  Plan is stateless: shardings in,
// {bytes_moved, bytes_reused, naive_bytes, steps} out.  Execute turns the
// PR 11 KV registry into group-transfer machinery: each member's source
// shard is addressed as a published KV block (src_block_base + rank); the
// handler pins the block's registered pages, allocates a fresh
// exportable region for its target shard, runs the planned schedule over
// the fabric with its peers, and re-publishes the result as
// dst_block_base + rank — a coordinator fans personalized Execute
// requests to the members and the array is resharded in place on the
// fleet.
#pragma once

#include <atomic>
#include <cstdint>
#include <memory>
#include <string>
#include <vector>

#include "base/iobuf.h"

namespace trpc {

class Channel;
class Server;

// Error codes, continuing the 2101..2112 family (kvstore.h, naming.h).
// kECollAbort: a peer failed its part of the step (or the local step
// timed out) — the whole step failed, the run is dead.  kECollEpoch: the
// group's naming view changed mid-schedule; recompile the group and
// re-run.  kECollMismatch: buffer sizes / shardings do not fit the plan.
constexpr int kECollAbort = 2121;
constexpr int kECollEpoch = 2122;
constexpr int kECollMismatch = 2123;

// Method names (tstd, served by coll_attach).  Copy transfers are
// PULL-based (Coll.Get): the destination issues the RPC with its
// registered buffer slice as the landing target, so the serving member
// puts the bytes straight into the getter's memory through the
// direct-landing plane — ONE multi-rail memcpy end to end.  Reduce
// transfers stay PUSH-based (Coll.Put): the receiver's handler folds
// the payload into its accumulator.
inline constexpr const char* kCollPutMethod = "Coll.Put";
inline constexpr const char* kCollGetMethod = "Coll.Get";
inline constexpr const char* kCollAbortMethod = "Coll.Abort";
inline constexpr const char* kReshardPlanMethod = "Reshard.Plan";
inline constexpr const char* kReshardExecuteMethod = "Reshard.Execute";

enum class CollOp : uint32_t {
  kAllGather = 1,
  kReduceScatter = 2,
  kAllToAll = 3,
  kReshard = 4,
};
const char* coll_op_name(CollOp op);

// ---- plans ---------------------------------------------------------------

// One planned put: `src` rank writes `len` bytes read from its local
// buffer at `src_off` into rank `dst`'s destination buffer at `dst_off`.
// src_from_recv: the bytes are read from the RECEIVE/accumulator buffer
// (ring forwarding) instead of the send buffer.  reduce: the receiver
// element-wise u32-adds instead of copying.
struct CollTransfer {
  uint32_t src = 0;
  uint32_t dst = 0;
  uint64_t src_off = 0;
  uint64_t dst_off = 0;
  uint64_t len = 0;
  bool src_from_recv = false;
  bool reduce = false;
};

// One schedule step: the unit of whole-or-nothing fault semantics.  A
// member may proceed to step k+1 only when its step-k sends are acked
// AND its step-k receives landed (the data dependency the ring imposes).
struct CollStep {
  std::vector<CollTransfer> puts;
};

struct TransferSchedule {
  CollOp op = CollOp::kAllGather;
  uint32_t nmembers = 0;
  uint64_t shard_bytes = 0;  // per-member shard size (0 for reshard)
  std::vector<CollStep> steps;
  // Local memcpys (src rank == dst rank): executed in place, never sent.
  std::vector<CollTransfer> local_copies;
  // Local memcpys applied AFTER the last step (reduce_scatter moves the
  // fully-reduced chunk from the accumulator into recvbuf here).
  std::vector<CollTransfer> final_copies;
  // Bytes the schedule moves over the fabric (sum of cross-member puts).
  uint64_t bytes_moved() const;
  // Bytes reused in place (local copies — the 2112.01075 win).
  uint64_t bytes_reused() const;
};

// ---- input dependencies (overlap-aware execution) ------------------------

// The send-buffer range a transfer READS, compiled from the plan: with a
// readiness map attached (see run() `ready`), the transfer may fire as
// soon as this range is stamped instead of waiting for the whole-buffer
// barrier.  len == 0 means no send-buffer input (the transfer reads the
// receive/accumulator buffer — ring forwarding — whose readiness the
// step barrier already orders).
struct CollDep {
  uint64_t off = 0;
  uint64_t len = 0;
};

// Input dependency of one transfer as executed by rank `src`: its
// send-buffer source range unless src_from_recv.
CollDep transfer_input_dep(const CollTransfer& t);

// Max send-buffer extent (off+len) rank `rank` reads anywhere in the
// plan — the range the barrier path waits on when a readiness map is
// attached with overlap disabled (byte-identical semantics, single
// wait).  0 when the rank never reads its send buffer.
uint64_t plan_producer_extent(const TransferSchedule& plan, uint32_t rank);

// Deterministic ring/pairwise planners — every member compiles the same
// plan from the same arguments.
//   all_gather:     send = shard, recv = n*shard; n-1 ring steps.
//   reduce_scatter: send = n*shard (MUTATED: it is the accumulator),
//                   recv = shard; element type u32, op = add.
//   all_to_all:     send = n*shard (block j for rank j), recv = n*shard
//                   (block i from rank i); n-1 pairwise rounds.
TransferSchedule plan_all_gather(uint32_t nmembers, uint64_t shard_bytes);
TransferSchedule plan_reduce_scatter(uint32_t nmembers,
                                     uint64_t shard_bytes);
TransferSchedule plan_all_to_all(uint32_t nmembers, uint64_t shard_bytes);

// ---- resharding ----------------------------------------------------------

// 1-D sharding descriptor: `total` global bytes covered by disjoint
// ranges, each owned by one member rank.  A rank's LOCAL buffer is its
// ranges concatenated in ascending global offset.
struct ShardRange {
  uint32_t rank = 0;
  uint64_t off = 0;
  uint64_t len = 0;
};
struct Sharding {
  uint64_t total = 0;
  std::vector<ShardRange> ranges;
};
// Validates coverage: ranges sorted+disjoint, covering [0, total), every
// rank < nmembers.
bool sharding_valid(const Sharding& s, uint32_t nmembers);
// Bytes of `rank`'s local buffer under `s`.
uint64_t sharding_local_bytes(const Sharding& s, uint32_t rank);

// Plans the minimal put set moving src-sharded data into dst's layout:
// bytes whose owner does not change become local_copies (reused), the
// rest become puts bucketed into (dst-src) mod n rounds so per-step
// fan-in is bounded.  Offsets in the transfers are LOCAL buffer offsets
// (send = src layout, recv = dst layout).
TransferSchedule plan_reshard(const Sharding& src, const Sharding& dst,
                              uint32_t nmembers);
// The naive full-exchange baseline the plan must beat whenever the
// shardings overlap: every member ships its whole source shard to every
// other member (the all-gather-then-slice strawman).
uint64_t reshard_naive_bytes(const Sharding& src, uint32_t nmembers);

// ---- group ---------------------------------------------------------------

// Channels to a fixed member snapshot.  NOT thread-safe for concurrent
// run() calls on the same instance; every member must issue the same
// sequence of collectives (run_seq ties the wire to the call order).
class GroupChannel {
 public:
  struct Options {
    int64_t timeout_ms = 30000;  // per-put RPC budget AND step budget
    bool use_shm = true;         // shm rings (one-sided puts) to peers
  };

  ~GroupChannel();
  // Explicit member list.  `members[my_rank]` is this process's address;
  // all members must pass the SAME ordered list.  Returns 0 on success.
  int Init(const std::vector<std::string>& members, uint32_t my_rank,
           const Options* opts = nullptr);
  // Snapshot a naming:// view ("naming://registry_host:port/service"):
  // resolves the live member set (drained members have withdrawn and are
  // absent), sorts by address for a deterministic rank order, and
  // records the view VERSION — any later change fails the running step
  // kECollEpoch.  `self_addr` must be a member.  Returns 0 on success.
  int InitNaming(const std::string& naming_url, const std::string& self_addr,
                 const Options* opts = nullptr);

  // Runs one collective.  Buffer contracts per op (see the planners):
  // reduce_scatter MUTATES sendbuf (it is the ring accumulator).  The
  // caller owns both buffers and must keep them alive through the call;
  // a FAILED run leaves recvbuf (and, for reduce, sendbuf) undefined —
  // free or refill before reuse, exactly the RmaBuffer failed-call
  // contract.  run_seq must advance identically on every member; pass 0
  // to use the group's internal call counter.  Returns 0, kECollAbort,
  // kECollEpoch, kECollMismatch, or a transport errno.
  //
  // `ready` (optional): an rma_ready_create handle over THIS member's
  // sendbuf.  The caller stamps ranges as it fills them; transfers whose
  // compiled input dependency (transfer_input_dep) is stamped fire
  // immediately.  With trpc_coll_overlap off the executor instead waits
  // once for the full producer extent before executing the unchanged
  // barrier path — byte-identical results either way.  A producer that
  // never stamps trips the step deadline (whole-or-nothing abort), never
  // a wedge.  0 = no readiness gating (legacy barrier semantics).
  int run(const TransferSchedule& plan, const void* sendbuf,
          uint64_t send_len, void* recvbuf, uint64_t recv_len,
          uint64_t run_seq = 0, uint64_t ready = 0);

  // Convenience wrappers: compile + run.
  int all_gather(const void* sendbuf, uint64_t shard_bytes, void* recvbuf,
                 uint64_t recv_len);
  int reduce_scatter(void* sendbuf, uint64_t send_len, void* recvbuf,
                     uint64_t shard_bytes);
  int all_to_all(const void* sendbuf, uint64_t send_len, void* recvbuf,
                 uint64_t recv_len);
  int reshard(const Sharding& src, const Sharding& dst, const void* sendbuf,
              uint64_t send_len, void* recvbuf, uint64_t recv_len,
              uint64_t run_seq = 0);

  uint32_t my_rank() const { return my_rank_; }
  uint32_t nmembers() const { return static_cast<uint32_t>(members_.size()); }
  const std::vector<std::string>& members() const { return members_; }
  uint64_t group_id() const { return group_id_; }
  uint64_t naming_version() const { return naming_version_; }

 private:
  int init_channels(const Options* opts);
  // Naming-backed groups: re-resolves the view and fails (kECollEpoch)
  // when the version moved.  Explicit groups always pass.
  int check_epoch();

  std::vector<std::string> members_;
  uint32_t my_rank_ = 0;
  uint64_t group_id_ = 0;
  Options opts_;
  std::vector<std::unique_ptr<Channel>> chans_;  // [rank]; null for self
  // Naming snapshot (empty registry addr = explicit group).
  std::string naming_registry_;
  std::string naming_service_;
  std::unique_ptr<Channel> naming_ch_;
  uint64_t naming_version_ = 0;
  uint64_t run_counter_ = 0;
};

// Attaches the native handlers (Coll.Put, Coll.Abort, Reshard.Plan,
// Reshard.Execute) to a not-yet-started server.  Any member of any group
// must serve this; Reshard.Plan may also run on a node that stores
// nothing.  Returns 0, or -1 when a registration was refused.
int coll_attach(Server* s);

// Flag registration (idempotent): trpc_coll_chunk_bytes,
// trpc_coll_inflight, trpc_coll_rendezvous_ms,
// trpc_coll_ready_granularity_bytes, trpc_coll_overlap — the capi calls
// it so /flags sees the knobs before first traffic.
void coll_ensure_registered();

// Current trpc_coll_ready_granularity_bytes value (the default chunk
// granularity for readiness maps created through the C API).
uint64_t coll_ready_default_granularity();

// ---- wire ----------------------------------------------------------------

// Coll.Put / Coll.Abort header (fixed little-endian, 80 bytes; mirrored
// by brpc_tpu/rpc/collective.py _PUT_WIRE — coll-wire marker).  The put
// payload (len bytes) follows the header in the request body, so the
// whole body rides the one-sided plane when it clears the stripe
// threshold.  Abort sends the header alone (step = failing step, flags =
// error code).
// Shared by Coll.Put (push: header + payload in the body), Coll.Get
// (pull: header only — shard_off is the SOURCE-buffer offset to serve,
// the response body is the bytes) and Coll.Abort (header only, flags =
// error code).
struct CollPutWire {
  uint64_t group_id;
  uint64_t run_seq;
  uint32_t op;
  uint32_t src_rank;
  uint32_t step;
  uint32_t nchunks;    // chunks in this transfer (shard)
  uint32_t chunk;      // this chunk's index within the transfer
  uint32_t flags;      // bit 0: reduce-add-u32; bit 1 (Get): serve from
                       // the recv/forwarding buffer; Abort: error code
  uint64_t dst_off;    // destination-buffer offset of THIS chunk
  uint64_t len;        // payload bytes
  uint64_t shard_off;  // Put: dst offset of the whole transfer;
                       // Get: SOURCE-buffer offset to serve from
  uint64_t shard_len;  // bytes of the whole transfer
  // Sessions key on (group, run, rank): one process may host SEVERAL
  // members (in-process groups in tests/bench), and the serving handler
  // cannot tell which local member a connection belongs to — the wire
  // says so.  Put/Abort address dst_rank's session; Get addresses
  // src_rank's (the member being read).
  uint32_t dst_rank;
  uint32_t reserved;
};
static_assert(sizeof(CollPutWire) == 80, "CollPutWire is wire format");
constexpr uint32_t kCollFlagReduce = 1u << 0;
constexpr uint32_t kCollFlagFromRecv = 1u << 1;

// Reshard.Plan / Reshard.Execute header (fixed little-endian, 64 bytes;
// mirrored by brpc_tpu/rpc/collective.py _RESHARD_WIRE — coll-wire
// marker).  Followed by nmembers 64-byte address rows (Execute only;
// Plan sets nmembers to the rank count and sends no rows), then
// (nsrc + ndst) ShardRangeWire rows.  Plan responds with a
// ReshardPlanWire; Execute responds with {u64 dst_len, u64 generation}.
struct ReshardReqWire {
  uint64_t run_id;
  uint64_t src_block_base;  // Execute: kv block id of rank r's source
  uint64_t dst_block_base;  // Execute: block id to publish the result as
  uint64_t total;           // global array bytes
  uint32_t my_rank;         // Execute: the RECEIVER's rank (personalized)
  uint32_t nmembers;
  uint32_t nsrc;
  uint32_t ndst;
  uint32_t use_shm;
  uint32_t timeout_ms;
  uint64_t reserved;
};
static_assert(sizeof(ReshardReqWire) == 64, "ReshardReqWire is wire format");

struct ShardRangeWire {
  uint32_t rank;
  uint32_t reserved;
  uint64_t off;
  uint64_t len;
};
static_assert(sizeof(ShardRangeWire) == 24, "ShardRangeWire is wire format");

// Reshard.Plan response (fixed little-endian, 40 bytes; coll-wire
// marker, mirrored by collective.py _PLAN_WIRE).
struct ReshardPlanWire {
  uint64_t bytes_moved;
  uint64_t bytes_reused;
  uint64_t naive_bytes;
  uint32_t steps;
  uint32_t transfers;
  uint64_t reserved;
};
static_assert(sizeof(ReshardPlanWire) == 40, "ReshardPlanWire is wire format");

// Test/metrics support: receive sessions currently registered in this
// process (0 when no run is in flight — cancel/abort quiescence).
size_t coll_sessions_live();

}  // namespace trpc
