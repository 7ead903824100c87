// A numpy data-memory handler (NEP 49) that recycles large blocks: the
// host memory a device-to-host transfer lands in (brpc_tpu/rpc/zerocopy.py
// makes it the current handler only around the call that starts a
// transfer, so only a transfer's destination is allocated here).
//
// Why: glibc serves a block over its mmap threshold (32 MB at most) from
// mmap every time and returns it with munmap, and a non-main arena does
// so whatever M_MMAP_MAX says, so every 64 MB fetch wrote 16,384 pages the
// process had never touched (63-68 ms on the v5e host, against 12 ms for
// the bytes; PERF.md, PR 25 and PR 28).  A block numpy gives back is kept
// here, and the next request of the same size gets it: pages already
// faulted in, from whatever thread asks.
//
// What a block is (PR 34): from kMinPooledBytes to kMaxIdleBytes a
// registered region (net/rma.h, rma_alloc: shm-backed, under an rkey), so
// that the bytes a transfer landed are memory the KV store can publish
// from where they lie (KvStore::publish takes registered memory only;
// kv.py's _publish_records asks trpc_host_pool_holds and then copies
// nothing).  Every such block is in the pool's table from its creation to its
// rma_free, whoever has it: numpy, the idle list, or a reader.  Anything
// else (a smaller or a larger block, calloc's, one made when no region
// could be) is libc's and passes through to realloc and free, which is
// how free, realloc and trim tell the two apart: by the table.
//
// Who may have a block: a record published from it co-owns the region's
// mapping (KvStore's Block::map, and every response that serves its
// bytes), the way every reader of registered memory defers rma_free's
// munmap.  The pool reads the same count: a block numpy gives back while
// anybody but the registry and this table owns its mapping is parked
// (`parked`) and reaches the idle list only once they have let go, found
// at the pool's next call.  So a published record keeps its bytes until
// it is withdrawn, evicted or replaced, whatever became of the view and
// the array, and no transfer lands in a block that is being served.
//
// The idle list is bounded: past kMaxIdleBytes the block that has lain
// longest is freed.  The regions' shm names are unlinked when the process
// ends normally (atexit), as an RmaBuffer's are by its free.
//
// The same line (kMinPooledBytes) is the one zerocopy.py draws between a
// view whose transfer its waiter thread sees through and one it leaves to
// its caller, and the counters that say how a view's transfer went, and
// whether a landing block was a recycled one, are kept here beside it.
#include <stdint.h>
#include <stdlib.h>
#include <string.h>
#include <unistd.h>

#include <atomic>
#include <map>
#include <memory>
#include <mutex>
#include <vector>

#include "net/rma.h"
#include "stat/reducer.h"

namespace {

using trpc::Adder;
using trpc::RmaMapping;

constexpr size_t kMinPooledBytes = 1u << 20;
// What may lie idle: the blocks of one pipeline at depth 8 and 64 MB
// (depth + 2, 0.7 GB) are all given back at once when it drains.
constexpr size_t kMaxIdleBytes = 1ull << 30;
// Who owns the mapping of a block nobody reads: the region registry and
// the pool's table.  One owner more is a published record or a response on its
// way.
constexpr long kOwnersAtRest = 2;

struct Block {
  size_t size = 0;
  std::shared_ptr<RmaMapping> map;
};

// The pool's state lives as long as the process (leaked, as the counters
// below are): numpy frees through the handler until the interpreter is
// gone, and the atexit hook runs after that.
struct Pool {
  std::mutex mu;
  std::map<uintptr_t, Block> blocks;  // every region block, by address
  std::vector<void*> idle;            // in the order given back, oldest first
  size_t idle_bytes = 0;
  std::vector<void*> parked;          // given back while somebody reads them
  pid_t made_by = 0;                  // the process the atexit hook is for
};
Pool& g_pool = *new Pool();

struct HostViewVars {
  // A PendingView of zerocopy.py, noted once, by the first resolve() of a
  // thread other than the module's waiter (trpc_host_view_note).
  Adder view_bytes;
  Adder view_ahead_bytes;
  Adder view_wait_us;
  Adder view_transfer_us;
  // A landing block of kMinPooledBytes or more, as pool_malloc served it.
  Adder pool_hit_bytes;
  Adder pool_miss_bytes;
  HostViewVars() {
    view_bytes.expose("host_view_bytes",
                      "bytes of the device-to-host views whose bytes were "
                      "asked for (first resolve() by their caller)");
    view_ahead_bytes.expose("host_view_ahead_bytes",
                            "of host_view_bytes, the bytes that had landed "
                            "before they were first asked for");
    view_wait_us.expose("host_view_wait_us",
                        "time the first resolve() of each view blocked");
    view_transfer_us.expose("host_view_transfer_us",
                            "time from the request of each view's transfer "
                            "to its landing, as whoever waited for it saw");
    pool_hit_bytes.expose("host_pool_hit_bytes",
                          "landing blocks of 1 MB or more served from the "
                          "recycled list");
    pool_miss_bytes.expose("host_pool_miss_bytes",
                           "landing blocks of 1 MB or more made new: "
                           "fresh pages");
  }
};

// Exposed when the library loads, so that a window in which nobody asked
// for a view reads 0 and only a program without the counters reads nothing
// (the benchmark's readers tell the two apart); leaked with the registry,
// as the other capi counters are.
HostViewVars& g_vars = *new HostViewVars();

size_t size_of(void* ptr) {  // g_pool.mu held
  return g_pool.blocks.at(reinterpret_cast<uintptr_t>(ptr)).size;
}

bool at_rest(void* ptr) {  // g_pool.mu held
  const Block& b = g_pool.blocks.at(reinterpret_cast<uintptr_t>(ptr));
  if (b.map.use_count() > kOwnersAtRest) {
    return false;
  }
  // Acquire: the last reader's reads of the block happened before it let
  // go of the mapping, and so before whatever lands here next.
  std::atomic_thread_fence(std::memory_order_acquire);
  return true;
}

// Puts a block nobody reads on the idle list and takes off it what
// passes the bound, oldest first, into `evicted` (g_pool.mu held; the
// caller frees those outside the lock).
void lay_idle(void* ptr, std::vector<void*>* evicted) {
  g_pool.idle.push_back(ptr);
  g_pool.idle_bytes += size_of(ptr);
  while (g_pool.idle_bytes > kMaxIdleBytes) {
    void* oldest = g_pool.idle.front();
    g_pool.idle.erase(g_pool.idle.begin());
    g_pool.idle_bytes -= size_of(oldest);
    g_pool.blocks.erase(reinterpret_cast<uintptr_t>(oldest));
    evicted->push_back(oldest);
  }
}

// The parked blocks whose readers have gone, onto the idle list
// (g_pool.mu held).
void collect_parked(std::vector<void*>* evicted) {
  for (size_t i = 0; i < g_pool.parked.size();) {
    if (at_rest(g_pool.parked[i])) {
      lay_idle(g_pool.parked[i], evicted);
      g_pool.parked.erase(g_pool.parked.begin() +
                          static_cast<ptrdiff_t>(i));
    } else {
      ++i;
    }
  }
}

void free_regions(const std::vector<void*>& regions) {
  for (void* p : regions) {
    trpc::rma_free(p);  // unlink, and munmap of a large block: no lock held
  }
}

// When the process ends normally no region's name stays behind in
// /dev/shm.  The mappings stay (the table owns them): a thread that
// outlives the interpreter may still write into its block.
void unlink_at_exit() {
  std::lock_guard<std::mutex> lk(g_pool.mu);
  if (getpid() != g_pool.made_by) {
    return;  // a forked child: the names are its parent's
  }
  for (const auto& [address, block] : g_pool.blocks) {
    trpc::rma_free(reinterpret_cast<void*>(address));
  }
}

void* new_region(size_t size) {
  void* ptr = trpc::rma_alloc(size, nullptr);
  if (ptr == nullptr) {
    return nullptr;
  }
  Block b;
  b.size = size;
  b.map = trpc::rma_pin_exportable(ptr, size, nullptr, nullptr);
  std::lock_guard<std::mutex> lk(g_pool.mu);
  if (g_pool.made_by != getpid()) {
    g_pool.made_by = getpid();
    atexit(unlink_at_exit);
  }
  g_pool.blocks[reinterpret_cast<uintptr_t>(ptr)] = std::move(b);
  return ptr;
}

void* pool_malloc(void* /*ctx*/, size_t size) {
  if (size < kMinPooledBytes) {
    return malloc(size);
  }
  if (size > kMaxIdleBytes) {
    g_vars.pool_miss_bytes << static_cast<int64_t>(size);
    return malloc(size);  // never recycled, so libc's
  }
  std::vector<void*> evicted;
  void* hit = nullptr;
  {
    std::lock_guard<std::mutex> lk(g_pool.mu);
    collect_parked(&evicted);
    // Newest first: the block most likely still in the caches and TLB.
    for (size_t i = g_pool.idle.size(); i-- > 0;) {
      if (size_of(g_pool.idle[i]) == size) {
        hit = g_pool.idle[i];
        g_pool.idle.erase(g_pool.idle.begin() + static_cast<ptrdiff_t>(i));
        g_pool.idle_bytes -= size;
        break;
      }
    }
  }
  free_regions(evicted);
  if (hit != nullptr) {
    g_vars.pool_hit_bytes << static_cast<int64_t>(size);
    return hit;
  }
  g_vars.pool_miss_bytes << static_cast<int64_t>(size);
  void* fresh = new_region(size);
  // No region to be had (/dev/shm full): libc's block, freed by free.
  return fresh != nullptr ? fresh : malloc(size);
}

void* pool_calloc(void* /*ctx*/, size_t nelem, size_t elsize) {
  return calloc(nelem, elsize);
}

void pool_free(void* /*ctx*/, void* ptr, size_t /*size*/) {
  std::vector<void*> evicted;
  bool ours = false;
  {
    std::lock_guard<std::mutex> lk(g_pool.mu);
    ours = g_pool.blocks.count(reinterpret_cast<uintptr_t>(ptr)) != 0;
    if (ours) {
      g_pool.parked.push_back(ptr);  // and on to the idle list, if at rest
      collect_parked(&evicted);
    }
  }
  if (!ours) {
    free(ptr);
  }
  free_regions(evicted);
}

void* pool_realloc(void* ctx, void* ptr, size_t new_size) {
  size_t old_size = 0;
  {
    std::lock_guard<std::mutex> lk(g_pool.mu);
    auto it = g_pool.blocks.find(reinterpret_cast<uintptr_t>(ptr));
    if (it != g_pool.blocks.end()) {
      old_size = it->second.size;
    }
  }
  if (old_size == 0) {
    return realloc(ptr, new_size);  // libc's stays libc's
  }
  if (new_size == old_size) {
    return ptr;
  }
  void* moved = pool_malloc(ctx, new_size);
  if (moved != nullptr) {
    memcpy(moved, ptr, new_size < old_size ? new_size : old_size);
    pool_free(ctx, ptr, old_size);
  }
  return moved;
}

// numpy/ndarraytypes.h: PyDataMem_Handler, version 1 (numpy >= 1.22).
// Declared here so the runtime builds without Python's headers.
struct NumpyAllocator {
  void* ctx;
  void* (*malloc)(void* ctx, size_t size);
  void* (*calloc)(void* ctx, size_t nelem, size_t elsize);
  void* (*realloc)(void* ctx, void* ptr, size_t new_size);
  void (*free)(void* ctx, void* ptr, size_t size);
};
struct NumpyHandler {
  char name[127];
  uint8_t version;
  NumpyAllocator allocator;
};

NumpyHandler g_handler = {
    "trpc_host_pool",
    1,
    {nullptr, pool_malloc, pool_calloc, pool_realloc, pool_free},
};

}  // namespace

extern "C" {

// The handler, for a PyCapsule named "mem_handler" (PyDataMem_SetHandler).
// Lives as long as the library: arrays allocated through it free through it.
void* trpc_host_pool_numpy_handler() { return &g_handler; }

// The size from which a landing block is recycled, and from which a
// view's transfer is worth a thread's wake (zerocopy.py's waiter).
size_t trpc_host_pool_min_bytes() { return kMinPooledBytes; }

// One view's account, at the first resolve() by its caller: its bytes,
// whether they had landed already, how long that resolve() blocked, and
// how long the transfer took from its request to its landing.
void trpc_host_view_note(uint64_t bytes, int ahead, int64_t wait_us,
                         int64_t transfer_us) {
  g_vars.view_bytes << static_cast<int64_t>(bytes);
  if (ahead) {
    g_vars.view_ahead_bytes << static_cast<int64_t>(bytes);
  }
  g_vars.view_wait_us << wait_us;
  g_vars.view_transfer_us << transfer_us;
}

// 1 when [ptr, ptr + len) lies in one of the pool's blocks: registered
// memory that stays out of the idle list while a record published from
// it lives (kv.py publishes such bytes where they lie).
int trpc_host_pool_holds(const void* ptr, size_t len) {
  const uintptr_t at = reinterpret_cast<uintptr_t>(ptr);
  std::lock_guard<std::mutex> lk(g_pool.mu);
  auto it = g_pool.blocks.upper_bound(at);
  if (it == g_pool.blocks.begin()) {
    return 0;
  }
  --it;
  return len <= it->second.size && at - it->first <= it->second.size - len;
}

// Bytes lying idle in the list (tests).
size_t trpc_host_pool_idle_bytes() {
  std::vector<void*> evicted;
  size_t bytes = 0;
  {
    std::lock_guard<std::mutex> lk(g_pool.mu);
    collect_parked(&evicted);
    bytes = g_pool.idle_bytes;
  }
  free_regions(evicted);
  return bytes;
}

// Frees every idle block and returns the bytes released (tests start
// from an empty list; a process that is done staging).  A block a
// record is still served from is not idle and stays.
size_t trpc_host_pool_trim() {
  std::vector<void*> idle;
  size_t bytes = 0;
  {
    std::lock_guard<std::mutex> lk(g_pool.mu);
    collect_parked(&idle);
    bytes = g_pool.idle_bytes;
    for (void* p : g_pool.idle) {
      g_pool.blocks.erase(reinterpret_cast<uintptr_t>(p));
      idle.push_back(p);
    }
    g_pool.idle.clear();
    g_pool.idle_bytes = 0;
  }
  free_regions(idle);
  return bytes;
}

}  // extern "C"
