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
// Blocks are libc's own (malloc/free compatible), so calloc and realloc
// pass through and a block may leave the list by plain free.  The list
// is bounded: past kMaxIdleBytes the block that has lain longest goes
// back to libc.  Smaller blocks never enter it.
//
// The same line (kMinPooledBytes) is the one zerocopy.py draws between a
// view whose transfer its waiter thread sees through and one it leaves to
// its caller, and the counters that say how a view's transfer went, and
// whether a landing block was a recycled one, are kept here beside it.
#include <stdint.h>
#include <stdlib.h>

#include <mutex>
#include <vector>

#include "stat/reducer.h"

namespace {

using trpc::Adder;

constexpr size_t kMinPooledBytes = 1u << 20;
// What may lie idle: the blocks of one pipeline at depth 8 and 64 MB
// (depth + 2, 0.7 GB) are all given back at once when it drains.
constexpr size_t kMaxIdleBytes = 1ull << 30;

struct IdleBlock {
  size_t size;
  void* ptr;
};

std::mutex g_mu;
std::vector<IdleBlock> g_idle;  // in the order given back, oldest first
size_t g_idle_bytes = 0;

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
                           "landing blocks of 1 MB or more served by "
                           "malloc: fresh pages");
  }
};

// Exposed when the library loads, so that a window in which nobody asked
// for a view reads 0 and only a program without the counters reads nothing
// (the benchmark's readers tell the two apart); leaked with the registry,
// as the other capi counters are.
HostViewVars& g_vars = *new HostViewVars();

void* pool_malloc(void* /*ctx*/, size_t size) {
  if (size < kMinPooledBytes) {
    return malloc(size);
  }
  {
    std::lock_guard<std::mutex> lk(g_mu);
    // Newest first: the block most likely still in the caches and TLB.
    for (size_t i = g_idle.size(); i-- > 0;) {
      if (g_idle[i].size == size) {
        void* ptr = g_idle[i].ptr;
        g_idle.erase(g_idle.begin() + static_cast<ptrdiff_t>(i));
        g_idle_bytes -= size;
        g_vars.pool_hit_bytes << static_cast<int64_t>(size);
        return ptr;
      }
    }
  }
  g_vars.pool_miss_bytes << static_cast<int64_t>(size);
  return malloc(size);
}

void* pool_calloc(void* /*ctx*/, size_t nelem, size_t elsize) {
  return calloc(nelem, elsize);
}

void* pool_realloc(void* /*ctx*/, void* ptr, size_t new_size) {
  return realloc(ptr, new_size);
}

void pool_free(void* /*ctx*/, void* ptr, size_t size) {
  if (ptr == nullptr || size < kMinPooledBytes || size > kMaxIdleBytes) {
    free(ptr);
    return;
  }
  std::vector<void*> evicted;
  {
    std::lock_guard<std::mutex> lk(g_mu);
    g_idle.push_back({size, ptr});
    g_idle_bytes += size;
    while (g_idle_bytes > kMaxIdleBytes) {
      evicted.push_back(g_idle.front().ptr);
      g_idle_bytes -= g_idle.front().size;
      g_idle.erase(g_idle.begin());
    }
  }
  for (void* p : evicted) {
    free(p);  // munmap of a large block: not under the lock
  }
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

// Bytes lying idle in the list (tests).
size_t trpc_host_pool_idle_bytes() {
  std::lock_guard<std::mutex> lk(g_mu);
  return g_idle_bytes;
}

// Gives every idle block back to libc and returns the bytes released
// (tests start from an empty list; a process that is done staging).
size_t trpc_host_pool_trim() {
  std::vector<IdleBlock> idle;
  size_t bytes = 0;
  {
    std::lock_guard<std::mutex> lk(g_mu);
    idle.swap(g_idle);
    bytes = g_idle_bytes;
    g_idle_bytes = 0;
  }
  for (const IdleBlock& b : idle) {
    free(b.ptr);
  }
  return bytes;
}

}  // extern "C"
