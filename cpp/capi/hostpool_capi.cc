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
#include <stdint.h>
#include <stdlib.h>

#include <mutex>
#include <vector>

namespace {

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

void* pool_malloc(void* /*ctx*/, size_t size) {
  if (size >= kMinPooledBytes) {
    std::lock_guard<std::mutex> lk(g_mu);
    // Newest first: the block most likely still in the caches and TLB.
    for (size_t i = g_idle.size(); i-- > 0;) {
      if (g_idle[i].size == size) {
        void* ptr = g_idle[i].ptr;
        g_idle.erase(g_idle.begin() + static_cast<ptrdiff_t>(i));
        g_idle_bytes -= size;
        return ptr;
      }
    }
  }
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
