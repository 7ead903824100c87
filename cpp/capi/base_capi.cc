// Flat C ABI over the runtime for Python ctypes (the image has no pybind11;
// parity role: the reference's C++ API surface consumed by its examples).
#include <cstring>

#include "base/endpoint.h"
#include "base/iobuf.h"

using trpc::EndPoint;
using trpc::IOBuf;

extern "C" {

// Wrap caller-owned memory (e.g. a dlpack-exported JAX host buffer)
// without copying: the bytes enter the IOBuf by reference and
// deleter(data, ctx) runs when the LAST IOBuf reference drops — which may
// be on a fiber worker after the wire write completes, so a Python ctypes
// deleter must be re-entrant-safe (ctypes acquires the GIL itself).
void trpc_iobuf_append_user_data(void* buf, void* data, size_t n,
                                 void (*deleter)(void*, void*), void* ctx) {
  static_cast<IOBuf*>(buf)->append_user_data(data, n, deleter, ctx);
}

// Data pointer of block ref i (pointer-identity introspection for the
// zero-copy tests: proves the caller's buffer itself is on the wire).
void* trpc_iobuf_block_ptr(void* buf, size_t i) {
  auto* b = static_cast<IOBuf*>(buf);
  if (i >= b->block_count()) {
    return nullptr;
  }
  const IOBuf::BlockRef& r = b->ref_at(i);
  return r.block->data + r.offset;
}

void* trpc_iobuf_create() { return new IOBuf(); }

void trpc_iobuf_destroy(void* buf) { delete static_cast<IOBuf*>(buf); }

void trpc_iobuf_append(void* buf, const void* data, size_t n) {
  static_cast<IOBuf*>(buf)->append(data, n);
}

size_t trpc_iobuf_size(void* buf) { return static_cast<IOBuf*>(buf)->size(); }

size_t trpc_iobuf_copy_to(void* buf, void* dst, size_t n, size_t pos) {
  return static_cast<IOBuf*>(buf)->copy_to(dst, n, pos);
}

size_t trpc_iobuf_cutn(void* from, void* to, size_t n) {
  return static_cast<IOBuf*>(from)->cutn(static_cast<IOBuf*>(to), n);
}

size_t trpc_iobuf_pop_front(void* buf, size_t n) {
  return static_cast<IOBuf*>(buf)->pop_front(n);
}

size_t trpc_iobuf_block_count(void* buf) {
  return static_cast<IOBuf*>(buf)->block_count();
}

// Returns 0 on success; writes normalized form into out.
int trpc_endpoint_parse(const char* s, char* out, size_t out_len) {
  EndPoint ep;
  if (trpc::hostname2endpoint(s, &ep) != 0) {
    return -1;
  }
  const std::string str = trpc::endpoint2str(ep);
  if (str.size() + 1 > out_len) {
    return -1;
  }
  memcpy(out, str.c_str(), str.size() + 1);
  return 0;
}

}  // extern "C"
