#include "base/iobuf.h"

#include "base/logging.h"

#include <errno.h>
#include <sys/socket.h>
#include <unistd.h>

#include <algorithm>

namespace trpc {

namespace {
constexpr int kMaxIov = 64;
// The write side carries whole coalesced KeepWrite batches (many small
// responses → many refs); a bigger budget keeps one drain = one writev.
constexpr int kMaxWriteIov = 256;
}

IOBuf::IOBuf(const IOBuf& other) : size_(other.size_), arena_(other.arena_) {
  refs_ = other.refs_;
  for (BlockRef& r : refs_) {
    r.block->add_ref();
  }
}

IOBuf& IOBuf::operator=(const IOBuf& other) {
  if (this != &other) {
    IOBuf tmp(other);
    *this = std::move(tmp);
  }
  return *this;
}

IOBuf::IOBuf(IOBuf&& other) noexcept
    : refs_(std::move(other.refs_)), size_(other.size_), arena_(other.arena_) {
  other.refs_.clear();
  other.size_ = 0;
}

IOBuf& IOBuf::operator=(IOBuf&& other) noexcept {
  if (this != &other) {
    clear();
    refs_ = std::move(other.refs_);
    size_ = other.size_;
    arena_ = other.arena_;
    other.refs_.clear();
    other.size_ = 0;
  }
  return *this;
}

void IOBuf::clear() {
  for (BlockRef& r : refs_) {
    r.block->release();
  }
  refs_.clear();
  size_ = 0;
}

void IOBuf::push_ref(Block* b, uint32_t offset, uint32_t length) {
  refs_.push_back(BlockRef{offset, length, b});
  size_ += length;
}

Block* IOBuf::extendable_tail(size_t want) const {
  if (refs_.empty()) {
    return nullptr;
  }
  const BlockRef& r = refs_.back();
  Block* b = r.block;
  // Extension is safe only while we hold the sole reference and our ref
  // covers the block's live tail.
  if (b->ref.load(std::memory_order_acquire) != 1 ||
      b->user_deleter != nullptr || r.offset + r.length != b->size ||
      b->size >= b->cap) {
    return nullptr;
  }
  return b;
}

void IOBuf::append(const void* data, size_t n) {
  const char* p = static_cast<const char*>(data);
  BlockArena* arena = arena_ ? arena_ : HostArena::instance();
  while (n > 0) {
    Block* b = extendable_tail(n);
    if (b != nullptr) {
      const size_t take = std::min<size_t>(n, b->cap - b->size);
      memcpy(b->data + b->size, p, take);
      b->size += take;
      refs_.back().length += take;
      size_ += take;
      p += take;
      n -= take;
      continue;
    }
    // Ask for ONE byte so every arena serves at its own granularity (a
    // device arena hands out full fixed-size blocks; large appends span
    // as many as needed) — EXCEPT bulk appends on the host arena, which
    // get large pooled blocks: a multi-MB body in 8KB slivers costs one
    // iovec per sliver at the writev below it, and per-iovec overhead is
    // what caps bulk goodput on paravirtualized kernels.  Genuine
    // exhaustion (slab growth failure) is a hard programming/resource
    // error at this copying entry point — the zero-copy path
    // (`append_block` of a block the caller allocated) reports it recoverably
    // instead.
    const uint32_t want =
        (arena == HostArena::instance() && n >= HostArena::kBigBlockMin)
            ? static_cast<uint32_t>(std::min<size_t>(n, 8u << 20))
            : 1;
    Block* nb = arena->allocate(want);
    CHECK(nb != nullptr) << "arena exhausted appending " << n << " bytes";
    const size_t take = std::min<size_t>(n, nb->cap);
    memcpy(nb->data, p, take);
    nb->size = take;
    push_ref(nb, 0, take);  // ref==1 from allocate
    p += take;
    n -= take;
  }
}

void IOBuf::append(const IOBuf& other) {
  refs_.reserve(refs_.size() + other.refs_.size());
  for (const BlockRef& r : other.refs_) {
    r.block->add_ref();
    refs_.push_back(r);
  }
  size_ += other.size_;
}

void IOBuf::append(IOBuf&& other) {
  if (refs_.empty()) {
    *this = std::move(other);
    return;
  }
  refs_.reserve(refs_.size() + other.refs_.size());
  for (const BlockRef& r : other.refs_) {
    refs_.push_back(r);
  }
  size_ += other.size_;
  other.refs_.clear();
  other.size_ = 0;
}

void IOBuf::append_user_data(void* data, size_t n,
                             void (*deleter)(void*, void*), void* ctx,
                             uint64_t meta) {
  Block* b = make_user_block(data, n, deleter, ctx, meta);
  push_ref(b, 0, n);
}

char* IOBuf::reserve(size_t n) {
  BlockArena* arena = arena_ ? arena_ : HostArena::instance();
  Block* b = extendable_tail(n);
  if (b == nullptr || b->cap - b->size < n) {
    b = arena->allocate(n);
    CHECK(b != nullptr) << "arena cannot reserve " << n << " bytes";
    b->size = n;
    push_ref(b, 0, n);
    return b->data;
  }
  char* p = b->data + b->size;
  b->size += n;
  refs_.back().length += n;
  size_ += n;
  return p;
}

size_t IOBuf::copy_to(void* dst, size_t n, size_t pos) const {
  char* out = static_cast<char*>(dst);
  size_t copied = 0;
  size_t skip = pos;
  for (const BlockRef& r : refs_) {
    if (copied >= n) {
      break;
    }
    if (skip >= r.length) {
      skip -= r.length;
      continue;
    }
    const size_t avail = r.length - skip;
    const size_t take = std::min(n - copied, avail);
    memcpy(out + copied, r.block->data + r.offset + skip, take);
    copied += take;
    skip = 0;
  }
  return copied;
}

std::string IOBuf::to_string() const {
  std::string s;
  s.resize(size_);
  copy_to(s.data(), size_);
  return s;
}

size_t IOBuf::cutn(IOBuf* out, size_t n) {
  n = std::min(n, size_);
  size_t left = n;
  size_t i = 0;
  while (left > 0 && i < refs_.size()) {
    BlockRef& r = refs_[i];
    if (r.length <= left) {
      out->refs_.push_back(r);  // transfer our reference
      out->size_ += r.length;
      left -= r.length;
      ++i;
    } else {
      r.block->add_ref();
      out->refs_.push_back(BlockRef{r.offset, static_cast<uint32_t>(left),
                                    r.block});
      out->size_ += left;
      r.offset += left;
      r.length -= left;
      left = 0;
    }
  }
  refs_.erase(refs_.begin(), refs_.begin() + i);
  size_ -= n;
  return n;
}

size_t IOBuf::pop_front(size_t n) {
  n = std::min(n, size_);
  size_t left = n;
  size_t i = 0;
  while (left > 0) {
    BlockRef& r = refs_[i];
    if (r.length <= left) {
      left -= r.length;
      r.block->release();
      ++i;
    } else {
      r.offset += left;
      r.length -= left;
      left = 0;
    }
  }
  refs_.erase(refs_.begin(), refs_.begin() + i);
  size_ -= n;
  return n;
}

size_t IOBuf::pop_back(size_t n) {
  n = std::min(n, size_);
  size_t left = n;
  while (left > 0) {
    BlockRef& r = refs_.back();
    if (r.length <= left) {
      left -= r.length;
      r.block->release();
      refs_.pop_back();
    } else {
      r.length -= left;
      left = 0;
    }
  }
  size_ -= n;
  return n;
}

int IOBuf::fill_iovec(iovec* iov, int max_iov, size_t max_bytes) const {
  int n = 0;
  size_t total = 0;
  for (const BlockRef& r : refs_) {
    if (n >= max_iov || total >= max_bytes) {
      break;
    }
    const size_t take = std::min<size_t>(r.length, max_bytes - total);
    iov[n].iov_base = r.block->data + r.offset;
    iov[n].iov_len = take;
    total += take;
    ++n;
  }
  return n;
}

ssize_t IOBuf::append_from_fd(int fd, size_t max_bytes, size_t block_hint) {
  BlockArena* arena = arena_ ? arena_ : HostArena::instance();
  const uint32_t fresh_cap = block_hint > HostArena::kDefaultBlockSize
                                 ? static_cast<uint32_t>(std::min<size_t>(
                                       block_hint, 64ull << 20))
                                 : HostArena::kDefaultBlockSize;
  // Read into up to kMaxIov fresh blocks with readv.
  iovec iov[kMaxIov];
  Block* blocks[kMaxIov];
  int n = 0;
  size_t planned = 0;
  while (n < kMaxIov && planned < max_bytes) {
    Block* b = extendable_tail(1);
    if (n == 0 && b != nullptr) {
      iov[n].iov_base = b->data + b->size;
      iov[n].iov_len = std::min<size_t>(b->cap - b->size, max_bytes);
      blocks[n] = nullptr;  // marks "extend tail"
      planned += iov[n].iov_len;
      ++n;
      continue;
    }
    Block* nb = arena->allocate(fresh_cap);
    iov[n].iov_base = nb->data;
    iov[n].iov_len = std::min<size_t>(nb->cap, max_bytes - planned);
    blocks[n] = nb;
    planned += iov[n].iov_len;
    ++n;
  }
  ssize_t rc = readv(fd, iov, n);
  if (rc <= 0) {
    for (int i = 0; i < n; ++i) {
      if (blocks[i] != nullptr) {
        blocks[i]->release();
      }
    }
    return rc;
  }
  size_t remain = static_cast<size_t>(rc);
  for (int i = 0; i < n; ++i) {
    const size_t got = std::min<size_t>(remain, iov[i].iov_len);
    if (blocks[i] == nullptr) {  // extended tail block
      Block* b = refs_.back().block;
      b->size += got;
      refs_.back().length += got;
      size_ += got;
    } else if (got > 0) {
      blocks[i]->size = got;
      push_ref(blocks[i], 0, got);
    } else {
      blocks[i]->release();
    }
    remain -= got;
  }
  return rc;
}

ssize_t IOBuf::cut_into_fd(int fd, size_t max_bytes) {
  iovec iov[kMaxWriteIov];
  const int n = fill_iovec(iov, kMaxWriteIov, max_bytes);
  if (n == 0) {
    return 0;
  }
  // MSG_NOSIGNAL: a peer racing its close ahead of this write must surface
  // as EPIPE, not a process-killing SIGPIPE — no global handler is owned
  // here.  Non-socket fds (pipes) fall back to writev.
  msghdr msg{};
  msg.msg_iov = iov;
  msg.msg_iovlen = static_cast<size_t>(n);
  ssize_t rc = ::sendmsg(fd, &msg, MSG_NOSIGNAL);
  if (rc < 0 && errno == ENOTSOCK) {
    rc = writev(fd, iov, n);
  }
  if (rc > 0) {
    pop_front(static_cast<size_t>(rc));
  }
  return rc;
}

bool IOBuf::equals(const void* data, size_t n) const {
  if (n != size_) {
    return false;
  }
  const char* p = static_cast<const char*>(data);
  size_t pos = 0;
  for (const BlockRef& r : refs_) {
    if (memcmp(p + pos, r.block->data + r.offset, r.length) != 0) {
      return false;
    }
    pos += r.length;
  }
  return true;
}

}  // namespace trpc
