#include "fiber/scheduler.h"

#include <linux/futex.h>
#include <pthread.h>
#include <sys/syscall.h>
#include <unistd.h>

#include <cstring>

#include "base/futex_mutex.h"
#include "base/logging.h"
#include "base/symbolize.h"
#include "base/rand.h"
#include "base/resource_pool.h"
#include "fiber/context.h"
#include <dlfcn.h>

#include "fiber/event.h"

// ASan fiber-switch annotations (parity: the reference's ASan-aware stack
// switching, task_group.h:311 asan_task_runner + stack poisoning).  No-ops
// unless built with -fsanitize=address.
#if defined(__has_feature)
#if __has_feature(address_sanitizer)
#define TRPC_HAS_ASAN_FEATURE 1
#endif
#endif
#if defined(__SANITIZE_ADDRESS__) || defined(TRPC_HAS_ASAN_FEATURE)
extern "C" {
void __sanitizer_start_switch_fiber(void** fake_stack_save,
                                    const void* bottom, size_t size);
void __sanitizer_finish_switch_fiber(void* fake_stack_save,
                                     const void** bottom_old,
                                     size_t* size_old);
}
#define TRPC_ASAN_FIBERS 1
#else
#define TRPC_ASAN_FIBERS 0
static inline void __sanitizer_start_switch_fiber(void**, const void*,
                                                  size_t) {}
static inline void __sanitizer_finish_switch_fiber(void*, const void**,
                                                   size_t*) {}
#endif

// TSan fiber-switch annotations: without them TSan sees one pthread's
// shadow stack teleporting between fiber stacks and reports phantom
// races.  No-ops unless built with -fsanitize=thread.
// Declarations + the acquire/release edge macros live in the shared shim
// (base/tsan.h); everything no-ops outside -fsanitize=thread.
#include "base/tsan.h"
#define TRPC_TSAN_FIBERS TRPC_TSAN

namespace trpc {

thread_local Worker* tls_worker = nullptr;

namespace {

using FiberPool = ResourcePool<FiberMeta>;

void requeue_post(void* a1, void*) {
  Scheduler::instance()->ready_to_run(static_cast<FiberMeta*>(a1));
}

void finish_fiber_post(void* p, void*) {
  FiberMeta* m = static_cast<FiberMeta*>(p);
  const uint32_t ver = m->version.load(std::memory_order_relaxed);
  if (TRPC_TSAN_FIBERS && m->tsan_fiber != nullptr) {
    __tsan_destroy_fiber(m->tsan_fiber);
    m->tsan_fiber = nullptr;
  }
  release_stack(m->stack);
  m->stack = StackMem{};
  m->sp = nullptr;
  // Even version = idle slot; the bumped done word releases joiners.  The
  // meta is pool-recycled, never freed, so late joiners touching the event
  // see the new value and return (type-stable memory, like TaskMeta).
  m->version.store(ver + 1, std::memory_order_release);
  m->done_event.value.store(ver + 1, std::memory_order_release);
  m->done_event.wake_all();
  FiberPool::instance()->release(m->slot);
}

void fiber_entry(void* p) {
  FiberMeta* m = static_cast<FiberMeta*>(p);
  // Complete the ASan handshake for the first entry onto this stack.
  __sanitizer_finish_switch_fiber(nullptr, nullptr, nullptr);
  m->fn.load(std::memory_order_relaxed)(m->arg);
  run_fls_destructors(m);
  Worker* w = tls_worker;  // worker we ended on (may differ from start)
  w->suspend_current(finish_fiber_post, m, nullptr, /*dying=*/true);
  CHECK(false) << "resumed a finished fiber";
}

}  // namespace

FiberMeta* fiber_meta_of(fiber_t f) {
  const uint32_t slot = static_cast<uint32_t>(f);
  const uint32_t ver = static_cast<uint32_t>(f >> 32);
  if ((ver & 1) == 0) {
    return nullptr;
  }
  FiberMeta* m = FiberPool::instance()->at(slot);
  if (m == nullptr || m->version.load(std::memory_order_acquire) != ver) {
    return nullptr;
  }
  return m;
}

void ParkingLot::signal(int n) {
  // Edge to a waker-to-parked-worker handoff TSan cannot model: the
  // release below pairs with wait()'s acquire only when the waiter
  // re-reads seq_, but a worker woken by the FUTEX_WAKE syscall itself
  // never touches seq_ again — annotate the same edge explicitly so
  // everything published before signal() is visible after wait().
  TRPC_TSAN_RELEASE(&seq_);
  seq_.fetch_add(1, std::memory_order_release);
  // seq_ is already bumped, so a worker past its stamp() re-check that
  // has not yet reached FUTEX_WAIT will see the changed word and return
  // without sleeping — skipping the wake syscall when nobody has
  // registered as parked is therefore lost-wakeup-free.
  if (waiters_.load(std::memory_order_acquire) > 0) {
    futex_word_op(&seq_, FUTEX_WAKE_PRIVATE, n, nullptr);
  }
}

void ParkingLot::wait(int stamp) {
  waiters_.fetch_add(1, std::memory_order_acq_rel);
  futex_word_op(&seq_, FUTEX_WAIT_PRIVATE, stamp, nullptr);
  waiters_.fetch_sub(1, std::memory_order_acq_rel);
  // Close the signal() edge (see above): the kernel ordered the wake
  // after the waker's seq_ bump, but no acquire-read of seq_ follows.
  TRPC_TSAN_ACQUIRE(&seq_);
}

Scheduler* Scheduler::instance() {
  // Deliberately leaked: worker pthreads outlive static destruction.
  static Scheduler* s = new Scheduler();
  return s;
}

void Scheduler::start(int workers) { start_tag(0, workers); }

void Scheduler::start_tag(int tag, int workers) {
  if (tag < 0 || tag >= kMaxTags) {
    return;
  }
  TagGroup& g = tags_[tag];
  std::call_once(g.once, [this, &g, tag, workers] {
    int n = workers;
    if (n <= 0) {
      const long ncpu = sysconf(_SC_NPROCESSORS_ONLN);
      n = std::max(4L, std::min(8L, ncpu));
    }
    n = std::min(n, kMaxWorkers);
    for (int i = 0; i < n; ++i) {
      g.workers[i] = new Worker(this, i, tag);
      pthread_t tid;
      pthread_create(
          &tid, nullptr,
          [](void* w) -> void* {
            static_cast<Worker*>(w)->main_loop();
            return nullptr;
          },
          g.workers[i]);
      pthread_detach(tid);
    }
    g.nworkers.store(n, std::memory_order_release);
  });
}

void Scheduler::ready_to_run(FiberMeta* m, bool urgent) {
  TagGroup& g = tags_[m->tag];
  Worker* w = tls_worker;
  // A thread about to block pthread-style must not trap work in its own
  // queues — it won't return to its scheduler loop until woken.  A worker
  // of ANOTHER tag must not take the fiber either: spawn stays in-group.
  if (w != nullptr && (w->tag() != m->tag || in_pthread_wait_mode())) {
    w = nullptr;
  }
  if (w != nullptr) {
    if (urgent) {
      // Claim the worker's one-deep priority slot; it runs before the queue.
      FiberMeta* expect = nullptr;
      if (w->urgent_.compare_exchange_strong(expect, m,
                                             std::memory_order_acq_rel)) {
        g.lot.signal(2);
        return;
      }
    }
    if (!w->runq().push(m)) {
      push_remote(m);
    }
  } else {
    push_remote(m);
  }
  g.lot.signal(urgent ? 2 : 1);
}

void Scheduler::push_remote(FiberMeta* m) {
  TagGroup& g = tags_[m->tag];
  std::lock_guard<std::mutex> lk(g.remote_mu);
  g.remote_q.push_back(m);
}

void Scheduler::ready_to_run_batch(FiberMeta* const* ms, size_t n,
                                   bool urgent) {
  if (n == 0) {
    return;
  }
  if (n == 1) {
    ready_to_run(ms[0], urgent);
    return;
  }
  TagGroup& g = tags_[ms[0]->tag];
  Worker* w = tls_worker;
  if (w != nullptr && (w->tag() != ms[0]->tag || in_pthread_wait_mode())) {
    w = nullptr;
  }
  size_t first = 0;
  if (urgent && w != nullptr) {
    // Urgent batches claim the one-deep priority slot for their FIRST
    // fiber (the slot is one-deep by design); the rest queue normally
    // but still ride the elevated signal below.
    FiberMeta* expect = nullptr;
    if (w->urgent_.compare_exchange_strong(expect, ms[0],
                                           std::memory_order_acq_rel)) {
      first = 1;
    }
  }
  if (w != nullptr) {
    // Push to the caller's own queue in order; thieves + the signal below
    // fan the batch out.  Overflow spills to the remote queue under ONE
    // lock (a nearly-full runq is exactly the loaded case where per-node
    // locking would hurt).
    size_t i = first;
    while (i < n && w->runq().push(ms[i])) {
      ++i;
    }
    if (i < n) {
      std::lock_guard<std::mutex> lk(g.remote_mu);
      for (; i < n; ++i) {
        g.remote_q.push_back(ms[i]);
      }
    }
  } else {
    std::lock_guard<std::mutex> lk(g.remote_mu);
    for (size_t i = first; i < n; ++i) {
      g.remote_q.push_back(ms[i]);
    }
  }
  bulk_wake_batches.fetch_add(1, std::memory_order_relaxed);
  bulk_wake_fibers.fetch_add(n, std::memory_order_relaxed);
  uint64_t cur = bulk_wake_max.load(std::memory_order_relaxed);
  while (n > cur && !bulk_wake_max.compare_exchange_weak(
                        cur, n, std::memory_order_relaxed)) {
  }
  // ONE signal for the whole batch: a single FUTEX_WAKE releases up to n
  // parked workers, where per-spawn publication would re-enter the futex
  // path n times.  Urgent batches wake one extra worker, mirroring
  // ready_to_run's signal(2) bias.
  g.lot.signal(static_cast<int>(n) + (urgent ? 1 : 0));
}

bool Scheduler::pop_remote(FiberMeta** out, int tag) {
  TagGroup& g = tags_[tag];
  std::lock_guard<std::mutex> lk(g.remote_mu);
  if (g.remote_q.empty()) {
    return false;
  }
  *out = g.remote_q.front();
  g.remote_q.pop_front();
  return true;
}

bool Scheduler::steal(FiberMeta** out, Worker* thief) {
  // Steal range = the thief's own tag group (task_control.h:94 parity:
  // per-tag groups do not poach each other's work).
  TagGroup& g = tags_[thief->tag()];
  const int n = g.nworkers.load(std::memory_order_acquire);
  if (n <= 1) {
    return false;
  }
  const uint64_t start = fast_rand_less_than(n);
  for (int i = 0; i < n; ++i) {
    Worker* victim = g.workers[(start + i) % n];
    if (victim == nullptr || victim == thief) {
      continue;
    }
    if (victim->runq().steal(out)) {
      return true;
    }
    // The victim may be pthread-blocked with a fiber parked in its urgent
    // slot; claim it so it can't starve.
    FiberMeta* urgent =
        victim->urgent_.exchange(nullptr, std::memory_order_acq_rel);
    if (urgent != nullptr) {
      *out = urgent;
      return true;
    }
  }
  return false;
}

Worker::Worker(Scheduler* sched, int index, int tag)
    : sched_(sched), index_(index), tag_(tag) {}

FiberMeta* Worker::pick_next() {
  FiberMeta* m = urgent_.exchange(nullptr, std::memory_order_acq_rel);
  if (m != nullptr) {
    return m;
  }
  if (runq_.pop(&m)) {
    return m;
  }
  if (sched_->pop_remote(&m, tag_)) {
    return m;
  }
  if (sched_->steal(&m, this)) {
    return m;
  }
  return nullptr;
}

void Worker::run_fiber(FiberMeta* m) {
  current_ = m;
  __sanitizer_start_switch_fiber(&asan_fake_stack_, m->stack.base,
                                 m->stack.size);
  if (TRPC_TSAN_FIBERS) {
    if (m->tsan_fiber == nullptr) {
      m->tsan_fiber = __tsan_create_fiber(0);
    }
    __tsan_switch_to_fiber(m->tsan_fiber, 0);
  }
  trpc_jump_context(&sched_sp_, m->sp, m);
  __sanitizer_finish_switch_fiber(asan_fake_stack_, nullptr, nullptr);
  current_ = nullptr;
  if (post_fn_ != nullptr) {
    PostSwitchFn fn = post_fn_;
    post_fn_ = nullptr;
    fn(post_a1_, post_a2_);
  }
}

void Worker::suspend_current(PostSwitchFn post_fn, void* a1, void* a2,
                             bool dying) {
  FiberMeta* m = current_;
  post_fn_ = post_fn;
  post_a1_ = a1;
  post_a2_ = a2;
  // A dying fiber passes nullptr fake-stack storage so ASan retires its
  // fake frames instead of preserving them for a resume.
  __sanitizer_start_switch_fiber(dying ? nullptr : &m->asan_fake_stack,
                                 pthread_stack_base_, pthread_stack_size_);
  if (TRPC_TSAN_FIBERS) {
    __tsan_switch_to_fiber(tsan_sched_fiber_, 0);
  }
  trpc_jump_context(&m->sp, sched_sp_, nullptr);
  // Resumed (possibly on another worker's scheduler context).
  __sanitizer_finish_switch_fiber(m->asan_fake_stack, nullptr, nullptr);
}

void Worker::main_loop() {
  tls_worker = this;
  if (TRPC_TSAN_FIBERS) {
    tsan_sched_fiber_ = __tsan_get_current_fiber();
  }
#if TRPC_ASAN_FIBERS
  {
    pthread_attr_t attr;
    pthread_getattr_np(pthread_self(), &attr);
    pthread_attr_getstack(&attr, &pthread_stack_base_, &pthread_stack_size_);
    pthread_attr_destroy(&attr);
  }
#endif
  ParkingLot& lot = sched_->group(tag_).lot;
  while (true) {
    FiberMeta* m = pick_next();
    if (m != nullptr) {
      run_fiber(m);
      continue;
    }
    const int stamp = lot.stamp();
    m = pick_next();  // re-check after stamp: closes the missed-signal window
    if (m != nullptr) {
      run_fiber(m);
      continue;
    }
    lot.wait(stamp);
  }
}

// ---- public API ---------------------------------------------------------

void fiber_init(int workers) { Scheduler::instance()->start(workers); }

int fiber_worker_count() { return Scheduler::instance()->worker_count(); }

int fiber_start_tag_workers(int tag, int workers) {
  if (tag < 0 || tag >= kMaxFiberTags) {
    return EINVAL;
  }
  Scheduler::instance()->start_tag(tag, workers);
  return 0;
}

int fiber_current_tag() {
  Worker* w = tls_worker;
  return w != nullptr ? w->tag() : 0;
}

int fiber_worker_count_tag(int tag) {
  if (tag < 0 || tag >= kMaxFiberTags) {
    return 0;
  }
  return Scheduler::instance()->worker_count(tag);
}

namespace {

// Shared by fiber_start / fiber_start_batch: resolve the worker tag from
// `flags` (explicit flag wins, else inherit the spawning worker's tag) and
// provision its group.  Returns the tag, or -1 for an out-of-range flag.
int resolve_spawn_tag(Scheduler* sched, int flags) {
  int tag = (flags >> 8) & 0xff;
  if (tag == 0) {
    tag = fiber_current_tag();
  } else {
    tag -= 1;
    if (tag >= kMaxFiberTags) {
      return -1;
    }
  }
  if (tag != 0 && sched->worker_count(tag) == 0) {
    sched->start_tag(tag, 0);  // auto-provision a default-sized group
  }
  return tag;
}

// Acquire + initialize one runnable meta (not yet published).
FiberMeta* make_fiber_meta(void (*fn)(void*), void* arg, int tag) {
  FiberMeta* m = nullptr;
  const uint32_t slot = FiberPool::instance()->acquire(&m);
  if (m == nullptr) {
    return nullptr;
  }
  m->slot = slot;
  m->tag = static_cast<uint8_t>(tag);
  m->fn.store(fn, std::memory_order_relaxed);
  m->arg = arg;
  m->interrupted.store(false, std::memory_order_relaxed);
  m->parked_on.store(nullptr, std::memory_order_relaxed);
  // Relaxed: pre-publication init (the slot is not yet visible), same as
  // the surrounding stores; a recycled meta must not leak the previous
  // fiber's trace or deadline context.
  m->ambient_trace.store(0, std::memory_order_relaxed);
  m->ambient_span.store(0, std::memory_order_relaxed);
  m->ambient_deadline.store(0, std::memory_order_relaxed);
  m->ambient_cancel.store(nullptr, std::memory_order_relaxed);
  const uint32_t ver = m->version.load(std::memory_order_relaxed) + 1;  // odd
  m->done_event.value.store(ver, std::memory_order_relaxed);
  m->version.store(ver, std::memory_order_relaxed);
  m->stack = allocate_stack(kDefaultStackSize);
  m->sp = make_context(m->stack.base, m->stack.size, fiber_entry);
  return m;
}

}  // namespace

int fiber_start(fiber_t* out, void (*fn)(void*), void* arg, int flags) {
  Scheduler* sched = Scheduler::instance();
  if (!sched->started()) {
    sched->start(0);
  }
  const int tag = resolve_spawn_tag(sched, flags);
  if (tag < 0) {
    return -1;
  }
  FiberMeta* m = make_fiber_meta(fn, arg, tag);
  if (m == nullptr) {
    return -1;
  }
  if (out != nullptr) {
    *out = m->id();
  }
  sched->ready_to_run(m, (flags & kFiberUrgent) != 0);
  return 0;
}

size_t fiber_start_batch(void (*fn)(void*), void* const* args, size_t n,
                         int flags) {
  if (n == 0) {
    return 0;
  }
  Scheduler* sched = Scheduler::instance();
  if (!sched->started()) {
    sched->start(0);
  }
  const int tag = resolve_spawn_tag(sched, flags);
  if (tag < 0) {
    return 0;
  }
  constexpr size_t kStride = 64;
  FiberMeta* ms[kStride];
  size_t started = 0;
  while (started < n) {
    const size_t want = std::min(n - started, kStride);
    size_t got = 0;
    while (got < want) {
      FiberMeta* m = make_fiber_meta(fn, args[started + got], tag);
      if (m == nullptr) {
        break;  // pool exhausted: publish what we have
      }
      ms[got++] = m;
    }
    sched->ready_to_run_batch(ms, got, (flags & kFiberUrgent) != 0);
    started += got;
    if (got < want) {
      break;
    }
  }
  return started;
}

void fiber_bulk_wake_stats(uint64_t* batches, uint64_t* fibers,
                           uint64_t* max_batch) {
  Scheduler* s = Scheduler::instance();
  *batches = s->bulk_wake_batches.load(std::memory_order_relaxed);
  *fibers = s->bulk_wake_fibers.load(std::memory_order_relaxed);
  *max_batch = s->bulk_wake_max.load(std::memory_order_relaxed);
}

namespace {

// Unwinds a PARKED fiber from its saved context (context.S layout:
// sp+48 saved rbp, sp+56 return address), walking the frame-pointer
// chain.  Best-effort under concurrency: the fiber may resume mid-walk,
// so every dereference is bounds-checked against its own stack — reads
// can go stale but cannot fault (the stack stays mapped while the meta
// is live).
std::string walk_parked_stack(FiberMeta* m, int max_frames) {
  uint8_t* sp = static_cast<uint8_t*>(m->sp);
  uint8_t* lo = static_cast<uint8_t*>(m->stack.base);
  uint8_t* hi = lo + m->stack.size;
  if (lo == nullptr || sp < lo || sp + 64 > hi) {
    return "";
  }
  std::string out;
  void* pc = *reinterpret_cast<void**>(sp + 56);
  uint8_t* rbp = *reinterpret_cast<uint8_t**>(sp + 48);
  for (int i = 0; i < max_frames && pc != nullptr; ++i) {
    out += "    #" + std::to_string(i) + " " + symbolize_addr(pc) + "\n";
    if (rbp < sp || rbp + 16 > hi ||
        (reinterpret_cast<uintptr_t>(rbp) & 7) != 0) {
      break;  // frame chain left the stack (or was never valid)
    }
    pc = *reinterpret_cast<void**>(rbp + 8);
    uint8_t* next = *reinterpret_cast<uint8_t**>(rbp);
    if (next <= rbp) {
      break;  // chains must grow upward; anything else is garbage
    }
    rbp = next;
  }
  return out;
}

}  // namespace

std::string fiber_dump_all(size_t max_rows, bool stacks) {
  return dump_pool_table<FiberMeta>(
      "live fibers (id  state  entry)\n", max_rows,
      [stacks](uint32_t slot, FiberMeta* m, std::string* line) {
        const uint32_t ver = m->version.load(std::memory_order_acquire);
        if ((ver & 1) == 0) {
          return false;  // even = idle slot
        }
        if (line == nullptr) {
          return true;  // counted, rows already capped
        }
        const Event* parked = m->parked_on.load(std::memory_order_acquire);
        char buf[256];
        const char* sym = "?";
        Dl_info info;
        void* fn = reinterpret_cast<void*>(
            m->fn.load(std::memory_order_relaxed));
        if (fn != nullptr && dladdr(fn, &info) != 0 &&
            info.dli_sname != nullptr) {
          sym = info.dli_sname;
        }
        snprintf(buf, sizeof(buf), "%016llx  %-8s %s\n",
                 static_cast<unsigned long long>(
                     (static_cast<uint64_t>(ver) << 32) | slot),
                 parked != nullptr ? "parked" : "runnable", sym);
        *line = buf;
        if (stacks && parked != nullptr) {
          *line += walk_parked_stack(m, 16);
        }
        return true;
      });
}

int fiber_interrupt(fiber_t f) {
  FiberMeta* m = fiber_meta_of(f);
  if (m == nullptr) {
    return ESRCH;
  }
  // Everything under the park lock: (a) the version re-check closes the
  // recycled-slot race (a delayed interrupted.store must not EINTR an
  // unrelated new fiber), and (b) the waiter cannot clear parked_on and
  // destroy the Event while we are inside wake_all (stack Events —
  // fiber_sleep — die right after the wait returns).  Spurious wakes of
  // co-waiters are part of the Event contract (callers re-check).
  m->park_lock();
  if (m->version.load(std::memory_order_acquire) !=
      static_cast<uint32_t>(f >> 32)) {
    m->park_unlock();
    return ESRCH;
  }
  m->interrupted.store(true, std::memory_order_release);
  Event* ev = m->parked_on.load(std::memory_order_acquire);
  if (ev != nullptr) {
    ev->wake_all();
  }
  m->park_unlock();
  return 0;
}

int fiber_join(fiber_t f) {
  const uint32_t ver = static_cast<uint32_t>(f >> 32);
  FiberMeta* m = fiber_meta_of(f);
  if (m == nullptr) {
    return 0;  // already gone (or never existed)
  }
  // The done event's value holds the live version until exit bumps it; the
  // meta is type-stable, so waiting on a recycled slot just returns.
  while (m->done_event.value.load(std::memory_order_acquire) == ver) {
    m->done_event.wait(ver, -1);
  }
  return 0;
}

bool fiber_exists(fiber_t f) { return fiber_meta_of(f) != nullptr; }

fiber_t fiber_self() {
  Worker* w = tls_worker;
  return (w != nullptr && w->current() != nullptr) ? w->current()->id() : 0;
}

bool in_fiber() { return tls_worker != nullptr && tls_worker->current() != nullptr; }

void fiber_yield() {
  Worker* w = tls_worker;
  if (w == nullptr || w->current() == nullptr) {
    sched_yield();
    return;
  }
  w->suspend_current(requeue_post, w->current(), nullptr);
}

}  // namespace trpc
