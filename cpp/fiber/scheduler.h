// Scheduler internals shared by event/timer/sync (not user-facing).
//
// Parity map: Scheduler ≈ bthread TaskControl (task_control.h:46), Worker ≈
// TaskGroup (task_group.h), FiberMeta ≈ TaskMeta, ParkingLot ≈ parking_lot.h.
#pragma once

#include <atomic>
#include <sched.h>
#include <cstdint>
#include <deque>
#include <mutex>
#include <vector>

#include "fiber/event.h"
#include "fiber/fiber.h"
#include "fiber/stack.h"
#include "fiber/wsqueue.h"

namespace trpc {

class Worker;

// Deferred action run by the scheduler AFTER the fiber's context has been
// switched away — the publish-after-switch pattern that closes the
// "woken before fully suspended" race (parity: TaskGroup::set_remained,
// task_group.h:124).
using PostSwitchFn = void (*)(void* arg1, void* arg2);

struct FiberMeta {
  // Atomic: /fibers dumps read it concurrently with slot recycling.
  std::atomic<void (*)(void*)> fn{nullptr};
  void* arg = nullptr;
  void* sp = nullptr;  // suspended continuation
  StackMem stack;
  void* asan_fake_stack = nullptr;  // ASan fiber handshake state
  void* tsan_fiber = nullptr;       // TSan fiber identity (tsan builds)
  // Even = idle slot; odd = live fiber.  The version half of fiber_t.
  std::atomic<uint32_t> version{0};
  // Interruption (parity: TaskGroup::interrupt / bthread_stop): the Event
  // this fiber is currently parked on (null while runnable), and a
  // pending-interrupt flag consumed by the next Event::wait return.
  // park_mu serializes interrupters against park/unpark: an interrupter
  // may only touch the Event while holding it, and the waiter clears
  // parked_on under it BEFORE the Event can be destroyed — so wake_all
  // from fiber_interrupt can never run on a dead Event.
  std::atomic<class Event*> parked_on{nullptr};
  std::atomic<bool> interrupted{false};
  std::atomic_flag park_mu = ATOMIC_FLAG_INIT;
  // Ambient trace context (net/span.cc reads/writes these when the
  // fiber installs a span).  Value storage directly on the meta instead
  // of FLS slots so the values follow the fiber across worker migration.
  // Relaxed: same-fiber accesses are program-ordered across migration by
  // the scheduler's queue handoff.
  std::atomic<uint64_t> ambient_trace{0};
  std::atomic<uint64_t> ambient_span{0};
  // Ambient deadline plane (net/deadline.h): the absolute monotonic
  // deadline (µs; 0 = none) and cancel scope of the request this fiber
  // is serving.  Same storage rationale and ordering as ambient_trace.
  std::atomic<int64_t> ambient_deadline{0};
  std::atomic<void*> ambient_cancel{nullptr};

  void park_lock() {
    while (park_mu.test_and_set(std::memory_order_acquire)) {
      sched_yield();
    }
  }
  void park_unlock() { park_mu.clear(std::memory_order_release); }
  // Join event: value holds the live version while running; bumped at exit.
  Event done_event;
  struct FlsSlot {
    void* value = nullptr;
    uint32_t version = 0;
  };
  std::vector<FlsSlot> fls;
  uint32_t slot = 0;  // own index in the pool
  uint8_t tag = 0;    // worker-group pin (task_control.h:94 tag parity)

  fiber_t id() const {
    return (static_cast<uint64_t>(version.load(std::memory_order_relaxed))
            << 32) |
           slot;
  }
};

FiberMeta* fiber_meta_of(fiber_t f);  // nullptr if stale/invalid
void run_fls_destructors(FiberMeta* m);

class ParkingLot {
 public:
  // Returns a stamp to pass to wait().
  int stamp() const { return seq_.load(std::memory_order_acquire); }
  void signal(int n);
  void wait(int stamp);

 private:
  std::atomic<int> seq_{0};
  // FUTEX_WAKE costs a syscall even with nobody parked — at 100k+ qps
  // most ready_to_run calls hit busy workers.  signal() ALWAYS bumps
  // seq_ (so a waiter between stamp and FUTEX_WAIT sees the change and
  // returns) and only syscalls when someone is actually parked.
  std::atomic<int> waiters_{0};
};

class Scheduler {
 public:
  static constexpr int kMaxTags = 4;  // kMaxFiberTags (fiber.h)

  static Scheduler* instance();
  void start(int workers);                  // tag 0
  void start_tag(int tag, int workers);     // idempotent per tag
  bool started() const {
    return tags_[0].nworkers.load(std::memory_order_acquire) > 0;
  }
  int worker_count(int tag = 0) const {
    return tags_[tag].nworkers.load(std::memory_order_acquire);
  }

  // Make a runnable fiber visible to some worker OF ITS TAG (any thread).
  void ready_to_run(FiberMeta* m, bool urgent = false);
  // Publish n runnables of ONE tag with a single ParkingLot signal (the
  // bulk-wake path behind fiber_start_batch).  Queue-push order follows
  // ms[]; execution order is unspecified (see fiber_start_batch).
  void ready_to_run_batch(FiberMeta* const* ms, size_t n, bool urgent);
  bool steal(FiberMeta** out, Worker* thief);
  bool pop_remote(FiberMeta** out, int tag);
  void push_remote(FiberMeta* m);

  // Bulk-wake telemetry (read by fiber_bulk_wake_stats).
  std::atomic<uint64_t> bulk_wake_batches{0};
  std::atomic<uint64_t> bulk_wake_fibers{0};
  std::atomic<uint64_t> bulk_wake_max{0};

  // Per-tag worker group: spawn/steal/park confined inside (the
  // reference's per-tag TaskControl groups, task_control.h:94-99).
  struct TagGroup {
    Worker* workers[64] = {};
    std::atomic<int> nworkers{0};
    std::mutex remote_mu;
    std::deque<FiberMeta*> remote_q;
    ParkingLot lot;
    std::once_flag once;
  };
  TagGroup& group(int tag) { return tags_[tag]; }

 private:
  Scheduler() = default;
  static constexpr int kMaxWorkers = 64;
  TagGroup tags_[kMaxTags];
};

class Worker {
 public:
  Worker(Scheduler* sched, int index, int tag);
  void main_loop();  // pthread entry

  // Called from a running fiber: switch back to the scheduler context.
  // post_fn(arg1, arg2) runs on the scheduler context after the switch.
  // dying = the fiber never resumes (lets ASan retire its fake frames).
  void suspend_current(PostSwitchFn post_fn, void* a1, void* a2,
                       bool dying = false);

  FiberMeta* current() const { return current_; }
  WorkStealingQueue<FiberMeta*>& runq() { return runq_; }
  int index() const { return index_; }
  int tag() const { return tag_; }

 private:
  friend class Scheduler;
  FiberMeta* pick_next();
  void run_fiber(FiberMeta* m);

  Scheduler* sched_;
  int index_;
  int tag_;
  // One-deep priority slot checked before the run queue (kFiberUrgent).
  std::atomic<FiberMeta*> urgent_{nullptr};
  WorkStealingQueue<FiberMeta*> runq_;
  FiberMeta* current_ = nullptr;
  void* sched_sp_ = nullptr;  // scheduler continuation while a fiber runs
  void* asan_fake_stack_ = nullptr;
  void* tsan_sched_fiber_ = nullptr;  // this worker's scheduler context
  void* pthread_stack_base_ = nullptr;  // this worker pthread's stack
  size_t pthread_stack_size_ = 0;
  PostSwitchFn post_fn_ = nullptr;
  void* post_a1_ = nullptr;
  void* post_a2_ = nullptr;
};

extern thread_local Worker* tls_worker;

}  // namespace trpc
