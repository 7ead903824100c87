"""Pipelined zero-copy batch pipeline: submit N calls in one crossing,
poll completions with the GIL released.

Parity: fabric-lib's answer to "RPC Considered Harmful" (PAPERS.md) — deep
submission pipelines over registered buffers instead of one synchronous
round-trip per operation.  `Channel.call` is one blocked GIL round-trip
through `trpc_channel_call` per call; this module drives the batch C API
(cpp/capi/batch_capi.cc): `submit` hands the native runtime N requests by
reference (buffer protocol, no copy) and returns immediately; an issuing
fiber replays them as concurrent async calls; `poll` drains a lock-light
completion ring while the calling pthread sleeps OUTSIDE the GIL, so
Python handler servers, background threads and the submitting thread all
make progress during a deep poll.

Ownership rules (the zero-copy contract):

- Request buffers are pinned by this module until the native side drops
  its last IOBuf reference (a deleter callback, exactly like
  `zerocopy.append_jax`) — NOT merely until the completion is polled,
  because a timed-out call's bytes may still sit in a socket write queue.
- Response bytes either land in a caller-provided writable buffer (one
  native memcpy on the completion fiber, pool blocks recycled
  immediately) or ride out as a `ZeroCopyResponse` view over the pool
  blocks themselves; `release()` (or GC) recycles them.  No intermediate
  `bytes` object is created at the boundary on either path.

Staged requests: a request may be a `zerocopy.PendingView` — a device
array whose transfer to the host `zerocopy.host_view` has started and
whose bytes no caller has taken.  `submit` still returns its tokens at
once; the calls go to the pipeline's STAGER, one thread per Batch, which
waits for the bytes in submit order (`submit` tells each view so:
`waited_for`, and `zerocopy`'s own waiter then leaves it to the stager)
and hands the calls of a submit, once all their bytes have landed, to the
native submit in one crossing.  Everything
submitted while calls are with the stager queues behind them, so submit
order stays wire order.  A submit with nothing pending and nothing queued
ahead of it never sees the stager.
"""

from __future__ import annotations

import collections
import ctypes
import errno
import itertools
import threading

import numpy as np

from brpc_tpu.rpc import zerocopy as _zc
from brpc_tpu.rpc._lib import load_library


def pinned_requests() -> int:
    """Number of buffers currently pinned by in-flight sends (shared
    registry with zerocopy.live_sends — one registry, one deleter)."""
    return _zc.live_sends()


class BatchCompletion(ctypes.Structure):
    """ABI mirror of `struct trpc_batch_completion` (batch_capi.cc)."""

    _fields_ = [
        ("token", ctypes.c_uint64),
        ("status", ctypes.c_int32),
        ("resp_copied", ctypes.c_uint32),
        ("resp_len", ctypes.c_uint64),
        ("resp_iobuf", ctypes.c_void_p),
        ("err", ctypes.c_char * 120),
    ]


class BatchStage(ctypes.Structure):
    """ABI mirror of `struct trpc_batch_stage` (batch_capi.cc)."""

    _fields_ = [
        ("token", ctypes.c_uint64),
        ("staged_us", ctypes.c_int64),
        ("fetch_us", ctypes.c_int64),
        ("fetch_bytes", ctypes.c_uint64),
        ("status", ctypes.c_int32),
        ("err", ctypes.c_char_p),
    ]


class _StagedCall:
    """One call with the stager: everything `submit` was given for it,
    and what the stager learns (`flat` once the bytes are here, `status`
    and `err` if they never will be)."""

    __slots__ = ("token", "submit_id", "method", "timeout_ms", "request",
                 "resp", "staged_us", "flat", "fetch_us", "fetch_bytes",
                 "status", "err")

    def __init__(self, submit_id, method, timeout_ms, request, resp):
        self.token = 0            # set once reserved
        self.submit_id = submit_id
        self.method = method
        self.timeout_ms = timeout_ms
        self.request = request
        self.resp = resp          # (uint8 view, caller's buffer) or None
        self.fetch_us = 0
        self.fetch_bytes = 0
        self.status = 0
        self.err = b""
        if isinstance(request, _zc.PendingView) and not request.landed:
            request.waited_for()    # by the stager, in fetch()
            self.staged_us = request.started_us
            self.flat = None
        else:
            self.staged_us = _now_us()
            self.flat = _as_u8(request)

    @property
    def ready(self) -> bool:
        """Nothing left to wait for: the bytes are here, or never will be."""
        return self.flat is not None or self.status != 0

    def fetch(self) -> None:
        """Waits for the request's bytes (GIL released in the wait)."""
        t0 = _now_us()
        try:
            flat = self.request.resolve()
        except Exception as e:  # noqa: BLE001 — completes through poll
            self.status = errno.EIO
            self.err = f"request fetch failed: {e!r}".encode()
            return
        self.fetch_us = _now_us() - t0
        self.fetch_bytes = flat.nbytes
        self.flat = flat


_now_us = _zc.now_us


class ZeroCopyResponse:
    """Response bytes viewed IN PLACE from the runtime's pool blocks.

    `view()` is a zero-copy memoryview when the response is physically
    contiguous (single block — the common case for pool-block responses);
    otherwise it materializes once.  `release()` (or GC) hands the blocks
    back to the pool; views must not outlive it."""

    def __init__(self, lib, iobuf_ptr: int, nbytes: int):
        self._lib = lib
        self._ptr = iobuf_ptr
        self.nbytes = nbytes

    def view(self) -> memoryview:
        lib = self._lib
        if not self._ptr:
            raise ValueError("response already released")
        if lib.trpc_iobuf_block_count(ctypes.c_void_p(self._ptr)) == 1:
            base = lib.trpc_iobuf_block_ptr(ctypes.c_void_p(self._ptr),
                                            ctypes.c_size_t(0))
            cbuf = (ctypes.c_char * self.nbytes).from_address(base)
            # The exported buffer pins this response (mv.obj -> cbuf ->
            # self), so dropping every other reference cannot recycle the
            # pool block under a live view; an EXPLICIT release() while
            # views exist is still the caller's contract to honor.
            cbuf._owner = self
            return memoryview(cbuf).cast("B")
        return memoryview(self.tobytes())

    def tobytes(self) -> bytes:
        if not self._ptr:
            raise ValueError("response already released")
        out = ctypes.create_string_buffer(self.nbytes)
        got = self._lib.trpc_iobuf_copy_to(
            ctypes.c_void_p(self._ptr), out, ctypes.c_size_t(self.nbytes),
            ctypes.c_size_t(0))
        return out.raw[:got]

    def release(self) -> None:
        ptr, self._ptr = self._ptr, None
        if ptr:
            self._lib.trpc_iobuf_destroy(ctypes.c_void_p(ptr))

    def __len__(self) -> int:
        return self.nbytes

    def __del__(self):
        try:
            self.release()
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass


class Completion:
    """One finished call: `token`, `ok`, `status`/`error`, and the
    response — `data` is None when it landed in the caller's buffer
    (`resp_len` bytes written there), a `ZeroCopyResponse` otherwise."""

    __slots__ = ("token", "status", "error", "resp_len", "in_caller_buffer",
                 "data")

    def __init__(self, token, status, error, resp_len, in_caller_buffer,
                 data):
        self.token = token
        self.status = status
        self.error = error
        self.resp_len = resp_len
        self.in_caller_buffer = in_caller_buffer
        self.data = data

    @property
    def ok(self) -> bool:
        return self.status == 0

    def tobytes(self) -> bytes:
        """Materializes the response (b'' for empty / caller-buffer)."""
        if isinstance(self.data, ZeroCopyResponse):
            return self.data.tobytes()
        return b""

    def __repr__(self):
        state = "ok" if self.ok else f"err {self.status}: {self.error!r}"
        return f"<Completion token={self.token} {state} len={self.resp_len}>"


def _as_u8(buf) -> np.ndarray:
    """Flat uint8 view of any buffer-protocol object (no copy); of a
    `PendingView`, its bytes, waited for."""
    if isinstance(buf, _zc.PendingView):
        return buf.resolve()
    return np.frombuffer(buf, dtype=np.uint8)


def _marshal(flats, requests, resps):
    """The ctypes arrays of one native crossing: request pointers and
    lengths (a None or empty `flat` sends no bytes and takes no pin),
    landing buffers, and the pins made (ctx array, token list)."""
    n = len(flats)
    req_ptrs = (ctypes.c_void_p * n)()
    req_lens = (ctypes.c_size_t * n)()
    rb = (ctypes.c_void_p * n)()
    rc = (ctypes.c_size_t * n)()
    pin_ctxs = (ctypes.c_void_p * n)()
    pins = []
    for i, (flat, request, resp) in enumerate(zip(flats, requests, resps)):
        if flat is not None and flat.nbytes:
            req_ptrs[i] = flat.ctypes.data
            req_lens[i] = flat.nbytes
            pin_ctxs[i] = tok = _zc.pin(flat, request)
            pins.append(tok)
        if resp is not None:
            rb[i] = resp[0].ctypes.data
            rc[i] = resp[0].nbytes
    return req_ptrs, req_lens, rb, rc, pin_ctxs, pins


# An idle stager ends itself after this long; see _stager_main.
_STAGER_IDLE_S = 1.0


class Batch:
    """A submission pipeline over one Channel/ClusterChannel.

    submit() is one GIL crossing for N calls and returns their tokens
    without blocking on the network; poll() drains completions (GIL
    released while waiting).  Completions are correlation-matched by
    token, not ordered: issue order IS wire order on a single-connection
    channel, but responses complete as the server finishes them.

    The batch holds a reference to its channel; buffered completions
    remain drainable after `channel.close()` as long as nothing was in
    flight at close time."""

    def __init__(self, channel, is_cluster: bool | None = None):
        self._lib = load_library()
        if is_cluster is None:
            from brpc_tpu.rpc.client import ClusterChannel

            is_cluster = isinstance(channel, ClusterChannel)
        self._channel = channel  # keeps the native channel alive
        self._ptr = self._lib.trpc_batch_create(
            ctypes.c_void_p(channel._ptr), 1 if is_cluster else 0)
        if not self._ptr:
            raise ValueError("batch over a closed channel")
        self._resp_pins: dict[int, object] = {}
        # Serializes submit/cancel/introspection against close, and
        # counts pollers so close can wait for them to drain out of the
        # native poll before destroying the handle.
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._active_polls = 0
        # Calls with the stager, in submit order, and by token (cancel).
        self._staged: collections.deque[_StagedCall] = collections.deque()
        self._staged_by_token: dict[int, _StagedCall] = {}
        self._stager: threading.Thread | None = None
        self._stage_work = threading.Condition(self._lock)
        self._staged_submits = 0
        self._settling = False  # quiesce/close: the stager drains and ends

    def submit(self, method: str, requests, resp_bufs=None,
               timeout_ms: int = 0) -> list[int]:
        """Submits len(requests) calls in ONE crossing; returns tokens in
        request order.  Each request is any buffer-protocol object
        (bytes, numpy, memoryview) or a `zerocopy.PendingView`; its bytes
        enter the wire path by reference and stay pinned until the
        runtime drops them.  resp_bufs (optional, per-call, entries may
        be None) are WRITABLE buffers the responses land in natively —
        the zero-copy receive path; they must stay alive until their
        completion is polled.  Never waits for a request's bytes: while
        any are still on their way the calls go to the stager, and a
        call's timeout_ms starts at its native issue."""
        if not self._ptr:
            raise ValueError("batch is closed")
        n = len(requests)
        if n == 0:
            return []
        # Validate the response buffers BEFORE anything is pinned or
        # queued: a raise past that point would strand it.
        resps = [None] * n
        if resp_bufs is not None:
            if len(resp_bufs) != n:
                raise ValueError("resp_bufs length must match requests")
            for i, buf in enumerate(resp_bufs):
                if buf is None:
                    continue
                v = np.frombuffer(buf, dtype=np.uint8)
                if not v.flags.writeable:
                    raise ValueError("resp_bufs entries must be writable")
                resps[i] = (v, buf)
        pending = any(isinstance(r, _zc.PendingView) and not r.landed
                      for r in requests)
        # self._lock is held across the native crossing AND the pin
        # insertion: tokens are only known once it returns, and a
        # concurrent poller that drained a completion in that window
        # would pop a pin that isn't registered yet (leaking it for the
        # batch's lifetime).  poll() pops under the same lock, so it
        # blocks those few microseconds until the pins are in place.
        with self._lock:
            if not self._ptr:
                raise ValueError("batch is closed")
            if pending or self._staged:
                return self._stage(method, requests, resps, timeout_ms)
            flats = [_as_u8(r) for r in requests]  # raises before any pin
            tokens = (ctypes.c_uint64 * n)()
            req_ptrs, req_lens, rb, rc, pin_ctxs, pins = _marshal(
                flats, requests, resps)
            got = self._lib.trpc_batch_submit(
                ctypes.c_void_p(self._ptr), method.encode(), req_ptrs,
                req_lens, rb, rc, ctypes.c_size_t(n),
                ctypes.c_int64(timeout_ms),
                ctypes.cast(_zc.release_cb, ctypes.c_void_p), pin_ctxs,
                tokens)
            if got != n:
                for tok in pins:  # nothing was issued; undo the pins
                    _zc.unpin(tok)
                raise RuntimeError("batch rejected the submit (closing?)")
            out = list(tokens)
            for t, resp in zip(out, resps):
                if resp is not None:
                    self._resp_pins[t] = resp
        return out

    # ---- the stager (self._lock held by every _stage*/_hand* caller) ----

    def _stage(self, method, requests, resps, timeout_ms) -> list[int]:
        """Queues the calls for the stager under tokens reserved now."""
        if self._settling:
            raise RuntimeError("batch rejected the submit (closing?)")
        n = len(requests)
        self._staged_submits += 1
        calls = [_StagedCall(self._staged_submits, method, timeout_ms, r,
                             resp)
                 for r, resp in zip(requests, resps)]
        tokens = (ctypes.c_uint64 * n)()
        if self._lib.trpc_batch_reserve(
                ctypes.c_void_p(self._ptr), ctypes.c_size_t(n), tokens) != n:
            raise RuntimeError("batch rejected the submit (closing?)")
        for call, token in zip(calls, tokens):
            call.token = token
            self._staged_by_token[token] = call
            if call.resp is not None:
                self._resp_pins[token] = call.resp
        self._staged.extend(calls)
        if self._stager is None:
            self._stager = threading.Thread(
                target=self._stager_main, name="trpc-batch-stager",
                daemon=True)
            self._stager.start()
        self._stage_work.notify()
        return list(tokens)

    def _stager_main(self) -> None:
        """Waits for the bytes of the head call and of the calls that were
        submitted with it (their transfers were started together and share
        the link, so they land together, and what the caller submitted as
        one crossing stays one), then hands them and every ready call
        behind them to the native submit; so on in submit order."""
        while True:
            with self._lock:
                while not self._staged and not self._settling:
                    if not self._stage_work.wait(timeout=_STAGER_IDLE_S):
                        if not self._staged and not self._settling:
                            # Idle: end, so that a pipeline its caller
                            # dropped is not kept alive by its own thread;
                            # the next pending submit starts another.
                            self._stager = None
                            return
                if self._settling:
                    for call in self._staged:
                        if not call.ready:
                            call.status = errno.ECANCELED
                            call.err = b"canceled while staged"
                    self._hand_over_ready()
                    return
                head = self._staged[0].submit_id
                together = list(itertools.takewhile(
                    lambda call: call.submit_id == head, self._staged))
            for call in together:
                if self._settling:
                    break           # the rest is canceled, not waited for
                if not call.ready:  # else: its bytes were there, or it
                    call.fetch()    # was canceled meanwhile
            with self._lock:
                self._hand_over_ready()

    def _hand_over_ready(self) -> None:
        """Every ready call at the front of the queue crosses, one native
        submit per run of calls with the same method and timeout."""
        staged = self._staged
        while staged and staged[0].ready:
            key = (staged[0].method, staged[0].timeout_ms)
            group = []
            while (staged and staged[0].ready
                   and (staged[0].method, staged[0].timeout_ms) == key):
                group.append(staged.popleft())
            self._hand_over(group)

    def _hand_over(self, group) -> None:
        n = len(group)
        stages = (BatchStage * n)()
        for st, call in zip(stages, group):
            del self._staged_by_token[call.token]
            st.token = call.token
            st.staged_us = call.staged_us
            st.fetch_us = call.fetch_us
            st.fetch_bytes = call.fetch_bytes
            st.status = call.status
            st.err = call.err or None
        req_ptrs, req_lens, rb, rc, pin_ctxs, pins = _marshal(
            [c.flat if c.status == 0 else None for c in group],
            [c.request for c in group], [c.resp for c in group])
        got = self._lib.trpc_batch_submit_staged(
            ctypes.c_void_p(self._ptr), group[0].method.encode(), req_ptrs,
            req_lens, rb, rc, ctypes.c_size_t(n),
            ctypes.c_int64(group[0].timeout_ms),
            ctypes.cast(_zc.release_cb, ctypes.c_void_p), pin_ctxs, stages)
        if got != n:  # the native side is closing: the calls are dropped
            for tok in pins:
                _zc.unpin(tok)
            for call in group:
                self._resp_pins.pop(call.token, None)

    def _settle_stager(self) -> None:
        """The stager hands over what has landed, completes the rest with
        ECANCELED and ends; no submit queues behind it afterwards."""
        with self._lock:
            self._settling = True
            stager = self._stager
            self._stage_work.notify_all()
        if stager is not None:
            stager.join()

    def poll(self, max_n: int = 64, timeout_ms: int = -1) -> list[Completion]:
        """Drains up to max_n completions, blocking OUTSIDE the GIL until
        at least one is ready or timeout_ms passes (0 = non-blocking,
        < 0 = wait forever).  Returns [] on timeout, and early (with
        whatever is buffered) once the batch is closing."""
        arr = (BatchCompletion * max_n)()
        with self._lock:
            if not self._ptr:
                raise ValueError("batch is closed")
            ptr = self._ptr
            self._active_polls += 1
        try:
            # The native handle stays valid for the whole call: close()
            # quiesces (which wakes parked pollers out of the wait) and
            # only destroys after _active_polls drains to zero.
            got = self._lib.trpc_batch_poll(
                ctypes.c_void_p(ptr), arr, ctypes.c_size_t(max_n),
                ctypes.c_int64(timeout_ms))
        finally:
            with self._lock:
                self._active_polls -= 1
                self._cond.notify_all()
        out = []
        if got:
            with self._lock:  # one locked pass, not one lock per record
                for i in range(got):
                    self._resp_pins.pop(arr[i].token, None)
        for i in range(got):
            c = arr[i]
            data = None
            if c.resp_iobuf:
                data = ZeroCopyResponse(self._lib, c.resp_iobuf, c.resp_len)
            out.append(Completion(
                token=c.token, status=c.status,
                error=c.err.decode(errors="replace") if c.status else "",
                resp_len=c.resp_len,
                in_caller_buffer=bool(c.resp_copied), data=data))
        return out

    def cancel(self, token: int) -> bool:
        """Best-effort cancel of one member: an in-flight call completes
        with ECANCELED via the runtime's StartCancel; a call that already
        completed (or was polled) is untouched.  True when the token was
        still live."""
        with self._lock:  # the native call is quick and must not race
            if not self._ptr:  # a concurrent destroy
                return False
            call = self._staged_by_token.get(token)
            if call is not None:
                # Still with the stager: it never reaches the wire, and
                # completes now, out of turn, like any canceled call.
                call.status = errno.ECANCELED
                call.err = b"canceled while staged"
                self._staged.remove(call)
                self._hand_over([call])
                return True
            return self._lib.trpc_batch_cancel(
                ctypes.c_void_p(self._ptr), ctypes.c_uint64(token)) == 0

    @property
    def outstanding(self) -> int:
        """Calls submitted but not yet drained by poll()."""
        with self._lock:
            if not self._ptr:
                return 0
            return len(self._staged) + self._lib.trpc_batch_outstanding(
                ctypes.c_void_p(self._ptr))

    @property
    def inflight(self) -> int:
        """Calls still in flight (not yet completed into the ring).
        Zero means the batch no longer needs its channel: everything has
        settled, and only buffered completions remain to drain."""
        with self._lock:
            if not self._ptr:
                return 0
            return len(self._staged) + self._lib.trpc_batch_inflight(
                ctypes.c_void_p(self._ptr))

    def quiesce(self) -> None:
        """Rejects further submits, cancels in-flight members and waits
        for them to settle; buffered completions remain pollable.  After
        this the batch no longer touches its channel (Channel.close runs
        it on every live pipeline before destroying the native channel)."""
        self._settle_stager()
        with self._lock:  # held across the call so a concurrent close
            if self._ptr:  # cannot destroy the handle mid-quiesce
                self._lib.trpc_batch_quiesce(ctypes.c_void_p(self._ptr))

    def close(self) -> None:
        """Cancels in-flight members, waits for them (and any poller on
        another thread) to settle, frees unpolled completions."""
        self._settle_stager()  # while the handle it crosses with lives
        with self._lock:
            ptr, self._ptr = self._ptr, None
        if ptr:
            # Quiesce wakes parked pollers; they observe the closed state
            # and drain out.  Destroy only once none is inside the native
            # poll — the handle dies with nobody touching it.
            self._lib.trpc_batch_quiesce(ctypes.c_void_p(ptr))
            with self._lock:
                while self._active_polls > 0:
                    self._cond.wait(timeout=1.0)
            self._lib.trpc_batch_destroy(ctypes.c_void_p(ptr))
        with self._lock:
            # Only after quiesce settled the in-flight members: a pin is
            # what keeps a caller-dropped landing buffer alive, and an
            # in-flight completion memcpys into it natively.
            self._resp_pins.clear()
        self._channel = None

    def __del__(self):
        try:
            self.close()
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass


def call_batch(channel, method: str, requests, resp_bufs=None,
               timeout_ms: int = 0):
    """Synchronous batched call: submits all requests in one crossing,
    waits for every completion, returns results ALIGNED with `requests`.
    Per-call error isolation: a failed member yields an `RpcError`
    INSTANCE at its position (not raised), everything else completes
    normally.  Success entries are `bytes` (or None when the response
    landed in the matching resp_bufs entry).  Runs on its own private
    pipeline — a shared one could hand it completions belonging to other
    submitters."""
    from brpc_tpu.rpc.client import make_rpc_error

    b = Batch(channel)
    track = getattr(channel, "_track_pipeline", None)
    if track is not None:
        track(b)  # channel.close() on another thread settles us first
    try:
        tokens = b.submit(method, requests, resp_bufs=resp_bufs,
                          timeout_ms=timeout_ms)
        want = set(tokens)
        by_token: dict[int, object] = {}
        while want:
            for c in b.poll(max_n=len(want), timeout_ms=-1):
                want.discard(c.token)
                if not c.ok:
                    by_token[c.token] = make_rpc_error(
                        channel._lib, c.status, c.error)
                elif c.in_caller_buffer:
                    by_token[c.token] = None
                elif c.data is not None:
                    by_token[c.token] = c.data.tobytes()
                    c.data.release()
                else:
                    by_token[c.token] = b""
        return [by_token[t] for t in tokens]
    finally:
        b.close()
