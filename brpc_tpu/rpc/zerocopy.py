"""Zero-copy JAX→wire path: a device array's bytes enter the C++ IOBuf by
reference, with no host-side copies at all.

Parity: the fork's RDMA path hands NIC-registered memory to IOBufs without
copying (/root/reference/src/brpc/rdma/block_pool.cpp allocation takeover,
/root/reference/src/butil/iobuf.h:257 append_user_data_with_meta).  The
TPU-native form inverts the ownership: instead of making JAX allocate into
our slabs (PJRT offers no host-destination transfer), we export the JAX
buffer itself:

- Host-backed buffers (the CPU mesh; any host-visible backend): dlpack
  import yields a numpy VIEW of the very bytes JAX owns — `append_jax`
  hands that pointer to `IOBuf::append_user_data`, the wire writes straight
  from it, and a deleter keeps the array alive until the last IOBuf
  reference drops.  Zero copies, pointer-identity verifiable.
- TPU-resident buffers: dlpack import fails (libtpu: the device "cannot be
  used as a DLPack device"; `unsafe_buffer_pointer()` does return a
  pointer there, but not one that addresses the payload —
  tools/PJRT_PROBE.md), so exactly ONE device→host DMA runs (the
  transport hop itself, the NIC-DMA analogue) and the RESULTING host buffer
  enters the IOBuf by reference.  One copy total, where landing in a
  temporary and copying into a slab would take two.  That DMA is
  only STARTED by `host_view` (`copy_to_host_async`): it returns a
  `PendingView`, and the caller's thread is free for the responses while
  its requests are on their way.

In what form it crosses: an array of 16-bit elements and two or more
dimensions lies tiled in HBM, and the runtime undoes the tiling on the
host, slowly: 43 MB of (20, 8480, 128) uint16 took 55 ms to land on the
v5e host (0.78 GB/s) whoever waited for it, where the same bytes as 32-bit
words take 5-9 ms (PERF.md, PR 32).  `_crossing_form` has the device lay
such an array out as one flat run of 32-bit words first (0.2 ms of the
caller's thread for the launch, under 1 ms of the device), the same bytes
in the same order, and that is what is transferred; a view hands its
bytes out flat anyway.

Who waits for a started transfer: somebody, from the moment it starts.
In `kv_disagg`'s loop a 9 MB page whose transfer was started three blocks
(52 ms) ahead still cost its caller 4.9 ms when it came back for it,
every block, although that transfer lands in 2.8 ms when it is waited for
at once (and, in a process that does nothing else, with nobody waiting at
all: why it stalls beside a client thread's other transfers is not
known; PERF.md, PR 32).  So a view is handed, when it is made, to the
module's one waiter thread (`_ViewWaiter`), which waits for the views in
the order they were started, with the same `np.asarray` under the view's
own lock that `resolve()` takes: a later `resolve()` finds the bytes
there, or returns the moment the waiter's wait ends.  Two things the
waiter can observe make it leave a view alone: a view under the landing
pool's size line (`trpc_host_pool_min_bytes`, 1 MB: below it a transfer
costs its latency, not its bytes, and a thread's wake costs more than it
saves) is never queued, and a view whose `resolve()` somebody has already
entered, or is about to (`waited_for`: the batch pipeline's stager), is
skipped.  The waiter's queue holds a view weakly and the thread holds it
only while it waits, and it ends itself when idle.

Where that DMA lands: jaxlib allocates the destination as a numpy array,
so through numpy's current data-memory handler (NEP 49), and glibc would
serve a block that large from fresh `mmap` pages every time: 16,384
first-touch faults under every 64 MB fetch, five times the cost of the
bytes (PERF.md, PR 25 and PR 28).  Every transfer is therefore started
(`_start_transfer`) with the recycling handler of cpp/capi/hostpool_capi.cc
current, for that one call and on that one thread: a block of 1 MB or
more that a fetch landed in is kept when numpy frees it and handed to the
next fetch of that size, from whatever thread asks.  Nothing else in the
process is allocated there.  The price is memory the process keeps: up
to 1 GB of such blocks may lie idle (the blocks of one pipeline at depth
8 and 64 MB are 0.7 GB), and they go back to the kernel only past that
bound or through `trpc_host_pool_trim`.  Every view's transfer starts when
the view is made: the memory of fetches on their way is bounded by the
depth their caller keeps open, not here.

What such a block is: a registered shm region (`rma_alloc`), so that the
KV store, which serves registered memory only, publishes a page or a
sequence out of the very block its transfer landed in (`kv.py`
`_publish_records`; no copy into a slab).  A block a record was published
from stays out of the recycled list, whatever becomes of the view and the
array, until the record is withdrawn, evicted or replaced and no response
serves its bytes any more: the pool reads that off the region's own
reference count (PERF.md, PR 34).
"""

from __future__ import annotations

import collections
import ctypes
import threading
import time
import typing
import weakref

import numpy as np

from brpc_tpu.rpc._lib import load_library


_DELETER_T = ctypes.CFUNCTYPE(None, ctypes.c_void_p, ctypes.c_void_p)

# Arrays whose bytes are on the wire, keyed by token; the entry (and with
# it the last Python reference) drops when the C++ side runs the deleter.
_live: dict[int, tuple] = {}
_lock = threading.Lock()
_next_token = 1


@_DELETER_T
def _release(data, ctx):  # noqa: ARG001 - data unused, identity is ctx
    # Runs on whatever thread drops the last IOBuf reference (usually a
    # fiber worker after the wire write); ctypes re-acquires the GIL.
    with _lock:
        _live.pop(ctx, None)


def live_sends() -> int:
    """Number of buffers currently pinned by in-flight sends (tests).
    One registry serves every zero-copy producer (per-call `append_jax`
    AND the batch pipeline), so a pin leaked by either is visible here."""
    with _lock:
        return len(_live)


# The CFUNCTYPE deleter for ctypes callers outside this module (the batch
# pipeline): pass as the request deleter with a `pin(...)` token as ctx.
release_cb = _release


def pin(*objs) -> int:
    """Registers `objs` in the live-send registry and returns the token
    to hand the native side as deleter ctx (with `release_cb`); the
    entry — and the last Python reference to the pinned buffers — drops
    when the runtime runs the deleter."""
    global _next_token
    with _lock:
        token = _next_token
        _next_token += 1
        _live[token] = objs
    return token


def unpin(token: int) -> None:
    """Drops a pin that was never handed to the native side (failed
    submit paths); a pin the runtime owns is released by its deleter."""
    with _lock:
        _live.pop(token, None)


def _flat_u8(host: np.ndarray) -> np.ndarray:
    return host.reshape(-1).view(np.uint8)


_MEM_HANDLER = b"mem_handler"  # the capsule's name, which numpy checks


class _Landing(typing.NamedTuple):
    """What a transfer needs of numpy and of cpp/capi/hostpool_capi.cc:
    made by the first transfer, so a process that stages no device array
    loads nothing."""

    set_handler: typing.Callable    # numpy's PyDataMem_SetHandler
    capsule: object                 # the recycling handler's
    min_waited_bytes: int           # the pool's size line (1 MB)
    note_view: typing.Callable      # trpc_host_view_note


_landing: _Landing | None = None


def _landing_handler():
    global _landing
    with _lock:
        if _landing is None:
            # numpy's C API table, as every compiled extension reaches it
            # (`import_array`); entry 304 since numpy 1.22.
            from numpy._core import _multiarray_umath

            api = ctypes.pythonapi
            api.PyCapsule_GetPointer.restype = ctypes.c_void_p
            api.PyCapsule_GetPointer.argtypes = [ctypes.py_object,
                                                 ctypes.c_char_p]
            api.PyCapsule_New.restype = ctypes.py_object
            api.PyCapsule_New.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                          ctypes.c_void_p]
            table = ctypes.cast(
                api.PyCapsule_GetPointer(_multiarray_umath._ARRAY_API, None),
                ctypes.POINTER(ctypes.c_void_p))
            set_handler = ctypes.PYFUNCTYPE(
                ctypes.py_object, ctypes.py_object)(table[304])
            lib = load_library()
            lib.trpc_host_pool_numpy_handler.restype = ctypes.c_void_p
            capsule = api.PyCapsule_New(
                lib.trpc_host_pool_numpy_handler(), _MEM_HANDLER, None)
            lib.trpc_host_pool_min_bytes.restype = ctypes.c_size_t
            lib.trpc_host_pool_min_bytes.argtypes = []
            lib.trpc_host_view_note.restype = None
            lib.trpc_host_view_note.argtypes = [
                ctypes.c_uint64, ctypes.c_int, ctypes.c_int64,
                ctypes.c_int64]
            _landing = _Landing(set_handler, capsule,
                                lib.trpc_host_pool_min_bytes(),
                                lib.trpc_host_view_note)
        return _landing


def _start_transfer(array) -> None:
    """`array.copy_to_host_async()` with the block it lands in taken from
    the recycling handler (the module's docstring).  numpy's current
    handler is the thread's own (a context variable), so it is set here,
    on the thread that makes the call, and put back."""
    landing = _landing or _landing_handler()
    previous = landing.set_handler(landing.capsule)
    try:
        array.copy_to_host_async()
    finally:
        landing.set_handler(previous)


def landing_block(nbytes: int) -> np.ndarray:
    """An uninitialised uint8 array of `nbytes` allocated with the
    recycling handler current, as a transfer's destination is: from 1 MB
    on its pages come from the recycled list and go back to it when the
    array dies.  For bytes that arrive from the wire on their way to the
    device (`stream.Stream.read_block`)."""
    landing = _landing or _landing_handler()
    previous = landing.set_handler(landing.capsule)
    try:
        return np.empty(nbytes, dtype=np.uint8)
    finally:
        landing.set_handler(previous)


_as_words = None    # the jitted relayout, made by the first array it serves


def _crossing_form(array):
    """The array as it crosses to the host: itself, or where its own form
    crosses slowly (the module's docstring: 16-bit elements, two or more
    dimensions), its bytes as one flat run of 32-bit words, made on the
    device.  Below the landing pool's size line the launch costs more than
    the crossing; an odd minor dimension has no whole words."""
    global _as_words
    dtype = getattr(array, "dtype", None)
    if (dtype is None or dtype.itemsize != 2 or array.ndim < 2
            or array.shape[-1] % 2
            or array.nbytes < _landing.min_waited_bytes
            or not hasattr(array, "sharding")):
        return array
    if _as_words is None:
        import jax

        def view_words(x):
            # Neighbours along the minor dimension share a word, the
            # first in its low half: the array's own bytes in row-major
            # order on a little-endian host.  (A reshape to (n, 2) and a
            # bitcast says the same and pads the pairs to whole tiles.)
            halves = jax.lax.bitcast_convert_type(x, "uint16")
            low = halves[..., 0::2].astype("uint32")
            high = halves[..., 1::2].astype("uint32")
            return (low | (high << 16)).reshape(-1)

        _as_words = jax.jit(view_words)
    return _as_words(array)


def now_us() -> int:
    """CLOCK_MONOTONIC in microseconds: the clock of the native runtime's
    phase stamps, and of `PendingView.started_us`."""
    return time.monotonic_ns() // 1000


# An idle waiter ends itself after this long, as the batch stager does.
_WAITER_IDLE_S = 1.0


class _ViewWaiter:
    """The one thread that sees started transfers through (the module's
    docstring): views in the order they were started.  Its queue holds
    them weakly and the thread holds one only while it waits for it, so a
    landing block goes back to the recycled list the moment its caller
    lets go of the view and the array, as without a waiter."""

    def __init__(self):
        self._wake = threading.Condition()
        self._views: collections.deque = collections.deque()
        self._thread: threading.Thread | None = None

    def watch(self, view: "PendingView") -> None:
        with self._wake:
            self._views.append(weakref.ref(view))
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._main, name="trpc-view-waiter", daemon=True)
                self._thread.start()
            self._wake.notify()

    def _main(self) -> None:
        while True:
            with self._wake:
                while not self._views:
                    if (not self._wake.wait(timeout=_WAITER_IDLE_S)
                            and not self._views):
                        # Idle: end; the next view starts another.
                        self._thread = None
                        return
                view = self._views.popleft()()
            if view is not None:
                view._see_through()
                del view


_waiter = _ViewWaiter()


class PendingView:
    """The bytes of an array whose transfer to the host has been asked for.
    The transfer starts when the view is made (of the array's crossing
    form, `_crossing_form`), and from then on somebody waits for it: the
    module's waiter thread, unless the view is under the landing pool's
    size line (1 MB) or its `resolve()` was entered first (the module's
    docstring says why: a transfer started ahead and left alone was not
    there when its caller came back).  `nbytes` is known at once;
    `resolve()` blocks until the bytes have landed (the wait releases the
    GIL), which is not at all once the waiter's wait has ended, and
    returns the flat uint8 view of the host copy, the same one to every
    caller; `shape` is the array's.  A transfer that fails raises from
    `resolve()` on its caller's thread.  `started_us` is the monotonic
    clock (the native runtime's) at which the view was made.  How many
    transfers are on their way at once is up to the caller: each holds one
    landing block of its size until the view and the array are dropped."""

    def __init__(self, array):
        self.nbytes = int(array.nbytes)
        self._array = array
        self._flat = None
        self._lock = threading.Lock()
        self._entered = False   # a resolve() other than the waiter's began
        self._noted = False     # ... and the first has been counted
        self._landed_us = 0
        self.started_us = now_us()
        landing = _landing or _landing_handler()
        self._crossing = _crossing_form(array)
        _start_transfer(self._crossing)
        if self.nbytes >= landing.min_waited_bytes:
            _waiter.watch(self)

    @property
    def landed(self) -> bool:
        """A `resolve()` of the caller's has returned the bytes: the next
        will not block.  Not what the waiter has seen through: a view is
        handed to a pipeline the same way whether or not the waiter was
        first (batch.py stages what has not `landed`)."""
        return self._noted

    @property
    def shape(self) -> tuple:
        return tuple(self._array.shape)

    def waited_for(self) -> None:
        """The caller has a thread of its own that is about to block in
        `resolve()` (the batch pipeline's stager): as good as entered, so
        the module's waiter leaves the view to it."""
        self._entered = True

    def _wait(self) -> np.ndarray:
        # self._lock held.
        if self._flat is None:
            self._flat = _flat_u8(np.asarray(self._crossing))
            self._landed_us = now_us()
        return self._flat

    def _see_through(self) -> None:
        """The waiter's part: the wait itself, unless somebody else has
        begun it.  What the fetch raises is its caller's to see."""
        if self._entered or not self._lock.acquire(blocking=False):
            return
        try:
            self._wait()
        except Exception:  # noqa: BLE001 - raised again by resolve()
            pass
        finally:
            self._lock.release()

    def resolve(self) -> np.ndarray:
        if self._noted:
            return self._flat
        self._entered = True
        ahead = self._flat is not None
        t0 = now_us()
        with self._lock:
            flat = self._wait()
            first, self._noted = not self._noted, True
        if first:
            _landing.note_view(self.nbytes, ahead, now_us() - t0,
                               self._landed_us - self.started_us)
        return flat

    def __array__(self, dtype=None, copy=None):
        return self.resolve()


def host_view(array):
    """(flat_uint8_view, owner): host-visible bytes of a JAX/numpy array
    with the minimum number of copies — zero for host-backed buffers
    (dlpack import), exactly one device→host DMA otherwise.  Where the
    bytes are not host-visible and the array can start its own transfer,
    the view is a `PendingView` and this returns without waiting for it;
    `host_bytes` is the same with the wait."""
    try:
        host = np.from_dlpack(array)
    except (RuntimeError, TypeError, BufferError, AttributeError):
        if hasattr(array, "copy_to_host_async"):
            return PendingView(array), array
        host = np.asarray(array)
    return _flat_u8(host), host


def host_bytes(array):
    """`host_view` for callers that read the bytes at once: (flat uint8
    numpy view, owner), any transfer waited for."""
    view, owner = host_view(array)
    if isinstance(view, PendingView):
        view = view.resolve()
    return view, owner


def append_jax(iobuf_ptr: int, array, lib=None) -> int:
    """Appends `array`'s bytes to a trpc_iobuf by REFERENCE (no copy beyond
    the unavoidable device→host DMA for TPU-resident arrays).  The array is
    kept alive until the IOBuf drops it.  Returns the byte length."""
    global _next_token
    lib = lib or load_library()
    flat, owner = host_bytes(array)
    with _lock:
        token = _next_token
        _next_token += 1
        # Keep `flat` itself alive, not just its parents: reshape(-1) on a
        # NON-contiguous view returns a fresh buffer, and pinning only
        # (owner, array) would leave the IOBuf holding a dangling pointer.
        _live[token] = (flat, owner, array)
    lib.trpc_iobuf_append_user_data(
        ctypes.c_void_p(iobuf_ptr),
        ctypes.c_void_p(flat.ctypes.data),
        ctypes.c_size_t(flat.size),
        _release,
        ctypes.c_void_p(token))
    return flat.size


def call_zero_copy(channel, method: str, array, timeout_ms: int = 0) -> bytes:
    """Sync RPC whose request payload is `array`'s bytes entering the wire
    path without host copies.  Returns the response bytes."""
    lib = channel._lib
    lib.trpc_iobuf_create.restype = ctypes.c_void_p
    req = lib.trpc_iobuf_create()
    resp = lib.trpc_iobuf_create()
    try:
        append_jax(req, array, lib)
        err = ctypes.create_string_buffer(256)
        rc = lib.trpc_channel_call_buf(
            ctypes.c_void_p(channel._ptr), method.encode(),
            ctypes.c_void_p(req), ctypes.c_void_p(resp),
            ctypes.c_int64(timeout_ms), err, ctypes.c_size_t(len(err)))
        if rc != 0:
            from brpc_tpu.rpc.client import RpcError

            raise RpcError(rc, err.value.decode(errors="replace"))
        n = lib.trpc_iobuf_size(ctypes.c_void_p(resp))
        out = ctypes.create_string_buffer(n)
        lib.trpc_iobuf_copy_to(ctypes.c_void_p(resp), out,
                               ctypes.c_size_t(n), ctypes.c_size_t(0))
        return out.raw
    finally:
        lib.trpc_iobuf_destroy(ctypes.c_void_p(req))
        lib.trpc_iobuf_destroy(ctypes.c_void_p(resp))


def alloc_staging(nbytes: int, lib=None) -> np.ndarray:
    """Allocates a REGISTERED ICI staging slab and returns a uint8 numpy
    view over it (no copy).  Bytes living here cross ici ring connections
    as SENDER-OWNED descriptors — one descriptor per payload, no ring DMA
    copy, receiver wraps them in place (cpp/net/ici_transport.h; the rdma
    block_pool takeover analogue).  Land device fetches here
    (np.copyto(view, np.asarray(dev_array))) and pass view.ctypes.data to
    the native call APIs.  Free with free_staging() only after every RPC
    referencing the region has completed."""
    lib = lib or load_library()
    lib.trpc_ici_staging_alloc.restype = ctypes.c_void_p
    lib.trpc_ici_staging_alloc.argtypes = [
        ctypes.c_size_t, ctypes.POINTER(ctypes.c_uint32)]
    ordinal = ctypes.c_uint32()
    base = lib.trpc_ici_staging_alloc(nbytes, ctypes.byref(ordinal))
    if not base:
        raise MemoryError(f"ici staging alloc of {nbytes} bytes failed")
    view = np.frombuffer(
        (ctypes.c_char * nbytes).from_address(base), dtype=np.uint8)
    with _lock:
        _staging[int(base)] = True
    return view


def free_staging(view: np.ndarray, lib=None) -> None:
    """Unregisters and unlinks a slab from alloc_staging; the unmap is
    deferred past any in-flight wrapped references by the native
    refcount.  Pass the slab-base view (what alloc_staging returned, or
    any zero-offset view of it — resolution is by base address); no view
    or slice may be used afterwards."""
    lib = lib or load_library()
    base = int(view.ctypes.data)
    with _lock:
        known = _staging.pop(base, None)
    if known is not None:
        lib.trpc_ici_staging_free.argtypes = [ctypes.c_void_p]
        lib.trpc_ici_staging_free(ctypes.c_void_p(base))


def zero_copy_counters(lib=None) -> tuple[int, int]:
    """Process-wide (descriptors, bytes) sent via the sender-owned path —
    asserts that a staged payload really elided the ring copy."""
    lib = lib or load_library()
    lib.trpc_ici_zero_copy_counters.argtypes = [
        ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint64)]
    wrs, nbytes = ctypes.c_uint64(), ctypes.c_uint64()
    lib.trpc_ici_zero_copy_counters(ctypes.byref(wrs), ctypes.byref(nbytes))
    return wrs.value, nbytes.value


_staging: dict[int, int] = {}


def block_ptr(iobuf_ptr: int, index: int = 0, lib=None) -> int:
    """Data pointer of an IOBuf block ref (pointer-identity tests)."""
    lib = lib or load_library()
    lib.trpc_iobuf_block_ptr.restype = ctypes.c_void_p
    return lib.trpc_iobuf_block_ptr(ctypes.c_void_p(iobuf_ptr),
                                    ctypes.c_size_t(index)) or 0
