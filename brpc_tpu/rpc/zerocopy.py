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
  `PendingView`, and whoever needs the bytes first waits for them — the
  batch pipeline's stager thread (batch.py), so the caller's thread is
  free for the responses while its requests are on their way.

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
"""

from __future__ import annotations

import ctypes
import threading
import time

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
# (numpy's PyDataMem_SetHandler, the recycling handler's capsule): made by
# the first transfer, so a process that stages no device array loads
# nothing.
_landing = None


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
            _landing = (set_handler, capsule)
        return _landing


def _start_transfer(array) -> None:
    """`array.copy_to_host_async()` with the block it lands in taken from
    the recycling handler (the module's docstring).  numpy's current
    handler is the thread's own (a context variable), so it is set here,
    on the thread that makes the call, and put back."""
    set_handler, capsule = _landing or _landing_handler()
    previous = set_handler(capsule)
    try:
        array.copy_to_host_async()
    finally:
        set_handler(previous)


class PendingView:
    """The bytes of an array whose transfer to the host has been asked for
    and not yet waited for.  The transfer starts when the view is made;
    `nbytes` is known at once; `resolve()` blocks its first caller until
    the bytes have landed (the wait releases the GIL) and returns the flat
    uint8 view of the array's own cached host copy, the same one to every
    caller; `shape` is the array's.  `started_us` is the monotonic clock
    (the native runtime's) at which the view was made.  How many transfers
    are on their way at once is up to the caller: each holds one landing
    block of its size until the view and the array are dropped."""

    def __init__(self, array):
        self.nbytes = int(array.nbytes)
        self._array = array
        self._flat = None
        self._lock = threading.Lock()
        self.started_us = time.monotonic_ns() // 1000
        _start_transfer(array)

    @property
    def landed(self) -> bool:
        """The bytes are here: `resolve()` will not block."""
        return self._flat is not None

    @property
    def shape(self) -> tuple:
        return tuple(self._array.shape)

    def resolve(self) -> np.ndarray:
        with self._lock:
            if self._flat is None:
                self._flat = _flat_u8(np.asarray(self._array))
            return self._flat

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
