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
  tools/PJRT_PROBE.md), so exactly ONE device→host DMA runs (`np.asarray` — the
  transport hop itself, the NIC-DMA analogue) and the RESULTING host buffer
  enters the IOBuf by reference.  One copy total, where the round-2 arena
  path took two (DMA into a temporary, memcpy into the slab).
"""

from __future__ import annotations

import ctypes
import threading

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


def host_view(array):
    """(flat_uint8_view, owner): host-visible bytes of a JAX/numpy array
    with the minimum number of copies — zero for host-backed buffers
    (dlpack import), exactly one device→host DMA otherwise."""
    try:
        host = np.from_dlpack(array)
    except (RuntimeError, TypeError, BufferError, AttributeError):
        host = np.asarray(array)
    return host.reshape(-1).view(np.uint8), host


def append_jax(iobuf_ptr: int, array, lib=None) -> int:
    """Appends `array`'s bytes to a trpc_iobuf by REFERENCE (no copy beyond
    the unavoidable device→host DMA for TPU-resident arrays).  The array is
    kept alive until the IOBuf drops it.  Returns the byte length."""
    global _next_token
    lib = lib or load_library()
    flat, owner = host_view(array)
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
