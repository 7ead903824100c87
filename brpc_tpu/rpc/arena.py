"""Device staging arena — zero-copy payload path between JAX and the C++
runtime.

Parity: the fork's RDMA block_pool
(/root/reference/src/brpc/rdma/block_pool.cpp) registers memory once and
lets IOBufs carry it without copies.  TPU-native form: the C++ DeviceArena
(cpp/base/device_arena.h) owns registered staging slabs; Python wraps a
block as a writable numpy view, a device array lands in it with ONE
device→host DMA (`jax.device_get`-style — the transport hop itself, the
analogue of the NIC DMA), and the block then rides the RPC data path with
zero further host copies (`trpc_iobuf_append_block` hands the block to the
IOBuf by reference; writev sends straight from it).
"""

from __future__ import annotations

import ctypes

import numpy as np

from brpc_tpu.rpc._lib import load_library as load


class DeviceArena:
    """Registered staging-slab allocator (C++ DeviceArena)."""

    def __init__(self, block_size: int = 256 * 1024,
                 blocks_per_slab: int = 32, shm_backed: bool = False):
        self._lib = load()
        self._lib.trpc_arena_create.restype = ctypes.c_void_p
        self._lib.trpc_arena_alloc.restype = ctypes.c_void_p
        self._ptr = self._lib.trpc_arena_create(
            ctypes.c_uint32(block_size), ctypes.c_uint32(blocks_per_slab),
            ctypes.c_int(1 if shm_backed else 0))
        self.block_size = int(
            self._lib.trpc_arena_block_size(ctypes.c_void_p(self._ptr)))

    def alloc(self) -> "ArenaBlock":
        data = ctypes.c_void_p()
        meta = ctypes.c_uint64()
        block = self._lib.trpc_arena_alloc(
            ctypes.c_void_p(self._ptr), ctypes.byref(data),
            ctypes.byref(meta))
        if not block:
            raise MemoryError("device arena exhausted")
        return ArenaBlock(self, block, data.value, meta.value)

    @property
    def blocks_in_use(self) -> int:
        return int(self._lib.trpc_arena_blocks_in_use(
            ctypes.c_void_p(self._ptr)))

    def close(self) -> None:
        if self._ptr:
            self._lib.trpc_arena_destroy(ctypes.c_void_p(self._ptr))
            self._ptr = None

    def __del__(self):  # pragma: no cover - interpreter teardown
        try:
            self.close()
        except Exception:
            pass


class ArenaBlock:
    """One staging block; fill `view` then send (send consumes it)."""

    def __init__(self, arena: DeviceArena, handle, data_ptr: int,
                 meta: int):
        self.arena = arena
        self.handle = handle
        self.meta = meta  # (slab_id << 32 | offset) — the lkey analogue
        buf = (ctypes.c_char * arena.block_size).from_address(data_ptr)
        self.view = np.frombuffer(buf, dtype=np.uint8)  # writable, no copy

    def put(self, array) -> int:
        """Lands a (host or device) array's bytes in the staging block.
        Host-backed arrays enter via a dlpack VIEW (one memcpy into the
        slab, no intermediate); TPU-resident arrays take one device→host
        DMA then the memcpy.  Returns the byte length.  For the fully
        copy-free path, see rpc.zerocopy.append_jax — a slab only pays off
        when the block must live in registered/shm-backed memory."""
        from brpc_tpu.rpc.zerocopy import host_bytes

        flat, _owner = host_bytes(array)
        n = flat.size
        if n > self.view.size:
            raise ValueError(f"{n} bytes > block size {self.view.size}")
        np.copyto(self.view[:n], flat)
        return n

    def release(self) -> None:
        if self.handle:
            self.arena._lib.trpc_arena_release(
                ctypes.c_void_p(self.arena._ptr),
                ctypes.c_void_p(self.handle))
            self.handle = None


def call_with_block(channel, method: str, block: ArenaBlock,
                    length: int, timeout_ms: int = 0) -> bytes:
    """Sync RPC whose request payload is the arena block's [0, length)
    bytes, entering the IOBuf WITHOUT copying (block reference handoff).
    The block is consumed; returns the response bytes."""
    lib = block.arena._lib
    lib.trpc_iobuf_create.restype = ctypes.c_void_p
    req = lib.trpc_iobuf_create()
    resp = lib.trpc_iobuf_create()
    try:
        rc = lib.trpc_iobuf_append_block(ctypes.c_void_p(req),
                                         ctypes.c_void_p(block.handle),
                                         ctypes.c_uint32(length))
        block.handle = None  # consumed either way
        if rc != 0:
            raise ValueError(f"length {length} exceeds block capacity")
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
