"""Zero-copy paths between JAX arrays and the C++ RPC runtime: the array's
own host buffer on the wire by reference (`zerocopy.append_jax`), and a
registered ici staging slab whose bytes cross as sender-owned descriptors
(RDMA block_pool parity; the native `DeviceArena` under it is tested in
cpp/tests/test_rpc.cc `device_arena_zero_copy_rpc`)."""

import time

import jax.numpy as jnp
import numpy as np
import pytest

from brpc_tpu.rpc.client import Channel
from brpc_tpu.rpc.server import Server


@pytest.fixture(scope="module")
def echo_server():
    srv = Server()
    srv.register("Echo.Echo", lambda call, req: call.respond(req))
    srv.start(0)
    yield srv
    srv.stop()


def test_zero_copy_pointer_identity():
    """The JAX buffer ITSELF must be on the wire: the IOBuf block ref's
    data pointer equals the dlpack-imported host pointer of the array —
    no staging copy anywhere (VERDICT r2 item 2)."""
    import ctypes

    import jax.numpy as jnp

    from brpc_tpu.rpc import zerocopy
    from brpc_tpu.rpc._lib import load_library

    lib = load_library()
    lib.trpc_iobuf_create.restype = ctypes.c_void_p
    x = jnp.arange(8192, dtype=jnp.uint32)
    jax_ptr = np.from_dlpack(x).ctypes.data  # the buffer JAX owns
    req = lib.trpc_iobuf_create()
    try:
        n = zerocopy.append_jax(req, x, lib)
        assert n == 8192 * 4
        assert zerocopy.live_sends() >= 1
        assert zerocopy.block_ptr(req, 0, lib) == jax_ptr
    finally:
        lib.trpc_iobuf_destroy(ctypes.c_void_p(req))
    # Destroying the IOBuf ran the deleter: the array is unpinned.
    for _ in range(200):
        if zerocopy.live_sends() == 0:
            break
        time.sleep(0.005)
    assert zerocopy.live_sends() == 0


def test_zero_copy_rpc_roundtrip(echo_server):
    """jax array → RPC echo with the staging copy gone (the wire writes
    straight from the dlpack-imported buffer)."""
    from brpc_tpu.rpc import zerocopy

    ch = Channel(f"127.0.0.1:{echo_server.port}", timeout_ms=5000)
    x = jnp.arange(1 << 18, dtype=jnp.uint32)  # 1MB payload
    resp = zerocopy.call_zero_copy(ch, "Echo.Echo", x)
    got = np.frombuffer(resp, dtype=np.uint32)
    np.testing.assert_array_equal(got, np.asarray(x))
    # The write fiber drops the last IOBuf reference a hair after the
    # response lands; the keepalive registry must drain to zero.
    for _ in range(200):
        if zerocopy.live_sends() == 0:
            break
        time.sleep(0.005)
    assert zerocopy.live_sends() == 0
    ch.close()


def test_ici_staging_zero_copy_from_python():
    """Python face of the sender-owned zero-copy path (VERDICT r4 #3):
    allocate a registered staging slab, land payload bytes in it via a
    numpy view, run the native echo over the ici rings, and assert the
    payload crossed as sender-owned descriptors (ring DMA elided) with
    the roundtrip content verified."""
    import ctypes

    import numpy as np

    from brpc_tpu.rpc import zerocopy
    from brpc_tpu.rpc._lib import load_library

    lib = load_library()
    size = 4 << 20
    view = zerocopy.alloc_staging(size)
    try:
        _staging_roundtrip(zerocopy, lib, view, size)
    finally:
        zerocopy.free_staging(view)


def _staging_roundtrip(zerocopy, lib, view, size):
    import ctypes

    import numpy as np

    assert view.size == size
    payload = np.arange(size // 4, dtype=np.uint32)
    np.copyto(view, payload.view(np.uint8))  # the "device DMA landing"

    wrs0, bytes0 = zerocopy.zero_copy_counters()
    f = lib.trpc_bench_echo_rpc
    f.restype = ctypes.c_int
    f.argtypes = [ctypes.c_void_p, ctypes.c_size_t, ctypes.c_int,
                  ctypes.c_int, ctypes.c_char_p, ctypes.c_void_p,
                  ctypes.POINTER(ctypes.c_double), ctypes.c_char_p,
                  ctypes.c_size_t, ctypes.c_char_p, ctypes.c_size_t]
    resp = np.empty(size, dtype=np.uint8)
    gbps = ctypes.c_double()
    used = ctypes.create_string_buffer(32)
    err = ctypes.create_string_buffer(256)
    rc = f(view.ctypes.data, size, 4, 1, b"ici", resp.ctypes.data,
           ctypes.byref(gbps), used, 32, err, 256)
    assert rc == 0, err.value
    assert used.value == b"ici_ring"
    assert np.array_equal(resp.view(np.uint32), payload)  # roundtrip
    wrs1, bytes1 = zerocopy.zero_copy_counters()
    assert wrs1 > wrs0
    assert bytes1 - bytes0 >= size  # the payload rode sender-owned descs
