"""Pallas ring all-gather — the explicit ICI schedule as a kernel.

The XLA `lax.all_gather` already rides ICI; this kernel is the hand-rolled
equivalent (N-1 neighbor hops with double-buffered `make_async_remote_copy`
RDMA, per the TPU kernel playbook) for when the schedule itself must be
controlled — e.g. overlapping each arriving chunk with consumer compute, the
role brpc's RDMA endpoint plays for ibverbs
(/root/reference/src/brpc/rdma/rdma_endpoint.cpp).

``interpret`` is the caller's explicit choice: ``False`` hands the kernel
to Mosaic on a multi-chip TPU mesh (and fails anywhere else), ``True`` runs
it under the pallas TPU interpreter, which is how the CPU-mesh tests and the
CPU-mesh dry run cover it. `ring_all_gather_reference` is the
XLA-collective oracle the kernel is checked against, never a substitute
for it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from brpc_tpu.parallel.fabric import Fabric


def ring_all_gather_reference(fabric: Fabric, axis: str = "link"):
    """Collective-based reference: out[j] = shard j's row, on every peer."""

    def spmd(x):
        return lax.all_gather(x, axis, tiled=True)

    return jax.jit(fabric.spmd(spmd, in_specs=P(axis), out_specs=P()))


def _ring_kernel(axis, num_devices, chunk_rows, row_len, local_ref, out_ref,
                 comm_ref, send_sem, recv_sem, cap_sem):
    from jax.experimental import pallas as pl  # noqa: PLC0415
    from jax.experimental.pallas import tpu as pltpu  # noqa: PLC0415

    # The mesh is validated 1-D by the wrapper, so the axis index IS the
    # flat LOGICAL device id the remote copies address.
    my_id = lax.axis_index(axis)
    left = lax.rem(my_id - 1 + num_devices, num_devices)
    right = lax.rem(my_id + 1, num_devices)
    barrier = pltpu.get_barrier_semaphore()

    def hop_rdma(step):
        # Hop `step` sends from slot step%2 and lands in the peer's other
        # slot; descriptors are recreated per call — start/wait pair up via
        # the shared semaphores, not object identity.
        send_slot = lax.rem(step, 2)
        recv_slot = lax.rem(step + 1, 2)
        return pltpu.make_async_remote_copy(
            src_ref=comm_ref.at[send_slot],
            dst_ref=comm_ref.at[recv_slot],
            send_sem=send_sem.at[send_slot],
            recv_sem=recv_sem.at[recv_slot],
            device_id=right,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )

    # Entry barrier: both neighbors are inside the kernel (scratch
    # allocated) before any hop-0 remote write may land.
    pltpu.semaphore_signal(barrier, inc=1, device_id=left)
    pltpu.semaphore_signal(barrier, inc=1, device_id=right)
    pltpu.semaphore_wait(barrier, 2)

    # Place the local chunk into its slot, seed the comm buffer, and put the
    # first hop's DMA in flight before any copy-out work.
    out_ref[pl.ds(my_id * chunk_rows, chunk_rows)] = local_ref[...]
    comm_ref[0] = local_ref[...]
    hop_rdma(0).start()

    def hop(step, _):
        recv_slot = lax.rem(step + 1, 2)
        parity = lax.rem(step, 2)
        src = lax.rem(my_id - step - 1 + 2 * num_devices, num_devices)
        cur = hop_rdma(step)
        cur.wait_recv()  # this hop's chunk has landed in comm[recv_slot]
        cur.wait_send()  # our send slot (parity) is drained — reusable

        # Double-buffered overlap: launch hop step+1 (forwarding the chunk
        # we just received) BEFORE copying this hop's chunk to the output,
        # so the next ICI transfer rides under the VMEM copy. Flow control
        # is point-to-point, not a counting barrier (a counting barrier
        # can't tell WHICH neighbor or WHICH round signaled, so a fast left
        # neighbor two signals ahead could unblock us while the right one
        # still holds the slot): after draining our own send of `parity` we
        # grant LEFT permission to overwrite comm[parity] next hop, and we
        # may only write into RIGHT's comm[parity] once right granted us
        # the same.
        @pl.when(step + 1 < num_devices - 1)
        def _start_next():
            pltpu.semaphore_signal(cap_sem.at[parity], inc=1, device_id=left)
            pltpu.semaphore_wait(cap_sem.at[parity], 1)
            hop_rdma(step + 1).start()

        out_ref[pl.ds(src * chunk_rows, chunk_rows)] = comm_ref[recv_slot]
        return 0

    lax.fori_loop(0, num_devices - 1, hop, 0)
    # Exit barrier: every signal we will ever receive has been consumed
    # (each grant pairs 1:1 with a wait), but neighbors may still have our
    # final DMA in flight — don't free scratch under them.
    pltpu.semaphore_signal(barrier, inc=1, device_id=left)
    pltpu.semaphore_signal(barrier, inc=1, device_id=right)
    pltpu.semaphore_wait(barrier, 2)


def ring_all_gather_pallas(fabric: Fabric, axis: str = "link",
                           interpret: bool = False):
    """Build the kernel-backed all-gather.

    ``interpret=False`` compiles with Mosaic and needs a multi-chip TPU
    mesh; ``interpret=True`` runs under the pallas TPU interpreter
    (``pltpu.InterpretParams``), which emulates the remote DMAs and
    semaphores on any backend.  Mosaic (libtpu 0.0.34, four v5e chips,
    PR 21) accepted the kernel at one float32 ``(8, 128)`` tile per
    device, the only shape it has been compiled at.  The whole gather
    lives in VMEM — ``(n + 3) * chunk`` bytes — so callers keep chunks
    small and loop over larger payloads (or use the XLA collective, as
    chip_smoke.py's 64 MB exchange does).
    """
    from jax.experimental import pallas as pl  # noqa: PLC0415
    from jax.experimental.pallas import tpu as pltpu  # noqa: PLC0415

    n = fabric.axis_size(axis)
    mesh_platform = fabric.mesh.devices.flat[0].platform
    if n < 2 or (not interpret and mesh_platform != "tpu"):
        raise RuntimeError(
            "pallas ring kernel compiles only for a multi-chip TPU mesh, got "
            f"{n} x {mesh_platform!r}; interpret=True runs it under the "
            "pallas interpreter instead")
    if len(fabric.mesh.shape) != 1:
        # The kernel addresses remote DMAs by flat LOGICAL device id, which
        # only equals the axis index on a 1-D mesh.
        raise RuntimeError("pallas ring kernel needs a 1-D mesh over the "
                           "gathered axis; build a dedicated Fabric for it")

    def spmd(x):
        chunk_rows, row_len = x.shape
        kernel = functools.partial(_ring_kernel, axis, n, chunk_rows, row_len)
        # Chunks stay in VMEM (direct loads/stores are only legal there).
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((n * chunk_rows, row_len), x.dtype),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((2, chunk_rows, row_len), x.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.REGULAR((2,)),
            ],
            compiler_params=pltpu.CompilerParams(collective_id=7),
            interpret=pltpu.InterpretParams() if interpret else False,
        )(x)

    return jax.jit(fabric.spmd(spmd, in_specs=P(axis), out_specs=P()))
