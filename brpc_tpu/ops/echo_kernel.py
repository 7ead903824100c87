"""Fused echo kernel — the data-plane hot op as a single HBM pass.

The echo server's work per payload is "receive, verify, materialize the
response": as plain jnp this is a roll (copy) plus a reduction — two HBM
passes unless XLA fuses them.  The Pallas kernel guarantees the fusion: one
grid over the payload, each block copied through VMEM exactly once while the
checksum accumulates in SMEM.

There is no off-TPU fallback: ``interpret`` is the caller's explicit choice
(the CPU tests pass ``interpret=True``); with ``interpret=False`` Mosaic
compiles the kernel or the call fails.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from brpc_tpu.ops.checksum import sum32

_ROWS = 16       # sublane-aligned block rows (uint32 min tile is 8x128)
_COLS = 8192     # lanes per row
_BLOCK = _ROWS * _COLS  # uint32 lanes per grid step (512KB)


def _kernel(x_ref, out_ref, acc_ref):
    from jax.experimental import pallas as pl  # noqa: PLC0415

    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[0, 0] = jnp.int32(0)

    block = x_ref[...]
    out_ref[...] = block
    # TPU lowers signed reductions only; int32 wrap == uint32 wrap.
    acc_ref[0, 0] += jnp.sum(block.astype(jnp.int32), dtype=jnp.int32)


def echo_fused(payload: jnp.ndarray, interpret: bool = False,
               rows: int = _ROWS, cols: int = _COLS):
    """payload: uint32[n] with n % (rows*cols) == 0.  Returns
    (copy, checksum).  rows/cols pick the per-grid-step tile."""
    from jax.experimental import pallas as pl  # noqa: PLC0415
    from jax.experimental.pallas import tpu as pltpu  # noqa: PLC0415

    n = payload.shape[0]
    block = rows * cols
    assert n % block == 0, f"payload lanes {n} not a multiple of {block}"
    x2d = payload.reshape(n // cols, cols)
    grid = (n // block,)
    copy, acc = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((rows, cols), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((rows, cols), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n // cols, cols), jnp.uint32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        interpret=interpret,
    )(x2d)
    return copy.reshape(n), acc[0, 0].astype(jnp.uint32)


def echo_reference(payload: jnp.ndarray):
    """The jnp composition the kernel fuses — used by the equivalence tests.

    NOT a performance fallback: XLA folds the +0 copy away, so off-TPU
    benchmarking uses models.echo.single_chip_echo_step (roll forces the
    copy); cross-backend goodput numbers are therefore not comparable.
    """
    return payload + jnp.uint32(0), sum32(payload)
