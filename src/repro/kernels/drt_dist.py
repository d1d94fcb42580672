"""Pallas TPU kernel: fused DRT distance statistics.

Computes ``[sum((x - y)^2), sum(y^2)]`` in ONE pass over a pair of layer
blocks — the inner loop of eq. (14)'s d2_p / n2_p terms.  The jnp reference
reads the operands twice (once per reduction) and materializes the
difference; the kernel streams both through VMEM once and keeps the two f32
accumulators in an SMEM scratch, emitting them on the last grid step.

Blocks are (BLOCK_R, 128) tiles of the flattened operands — 8x128 VPU
aligned; the TPU grid is sequential, so cross-step accumulation in scratch is
well-defined.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import resolve_interpret

F32 = jnp.float32

BLOCK_R = 256  # rows per grid step: 256 x 128 x 4B x 2 operands = 256 KiB VMEM
LANES = 128


def _kernel(x_ref, y_ref, out_ref, acc_ref):
    i = pl.program_id(0)
    n = pl.num_programs(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[0, 0] = 0.0
        acc_ref[0, 1] = 0.0

    x = x_ref[...].astype(F32)
    y = y_ref[...].astype(F32)
    d = x - y
    acc_ref[0, 0] += jnp.sum(d * d)
    acc_ref[0, 1] += jnp.sum(y * y)

    @pl.when(i == n - 1)
    def _emit():
        out_ref[0, 0] = acc_ref[0, 0]
        out_ref[0, 1] = acc_ref[0, 1]


@functools.partial(jax.jit, static_argnames=("interpret", "block_r"))
def drt_dist(
    x: jax.Array, y: jax.Array, *, interpret: bool | None = None, block_r: int = BLOCK_R
) -> jax.Array:
    """[sum((x-y)^2), sum(y^2)] as (2,) f32.  Any shape / float dtype.
    Interpret mode off a TPU (``repro.kernels.runtime``)."""
    assert x.shape == y.shape, (x.shape, y.shape)
    xf = x.reshape(-1)
    yf = y.reshape(-1)
    per_block = block_r * LANES
    pad = (-xf.size) % per_block
    if pad:
        xf = jnp.pad(xf, (0, pad))
        yf = jnp.pad(yf, (0, pad))
    rows = xf.size // LANES
    grid = rows // block_r
    out = pl.pallas_call(
        _kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((block_r, LANES), lambda i: (i, 0)),
            pl.BlockSpec((block_r, LANES), lambda i: (i, 0)),
        ],
        # the two scalar accumulators live in SMEM: Mosaic stores no scalar
        # to VMEM
        out_specs=pl.BlockSpec((1, 2), lambda i: (0, 0), memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((1, 2), F32),
        scratch_shapes=[pltpu.SMEM((1, 2), F32)],
        interpret=resolve_interpret(interpret),
    )(xf.reshape(rows, LANES), yf.reshape(rows, LANES))
    return out[0]
