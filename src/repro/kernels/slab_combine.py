"""Pallas TPU kernels: whole-slab batched consensus combines.

PR 2's combine kernels (``weighted_combine`` / ``dequant_combine``) fuse the
accumulator into VMEM but launch once per (group, slot) segment — the Python
loop around them issues O(groups x slots) kernels per consensus round.  These
kernels make the combine ONE grid-based launch over the packed ``(K, D)``
slab per round.

The trick is the :class:`~repro.core.packing.SlabLayout` invariant that every
DRT-layer segment is padded to a multiple of the lane width (128): a 128-wide
column block never straddles a layer boundary, so the host gathers the
per-block mixing structure from ``layout.block_layer`` (a static numpy map)
and the grid streams (mixing block, slab block) pairs through the MXU:

  ``slab_combine``          out[k, c] = sum_l A[layer(c), l, k] * slab[l, c]
                            — the gather engine's per-layer agent mixing as
                            one (K, K) x (K, 128) matmul per block.
  ``slab_dequant_combine``  the fused int8 dequantize-and-combine: per-column
                            scales are reconstructed IN the kernel from the
                            static column->scale-segment map via a one-hot
                            matmul (dynamic gathers don't vectorize on TPU),
                            so the dequantized f32 neighbours never hit HBM.
  ``slab_source_combine``   out[c] = sum_n w[n, layer(c)] * srcs[n][c]
                            — the permute engine's neighbour combine over the
                            (1 + n_nbrs) source slabs.

Padding lanes need no masking: pack keeps them zero, every combine here is
linear in the slab values, and the int8 wire quantizes exact zeros to q = 0
(the uniform draw is 0 on padding columns), so zeros stay zero through any
of these kernels and later rounds' segment reductions remain exact.

Interpret mode on CPU is bit-compatible with the jnp slab path and is what
the tier-1 tests pin; on TPU the grid runs compiled by Mosaic.  Every block
is tile-aligned for Mosaic: per-block rows of the static tables arrive as
``(n_blocks, 1, w)`` arrays whose ``(1, w)`` block spans the array's last two
dims, and every contraction runs at ``HIGHEST`` precision (the one-hot scale
matmul must be exact, and the combine must not drop f32 parameter bits to
the MXU's default bf16 pass).  Use these through the ``repro.kernels``
(ops.py) wrappers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.runtime import resolve_interpret

F32 = jnp.float32

LANES = 128  # column-block width; SlabLayout pads every layer segment to it
HIGHEST = jax.lax.Precision.HIGHEST


def _combine_kernel(a_ref, x_ref, o_ref):
    # out[k, c] = sum_l a[l, k] * x[l, c] for this block's single DRT layer
    o_ref[...] = jax.lax.dot_general(
        a_ref[0], x_ref[...].astype(F32),
        (((0,), (0,)), ((), ())),
        precision=HIGHEST,
        preferred_element_type=F32,
    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def slab_combine(A_blocks: jax.Array, slab: jax.Array, *, interpret: bool | None = None):
    """Whole-slab per-layer agent mixing in ONE launch.

    ``A_blocks``: (n_blocks, K, K) f32 — the mixing matrix of each column
    block's layer, i.e. ``A[layout.block_layer]``; column-stochastic over
    axis 1 (``out_k = sum_l A[l, k] psi_l``).  ``slab``: (K, n_blocks*128)
    packed slab.  Returns (K, D) in the slab dtype.
    """
    K, D = slab.shape
    nb = A_blocks.shape[0]
    if nb * LANES != D:
        raise ValueError(f"slab width {D} != {nb} blocks x {LANES} lanes")
    return pl.pallas_call(
        _combine_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, K, K), lambda i: (i, 0, 0)),
            pl.BlockSpec((K, LANES), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((K, LANES), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((K, D), slab.dtype),
        interpret=resolve_interpret(interpret),
    )(A_blocks.astype(F32), slab)


def _dequant_combine_kernel(a_ref, s_ref, seg_ref, q_ref, o_ref):
    n_segs = s_ref.shape[1]
    # per-column scale via one-hot matmul over the static segment ids —
    # the MXU-friendly spelling of s[:, seg[c]]
    onehot = (
        seg_ref[...] == jax.lax.broadcasted_iota(jnp.int32, (n_segs, LANES), 0)
    ).astype(F32)
    s_cols = jnp.dot(
        s_ref[...], onehot, precision=HIGHEST, preferred_element_type=F32
    )  # (K, 128)
    deq = s_cols * q_ref[...].astype(F32)
    o_ref[...] = jax.lax.dot_general(
        a_ref[0], deq, (((0,), (0,)), ((), ())),
        precision=HIGHEST, preferred_element_type=F32,
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def slab_dequant_combine(
    A_blocks: jax.Array,
    scales: jax.Array,
    col_seg: jax.Array,
    q_slab: jax.Array,
    *,
    interpret: bool | None = None,
):
    """Fused int8 dequantize + whole-slab combine in ONE launch.

    ``out[k, c] = sum_l A_blocks[c//128, l, k] * scales[l, seg(c)] * q[l, c]``

    ``scales``: (K, n_scale_segs) f32 per-agent segment scales;
    ``col_seg``: (n_blocks, 128) int32 — ``layout.col_scale_seg`` reshaped;
    ``q_slab``: (K, n_blocks*128) int8.  Returns f32 (K, D); the decoded f32
    neighbour slab never materializes in HBM.
    """
    K, D = q_slab.shape
    nb = A_blocks.shape[0]
    if nb * LANES != D:
        raise ValueError(f"slab width {D} != {nb} blocks x {LANES} lanes")
    n_segs = scales.shape[-1]
    return pl.pallas_call(
        _dequant_combine_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, K, K), lambda i: (i, 0, 0)),
            pl.BlockSpec((K, n_segs), lambda i: (0, 0)),
            pl.BlockSpec((None, 1, LANES), lambda i: (i, 0, 0)),
            pl.BlockSpec((K, LANES), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((K, LANES), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((K, D), F32),
        interpret=resolve_interpret(interpret),
    )(
        A_blocks.astype(F32),
        scales.astype(F32),
        col_seg.astype(jnp.int32).reshape(nb, 1, LANES),
        q_slab,
    )


def _source_combine_kernel(w_ref, *refs):
    # out[c] = sum_n w[n] * x_n[c]; w row = this block's layer weights
    *x_refs, o_ref = refs
    w = w_ref[...]  # (1, N)
    out = None
    for n, x_ref in enumerate(x_refs):
        term = w[:, n : n + 1] * x_ref[...].astype(F32)
        out = term if out is None else out + term
    o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def slab_source_combine(
    w_blocks: jax.Array, srcs, *, interpret: bool | None = None
):
    """Per-layer weighted combine over N source slabs in ONE launch (the
    permute engine's {self} + received-neighbour combine).

    ``w_blocks``: (n_blocks, N) f32 — per column block, the weight of each
    source for that block's layer (``w_all[:, layout.block_layer].T``);
    ``srcs``: a sequence of N (n_blocks*128,) slabs, read in place (stacking
    them would copy N slabs, 6 GB at one h2o-danube-3-4b layer's width).
    Returns (D,) in the first source's dtype.
    """
    N = len(srcs)
    (D,) = srcs[0].shape
    nb = w_blocks.shape[0]
    if nb * LANES != D or w_blocks.shape[1] != N:
        raise ValueError(
            f"{N} slabs of width {D} vs weights {w_blocks.shape} x {LANES} lanes"
        )
    out = pl.pallas_call(
        _source_combine_kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((None, 1, N), lambda i: (i, 0, 0))]
        + [pl.BlockSpec((1, LANES), lambda i: (0, i))] * N,
        out_specs=pl.BlockSpec((1, LANES), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, D), srcs[0].dtype),
        interpret=resolve_interpret(interpret),
    )(w_blocks.astype(F32).reshape(nb, 1, N), *[x.reshape(1, D) for x in srcs])
    return out.reshape(D)
