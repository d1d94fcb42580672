"""Pallas TPU kernels: batched consensus combines over the packed slab.

PR 2's combine kernels (``weighted_combine`` / ``dequant_combine``) fuse the
accumulator into VMEM but launch once per (group, slot) segment — the Python
loop around them issues O(groups x slots) kernels per consensus round.  The
kernels here batch that loop.

``slab_combine`` (the exact path) runs once per group, on the group's region
``(n_slots, K, s_pad)`` as the round loop carries it: every slot of a region
is one DRT layer, so a tile taken inside one slot needs no per-column layer
map.  The kernel sees the region as rows ``(n_slots * K, s_pad)``, slot
after slot; a block holds the fewest whole slots Mosaic can tile
(:func:`block_rows`: one slot at K=16; four at K=2, or the whole region
where its rows do not split into eights), and the grid is
``(row blocks, column tiles)``.  The block's (K, K) mixing matrices sit in
SMEM and change only with the row block, and each tile is as wide as about
2 MiB of the block's rows (:func:`tile_cols`, from the rows and the
region's width alone).  Inside a tile, one agent's 1024 columns load as one
dense vreg (a sublane-strided load), however few the agents.  The combine
is then a plain f32 multiply-add over the K input agents on the VPU: K^2
multiply-adds a column are far below what an HBM-bound stream leaves it at
small K.

The other two still make ONE grid launch over the joined ``(K, D)`` slab
with a 128-lane block per step.  Every DRT-layer segment is padded to a
multiple of the lane width (the :class:`~repro.core.packing.SlabLayout`
invariant), so a block never straddles a layer boundary and the host gathers
the per-block mixing structure from ``layout.block_layer``:

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

Interpret mode on CPU is what the tier-1 tests pin against the jnp slab path
and the per-slot kernel references; on TPU the grid runs compiled by Mosaic.
Every block is tile-aligned for Mosaic: per-block rows of the static tables
arrive as ``(n_blocks, 1, w)`` arrays whose ``(1, w)`` block spans the
array's last two dims, and every contraction runs at ``HIGHEST`` precision
(the one-hot scale matmul must be exact, and the combine must not drop f32
parameter bits to the MXU's default bf16 pass).  Use these through the
``repro.kernels`` (ops.py) wrappers.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import resolve_interpret

F32 = jnp.float32

LANES = 128  # column-block width; SlabLayout pads every layer segment to it
HIGHEST = jax.lax.Precision.HIGHEST

# one input or output tile of slab_combine: double-buffered in and out that
# is 8 MiB, half of v5e's default scoped VMEM
TILE_BYTES = 2 << 20
CHUNK = 8 * LANES  # columns per step of the tile loop: one vreg per agent
SUBLANES = 8  # f32 rows of one (8, 128) tile


def block_rows(n_slots: int, K: int) -> int:
    """Rows of one ``slab_combine`` block of the ``(n_slots * K, s_pad)``
    view: the fewest whole slots whose rows Mosaic can tile — a multiple of
    8 rows, or every row of the region."""
    rows = math.lcm(K, SUBLANES)
    return rows if (n_slots * K) % rows == 0 else n_slots * K


def tile_cols(rows: int, s_pad: int, dtype) -> int:
    """Columns per ``slab_combine`` tile: the widest multiple of ``CHUNK``
    whose ``rows`` (:func:`block_rows`) hold at most ``TILE_BYTES``, capped
    at the region's width ``s_pad``."""
    per_col = rows * jnp.dtype(dtype).itemsize
    return min(s_pad, max(CHUNK, TILE_BYTES // per_col // CHUNK * CHUNK))


def _combine_kernel(a_ref, x_ref, o_ref, *, K):
    # out[k] = sum_l A[n, l, k] * x[l] for each slot n of the block;
    # a_ref: (1, S*K*K) SMEM, the S slots' A row-major; x_ref, o_ref:
    # (S*K, tile), slot-major rows
    rows, tc = x_ref.shape

    def combine(cols):
        for r0 in range(0, rows, K):
            a0 = r0 * K  # slot r0 // K's matrix in a_ref
            # one agent's CHUNK columns load as one dense vreg (a
            # sublane-strided load across the (rows, 128) tiles), however few
            # the agents
            xs = [x_ref[r0 + l : r0 + l + 1, cols].astype(F32) for l in range(K)]
            for k in range(K):
                acc = a_ref[0, a0 + k] * xs[0]
                for l in range(1, K):
                    acc = acc + a_ref[0, a0 + l * K + k] * xs[l]
                o_ref[r0 + k : r0 + k + 1, cols] = acc.astype(o_ref.dtype)

    def body(j, carry):
        combine(pl.ds(pl.multiple_of(j * CHUNK, CHUNK), CHUNK))
        return carry

    if tc >= CHUNK:
        jax.lax.fori_loop(0, tc // CHUNK, body, 0)
    if tc % CHUNK:  # a region narrower than one tile, cut to its own width
        combine(pl.ds(tc - tc % CHUNK, tc % CHUNK))


@functools.partial(jax.jit, static_argnames=("interpret",))
def slab_combine(A: jax.Array, region: jax.Array, *, interpret: bool | None = None):
    """Per-layer agent mixing of one group's region, in ONE launch.

    ``A``: (n_slots, K, K) — the mixing matrices of the group's layers,
    ``A[layer0 : layer0 + n_slots]``, column-stochastic over axis 1
    (``out_k = sum_l A[l, k] psi_l``).  ``region``: (n_slots, K, s_pad), the
    slot-major region of ``SlabLayout.pack_regions``.  Returns the combined
    region, same shape and dtype; it may take the input's buffer.

    The kernel sees the region as ``(n_slots * K, s_pad)`` rows: XLA lays a
    parameter leaf out into a rank-2 operand with one fused copy, where it
    lowers the same relayout into a rank-3 ``(1, K, s_pad)`` operand as a
    reshape whose generated code grows with the leaf (284 MB for one K=2
    h2o-danube-3-4b embedding), and the code of a loaded program stays in
    HBM.  The body unrolls K^2 multiply-adds over K live vregs per slot,
    sized for the K <= 16 of the benchmark's cells; K = 64 compiles for
    v5e (``tests/test_chip_compile.py``), its speed unmeasured.
    """
    n, K, s = region.shape
    if A.shape != (n, K, K) or s % LANES:
        raise ValueError(
            f"region {region.shape} needs (n_slots, K, K) mixing, got "
            f"{A.shape}, and a width that is a multiple of {LANES}"
        )
    rows = block_rows(n, K)
    per = rows // K  # slots a block holds
    tc = tile_cols(rows, s, region.dtype)
    block = pl.BlockSpec((rows, tc), lambda i, j: (i, j))
    out = pl.pallas_call(
        functools.partial(_combine_kernel, K=K),
        grid=(n // per, pl.cdiv(s, tc)),
        in_specs=[
            pl.BlockSpec(
                (None, 1, per * K * K),
                lambda i, j: (i, 0, 0),
                memory_space=pltpu.SMEM,
            ),
            block,
        ],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((n * K, s), region.dtype),
        # each tile reads only the columns it writes
        input_output_aliases={1: 0},
        interpret=resolve_interpret(interpret),
    )(A.astype(F32).reshape(n // per, 1, per * K * K), region.reshape(n * K, s))
    return out.reshape(n, K, s)


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
