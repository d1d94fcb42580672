"""Pallas TPU kernels: fused encode -> combine for coded consensus rounds.

PR 4 batched the *decode+combine* side of a coded round into one whole-slab
launch (``repro.kernels.slab_combine``), but the encode side still ran as
jnp slab passes: per-(leaf, slot) scale reductions, a K x D uniform field,
the f32 ``x/s + u`` quantization temporaries, and a separately materialized
dequantized neighbour slab — ~5 full-slab HBM passes per coded round on top
of the combine.  The kernels here collapse a coded round's slab work into
ONE ``pallas_call``:

  ``slab_encode_combine``  the whole coded round for the gather engine, one
                           launch: stream the packed (K, D) slab through a
                           (phase, block) grid —

                           * phase 0 re-derives each lane block's WIRE view
                             (int8: in-kernel counter RNG from the static
                             ``col_leaf``/``col_idx`` maps + per-column scale
                             reconstruction from ``col_scale_seg``; bf16/f16:
                             the cast round-trip) and accumulates the
                             per-DRT-layer Gram matrices (raw and less agent
                             0's) into a VMEM scratch
                             — the decoded (and the f32 wire) slab never
                             exist in HBM;
                           * the first phase-1 step runs the FULL DRT
                             mixing-matrix pipeline (eqs. 12-14, the same
                             ``repro.core.drt`` code traced in-kernel) on the
                             accumulated (L, K, K) Gram scratch;
                           * phase 1 recomputes each block's wire view
                             (VPU-cheap, HBM-free) and writes the combined
                             output ``A_off^T . dec + diag . x`` — the
                             full-precision self term rides in the same
                             launch.

                           HBM traffic per coded round: 2 reads + 1 write of
                           the f32 slab (1 read + 1 write for classical,
                           which needs no Gram phase) vs ~5 full-slab passes
                           + a K x D uniform field on the unfused path.

  ``slab_quant_encode``    the standalone int8 encode (in-kernel RNG + scale
                           reconstruction + stochastic round), one launch ->
                           int8 wire slab.  The permute engine's per-shard
                           encode, and the bit-parity probe for the fused
                           kernel's wire view.

  ``slab_cast_combine``    bf16/f16 convenience wrapper over
                           ``slab_encode_combine`` (mode='bf16'/'f16').

Bit-parity contract: the wire view a kernel derives for a block is computed
with the SAME uint32 hash (``repro.comm.rng``), the same scale values (the
one-hot segment matmul is exact: one unit product per column) and the same
floor/clip arithmetic as the jnp slab path, so ``slab_quant_encode`` equals
``packing.slab_encode_batched`` bit-for-bit and the fused round matches the
two-phase jnp round to float-accumulation order (asserted in
``tests/test_kernels.py``).

The uniforms are "threaded" as per-(agent, leaf) key WORDS (two uint32 each,
from the same ``split(agent_key, n_tree_leaves)`` the tree codec performs)
plus two static per-column maps — the K x D uniform field itself is never
materialized anywhere.

Scale granularity note: the per-(leaf, slot) absmax reduction stays a jnp
segment reduction (one streaming pass XLA fuses; the output is a
(K, n_scale_segs) vector that lives in VMEM for the whole launch).
Everything per-COLUMN — scale broadcast, RNG, quantize, dequantize, combine
— happens in-kernel.

Interpret mode on CPU is what the tier-1 tests pin (as for every kernel in
this package); on TPU the grid runs compiled by Mosaic.  Tile alignment:
``block_layer`` sits whole in SMEM and each step reads its own entry; the
per-column tables arrive as ``(n_blocks, 1, lane)`` arrays so a block's
``(1, lane)`` row spans the array's last two dims.  Use through the
``repro.kernels`` (ops.py) wrappers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.comm.rng import bits_to_uniform, counter_bits
from repro.core import drt as drt_mod
from repro.kernels.runtime import resolve_interpret
from repro.utils.pytree import sq_dists_from_grams

F32 = jnp.float32

LANES = 128  # column-block width; SlabLayout pads every layer segment to it
QMAX = 127.0
HIGHEST = jax.lax.Precision.HIGHEST

_CAST = {"bf16": jnp.bfloat16, "f16": jnp.float16}


def _gather_key_words(w_ref, leaf_cols):
    """(K, LANES) uint32 key words for this block's columns (``leaf_cols``:
    (1, LANES)): select each column's owning-leaf word from the
    (K, n_leaves) table.  A static select chain over the (small) leaf count
    — uint32 has no MXU path and dynamic gathers don't vectorize on TPU."""
    words = w_ref[...]  # (K, n_leaves) uint32
    n_leaves = words.shape[1]
    out = jnp.broadcast_to(words[:, 0:1], (words.shape[0], leaf_cols.shape[-1]))
    for l in range(1, n_leaves):
        out = jnp.where(leaf_cols == l, words[:, l : l + 1], out)
    return out


def _scale_cols(s_ref, seg_cols):
    """(K, LANES) per-column scales via the one-hot segment matmul over the
    (1, LANES) ``seg_cols`` (exact at HIGHEST precision: one unit product
    per column; MXU-friendly)."""
    n_segs = s_ref.shape[1]
    onehot = (
        seg_cols
        == jax.lax.broadcasted_iota(jnp.int32, (n_segs, seg_cols.shape[-1]), 0)
    ).astype(F32)
    return jnp.dot(
        s_ref[...].astype(F32), onehot,
        precision=HIGHEST, preferred_element_type=F32,
    )


def _quant_block(x, s_cols, u):
    """Stochastic-rounding int8 values, kept in f32 (int8 round-trips f32
    exactly, so the fused dequant path saves the down/up cast pair)."""
    return jnp.clip(jnp.floor(x / s_cols + u), -QMAX, QMAX)


def _int8_wire_block(x, quant_refs):
    """(quantized values f32, per-column scales) of this block — the
    receiver's decoded view is their product."""
    s_ref, seg_ref, leaf_ref, idx_ref, w0_ref, w1_ref = quant_refs
    leaf_cols = leaf_ref[...]
    k0 = _gather_key_words(w0_ref, leaf_cols)
    k1 = _gather_key_words(w1_ref, leaf_cols)
    u = bits_to_uniform(counter_bits(k0, k1, idx_ref[...]))
    s_cols = _scale_cols(s_ref, seg_ref[...])
    return _quant_block(x, s_cols, u), s_cols


def _combine_block(A, dec, x):
    """out[k, c] = sum_{l != k} A[l, k] dec[l, c] + A[k, k] x[k, c] — the
    off-diagonal decoded combine plus the full-precision self term."""
    K = A.shape[0]
    eye = jnp.eye(K, dtype=F32)
    off = jax.lax.dot_general(
        A * (1.0 - eye), dec, (((0,), (0,)), ((), ())),
        precision=HIGHEST, preferred_element_type=F32,
    )
    diag = jnp.sum(A * eye, axis=1, keepdims=True)  # (K, 1) without a gather
    return off + diag * x


def _encode_combine_kernel(mode, algorithm, kappa, N_clip, weight_mode, *refs):
    if algorithm == "drt":
        *head, mix_ref, out_ref, A_ref, G_scr = refs
    else:
        *head, mix_ref, out_ref = refs
        A_ref = G_scr = None
    bl_ref, slab_ref, *wire_refs = head

    x = slab_ref[...].astype(F32)
    if mode == "sent":
        dec = wire_refs[0][...].astype(F32)  # precomputed f32 wire (top-k)
    elif mode in _CAST:
        dec = x.astype(_CAST[mode]).astype(F32)
    elif mode == "int8":
        q, s_cols = _int8_wire_block(x, wire_refs)
        dec = q * s_cols
    else:
        raise ValueError(f"unknown wire mode {mode!r}")

    if algorithm == "classical":
        # the mixing matrix is the (layer-independent) Metropolis input;
        # single phase: 1 slab read + 1 write per round, nothing else
        out_ref[...] = _combine_block(mix_ref[...].astype(F32), dec, x)
        return

    ph = pl.program_id(0)
    i = pl.program_id(1)
    p = bl_ref[i]  # this block's DRT layer

    @pl.when(ph == 0)
    def _gram_phase():
        @pl.when(i == 0)
        def _init():
            G_scr[...] = jnp.zeros_like(G_scr)

        # partial Grams of this block's layer, raw and less agent 0's (the
        # two SlabLayout.pairwise_sq_dists reads)
        for j, x in enumerate((dec, dec - dec[0:1])):
            Gp = jax.lax.dot_general(
                x, x, (((1,), (1,)), ((), ())),
                precision=HIGHEST, preferred_element_type=F32,
            )  # (K, K)
            G_scr[j, pl.ds(p, 1)] = G_scr[j, pl.ds(p, 1)] + Gp[None]

    @pl.when(jnp.logical_and(ph == 1, i == 0))
    def _mixing():
        # the FULL DRT pipeline (eqs. 12-14) on the accumulated Gram scratch
        # — the same repro.core.drt code the jnp path runs, traced in-kernel.
        # A lands in the (whole-array, VMEM-resident) second OUTPUT, which
        # phase-1 blocks read back — the engine returns it as A_last
        d2, n2 = sq_dists_from_grams(G_scr[0], G_scr[1])  # (L, K, K), (L, K)
        C = mix_ref[...].astype(F32)
        log_a = drt_mod.drt_log_unnormalized(d2, n2, C, kappa, weight_mode)
        A_ref[...] = drt_mod.drt_normalize(
            drt_mod.drt_clip_and_self(log_a, C, N_clip), C
        )

    @pl.when(ph == 1)
    def _combine_phase():
        A = A_ref[pl.ds(p, 1)][0]  # (K, K) this layer's mixing matrix
        out_ref[...] = _combine_block(A, dec, x)


@functools.partial(
    jax.jit,
    static_argnames=(
        "mode", "algorithm", "num_layers", "kappa", "N_clip", "weight_mode",
        "lane", "interpret",
    ),
)
def slab_encode_combine(
    block_layer: jax.Array,
    slab: jax.Array,
    wire_operands: tuple,
    mix: jax.Array,
    *,
    mode: str,
    algorithm: str = "drt",
    num_layers: int,
    kappa: float = 1e-6,
    N_clip: float = 32.0,
    weight_mode: str = "paper",
    lane: int = LANES,
    interpret: bool | None = None,
):
    """ONE coded consensus round's slab work in ONE launch (see module doc).

    ``block_layer``: (n_blocks,) int32 — ``SlabLayout.block_layer``.
    ``slab``: (K, D) f32 packed current iterates (also the self term).
    ``wire_operands``: mode-dependent —
      * ``mode='int8'``: ``(scales (K, n_segs) f32, col_seg (nb, 128) i32,
        col_leaf (nb, 128) i32, col_idx (nb, 128) u32, w0 (K, n_leaves) u32,
        w1 (K, n_leaves) u32)``;
      * ``mode='bf16' | 'f16'``: ``()`` — the cast round-trip is derived from
        ``slab`` in-kernel;
      * ``mode='sent'``: ``(sent_slab (K, D) f32,)`` — a precomputed f32 wire
        (top-k sent values).
    ``mix``: the graph input — ``C`` (K, K) for ``algorithm='drt'`` (feeds the
    in-kernel eq. 12-14 pipeline; pass ``kappa``/``N_clip``/``weight_mode``
    from the resolved ``DRTConfig``), the Metropolis matrix for
    ``'classical'``.

    Returns ``(combined, A)``: the combined (K, D) f32 slab
    ``out_k = sum_{l != k} A[layer, l, k] dec_l + A[layer, k, k] x_k`` and
    the round's (L, K, K) mixing matrices (a second kernel output for
    ``'drt'``; the broadcast Metropolis matrix for ``'classical'``).
    """
    K, D = slab.shape
    nb = block_layer.shape[0]
    if nb * lane != D:
        raise ValueError(f"slab width {D} != {nb} blocks x {lane} lanes")
    drt = algorithm == "drt"
    if not drt and algorithm != "classical":
        raise ValueError(f"unknown algorithm {algorithm!r}")
    # classical runs a single phase (no Gram accumulation); ph is then always
    # 0 and every index map below ignores it
    grid = (2, nb) if drt else (1, nb)

    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),  # whole block->layer map
        pl.BlockSpec((K, lane), lambda ph, i: (0, i)),
    ]
    operands = [jnp.asarray(block_layer, jnp.int32), slab.astype(F32)]
    if mode == "int8":
        scales, col_seg, col_leaf, col_idx, w0, w1 = wire_operands
        n_segs = scales.shape[-1]
        n_leaves = w0.shape[-1]
        in_specs += [
            pl.BlockSpec((K, n_segs), lambda ph, i: (0, 0)),
            *[pl.BlockSpec((None, 1, lane), lambda ph, i: (i, 0, 0))] * 3,
            pl.BlockSpec((K, n_leaves), lambda ph, i: (0, 0)),
            pl.BlockSpec((K, n_leaves), lambda ph, i: (0, 0)),
        ]
        operands += [
            scales.astype(F32),
            *_block_rows(col_seg, col_leaf, col_idx),
            w0.astype(jnp.uint32),
            w1.astype(jnp.uint32),
        ]
    elif mode == "sent":
        (sent,) = wire_operands
        in_specs += [pl.BlockSpec((K, lane), lambda ph, i: (0, i))]
        operands += [sent.astype(F32)]
    elif mode in _CAST:
        if wire_operands:
            raise ValueError(f"mode {mode!r} takes no wire operands")
    else:
        raise ValueError(f"unknown wire mode {mode!r}")
    in_specs += [pl.BlockSpec(mix.shape, lambda ph, i: (0, 0))]
    operands += [mix.astype(F32)]

    kernel = functools.partial(
        _encode_combine_kernel, mode, algorithm, float(kappa), float(N_clip),
        weight_mode,
    )
    if drt:
        # slab output: phase 0 parks the window on block 0 without writing;
        # its only flush happens after (1, 0) writes it — each output
        # block's visits stay one contiguous run of grid steps.  The A
        # output's window is the whole array for every step, so it stays
        # VMEM-resident for the phase-1 per-block reads.
        out, A = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=in_specs,
            out_specs=(
                pl.BlockSpec((K, lane), lambda ph, i: (0, ph * i)),
                pl.BlockSpec((num_layers, K, K), lambda ph, i: (0, 0, 0)),
            ),
            out_shape=(
                jax.ShapeDtypeStruct((K, D), F32),
                jax.ShapeDtypeStruct((num_layers, K, K), F32),
            ),
            # raw and centred Gram accumulators
            scratch_shapes=[pltpu.VMEM((2, num_layers, K, K), F32)],
            interpret=resolve_interpret(interpret),
        )(*operands)
        return out, A
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((K, lane), lambda ph, i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((K, D), F32),
        interpret=resolve_interpret(interpret),
    )(*operands)
    return out, jnp.broadcast_to(mix.astype(F32), (num_layers, K, K))


def _block_rows(col_seg, col_leaf, col_idx):
    """The (n_blocks, lane) column tables as (n_blocks, 1, lane) arrays, so
    each grid step's (1, lane) block spans the last two dims (Mosaic tiling)."""
    nb, lane = col_seg.shape
    return (
        col_seg.astype(jnp.int32).reshape(nb, 1, lane),
        col_leaf.astype(jnp.int32).reshape(nb, 1, lane),
        col_idx.astype(jnp.uint32).reshape(nb, 1, lane),
    )


def slab_cast_combine(block_layer, slab, mix, *, dtype="bf16", **kw):
    """bf16/f16 cast-combine: one launch per coded round; the cast wire slab
    never exists in HBM (encode, decode, stats, combine and the self term all
    derive from the f32 slab in VMEM)."""
    return slab_encode_combine(block_layer, slab, (), mix, mode=dtype, **kw)


# ---------------------------------------------------------------------------
# standalone encode (permute engine / parity probe)
# ---------------------------------------------------------------------------


def _quant_encode_kernel(
    slab_ref, s_ref, seg_ref, leaf_ref, idx_ref, w0_ref, w1_ref, q_ref
):
    quant_refs = (s_ref, seg_ref, leaf_ref, idx_ref, w0_ref, w1_ref)
    q, _ = _int8_wire_block(slab_ref[...].astype(F32), quant_refs)
    q_ref[...] = q.astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("interpret",))
def slab_quant_encode(
    scales: jax.Array,
    col_seg: jax.Array,
    col_leaf: jax.Array,
    col_idx: jax.Array,
    w0: jax.Array,
    w1: jax.Array,
    slab: jax.Array,
    *,
    interpret: bool | None = None,
):
    """Fused int8 stochastic-rounding encode of a packed (K, D) slab in ONE
    launch: per-column scale reconstruction AND the counter-RNG uniforms are
    computed in-kernel from static maps, so the only HBM traffic is the f32
    read and the int8 write — no K x D uniform field, no f32 temporaries.

    ``scales``: (K, n_scale_segs) f32 (``packing.slab_quant_scales``);
    ``col_seg``/``col_leaf``: (nb, 128) int32; ``col_idx``: (nb, 128) uint32
    (``SlabLayout.col_scale_seg`` / ``col_leaf`` / ``col_idx`` reshaped);
    ``w0``/``w1``: (K, n_tree_leaves) uint32 (``packing.leaf_key_words``).
    Returns the (K, D) int8 wire, bit-identical to the jnp slab encode.
    """
    K, D = slab.shape
    nb, lane = col_seg.shape  # lane = layout.lane (static)
    if nb * lane != D:
        raise ValueError(f"slab width {D} != {nb} blocks x {lane} lanes")
    n_segs = scales.shape[-1]
    n_leaves = w0.shape[-1]
    return pl.pallas_call(
        _quant_encode_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((K, lane), lambda i: (0, i)),
            pl.BlockSpec((K, n_segs), lambda i: (0, 0)),
            *[pl.BlockSpec((None, 1, lane), lambda i: (i, 0, 0))] * 3,
            pl.BlockSpec((K, n_leaves), lambda i: (0, 0)),
            pl.BlockSpec((K, n_leaves), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((K, lane), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((K, D), jnp.int8),
        interpret=resolve_interpret(interpret),
    )(
        slab.astype(F32),
        scales.astype(F32),
        *_block_rows(col_seg, col_leaf, col_idx),
        w0.astype(jnp.uint32),
        w1.astype(jnp.uint32),
    )
