"""Pallas TPU kernel: fused edge-list (sparse) consensus round on the slab.

The dense round kernels (``slab_combine``, ``slab_encode_combine``) pay
O(K^2) per lane block — an all-pairs Gram accumulation plus a (K, K) mixing
matmul — regardless of how sparse the realized graph is.  On the sparse
topologies the paper cares about (ring, ER, gossip draws) the realized edge
count |E| is O(K), so the dense kernels waste a factor of K.

``slab_edge_combine`` runs ONE launch per consensus round over the packed
(K, D) slab with a padded DIRECTED edge list (``src``/``dst``/``w``,
``w == 0`` marking padding — see :class:`repro.core.dynamic.EdgeStacks`):

  * phase 0 streams the decoded slab once, accumulating the per-DRT-layer
    squared norms ``n2 (L, K)`` and per-EDGE squared distances ``d2e (L, E)``
    into VMEM scratch — O(|E| x lane) work per block instead of the dense
    Gram's O(K^2 x lane);
  * the first phase-1 step runs the SAME edge-factorized eq. 12-14 pipeline
    as the jnp path (:func:`repro.core.drt.drt_edge_mixing`, traced
    in-kernel) on the scratch stats and writes the column-stochastic factors
    ``A_self (L, K)`` / ``A_e (L, E)`` to whole-array VMEM-resident outputs;
  * phase 1 writes each block's combined output
    ``out = A_self[p] * x_self + scatter_dst(A_e[p] * gather_src(x_dec))``
    — the full-precision self term and the decoded neighbour contributions
    in one pass, O(|E| x lane) per block.

``algorithm='classical'`` needs no stats phase: a single-phase grid computes
the Metropolis edge factorization in-kernel at the first block (the same
:func:`repro.core.dynamic.metropolis_edge_weights` code) and combines.

The caller passes the DECODED slab separately from the self slab, so one
kernel serves exact rounds (``dec is self``) and coded rounds (jnp
encode/decode feeds the kernel; the round's slab-side stats + mixing +
combine still collapse into this one launch).

Mosaic lowers no row gather or scatter, so every per-edge gather
(``x[src]``) and per-destination reduction (``.at[dst].add``) in these
kernels is a one-hot matmul or a masked reduction over (K, E) incidence
masks: O(K E) per block, cheap at the small in-kernel K.  Compiled, the
edge-factor algebra runs in that masked spelling
(:class:`repro.core.drt.EdgeSegments` ``dense=True``); interpreted, it runs
the jnp path's own gathers and scatters, which keeps interpret mode bit for
bit on the jnp edge path.  This decoded-slab kernel scatters by a
(K, E) x (E, lane) matmul.

``slab_edge_encode_combine`` is the sort-free rewrite over the
dst-contiguous edge order, plus wire residency: instead
of taking a decoded (K, D) f32 slab that a jnp decode pass materialized in
HBM (~2 extra full-slab passes per coded round — one write, re-read by both
phases), it takes the COMPACT WIRE itself (int8 quantized values + scales,
the bf16/f16 cast slab, or the top-k sent slab) and re-derives each lane
block's decoded view inside the kernel in both phases
(recompute-over-rematerialize, the ``slab_codec`` decode machinery: exact
one-hot scale reconstruction from ``SlabLayout.col_scale_seg`` for int8, the
cast round-trip for bf16/f16).  The ``.at[dst].add`` scatter is replaced by
a per-destination segment combine over the ``csr_from_edges`` tables (the
(dst, src)-sorted edge order makes each destination's edges contiguous, so
the combine is Dmax gather-accumulate steps — no scatter, no sort):

    out[k] = A_self[p, base + k] * x_self[k]
           + sum_j valid[k, j] * A_e[p, pos[k, j]] * dec[nbr[k, j]]

HBM traffic per coded round (f32-slab units S = K x D x 4B; wire fraction
rho = wire bytes / 4): self read (phase-parked, 1 S) + wire read x2 phases
(2 rho S) + combined write (1 S) — int8 2.5 S vs the dense fused kernel's
3 S and the decoded-slab edge round's ~6 S (priced by
``repro.kernels.traffic``, gated in ``benchmarks/check_regression.py``).

The ``dst_base`` scalar + (K_local, Dmax) CSR tables make the kernel
destination-shardable: under ``shard_map`` each data-mesh shard passes its
destination-contiguous slab rows and CSR shard with the full wire + edge
list (stats and the eq. 12-14 factors are global; the combine is local) —
see ``repro.launch.sharding.edge_round_shard_specs``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import drt as drt_mod
from repro.core.dynamic import metropolis_edge_weights
from repro.kernels.runtime import resolve_interpret
from repro.kernels.slab_codec import _CAST, _scale_cols

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST

LANES = 128


def _onehot(idx, n):
    """(n, E) f32 one-hot of a (1, E) index row: ``[k, e] = (idx[e] == k)``."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (n, idx.shape[-1]), 0)
    return (iota == idx).astype(F32)


def _take_rows(x, idx):
    """``x[idx]`` for a (1, E) index row: (K, lane) -> (E, lane) as a one-hot
    matmul (exact at HIGHEST precision: one unit product per row).  Mosaic
    lowers no row gather."""
    return jax.lax.dot_general(
        _onehot(idx, x.shape[0]), x, (((0,), (0,)), ((), ())),
        precision=HIGHEST, preferred_element_type=F32,
    )


def _row_sq_sums(x):
    """(N, lane) -> (1, N): each row's sum of squares, as a lane row."""
    return jnp.sum(jnp.square(x), axis=1)[None]


def _pick_cols(row, idx):
    """``row[0, idx]`` as a column: (1, N) row, (M, 1) index column ->
    (M, 1), by a masked lane reduction (exact: one nonzero per row)."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (idx.shape[0], row.shape[-1]), 1)
    return jnp.sum(jnp.where(iota == idx, row, 0.0), axis=1, keepdims=True)


def _edge_combine_block(x_self, x_dec, src, dst, a_self, a_e):
    """out[k, c] = a_self[k] x_self[k, c] + sum_{e: dst[e]==k} a_e[e] x_dec[src[e], c].

    ``src``/``dst`` are (1, E) rows and ``a_self`` (1, K) / ``a_e`` (1, E)
    rows.  Padding edges arrive with ``a_e == 0`` (the weight functions mask
    on ``w > 0``), so their contribution is an exact zero.  The scatter by
    destination is a one-hot matmul."""
    K = x_self.shape[0]
    ids = jax.lax.broadcasted_iota(jnp.int32, (K, 1), 0)
    out = x_self * _pick_cols(a_self, ids)
    weighted = _onehot(dst, K) * a_e  # (K, E): edge weight at its dst row
    return out + jax.lax.dot_general(
        weighted, _take_rows(x_dec, src), (((1,), (0,)), ((), ())),
        precision=HIGHEST, preferred_element_type=F32,
    )


def _edge_kernel(algorithm, kappa, N_clip, weight_mode, num_layers, dense, *refs):
    (bl_ref, self_ref, dec_ref, src_ref, dst_ref, w_ref,
     out_ref, As_ref, Ae_ref, *scratch) = refs
    src = src_ref[...]  # (1, E) rows
    dst = dst_ref[...]
    w = w_ref[...]
    K = self_ref.shape[0]
    p = bl_ref[pl.program_id(1)]  # this block's DRT layer

    if algorithm == "classical":
        # single phase: weights are a pure function of the edge list — derive
        # them once at block 0 (the same jnp code as the unkerneled path, in
        # its masked-reduction spelling), combine every block
        @pl.when(pl.program_id(1) == 0)
        def _weights():
            m_self, m_e = metropolis_edge_weights(
                src[0], dst[0], w[0], K, dense=dense
            )
            As_ref[...] = jnp.broadcast_to(m_self, As_ref.shape)
            Ae_ref[...] = jnp.broadcast_to(m_e, Ae_ref.shape)

        out_ref[...] = _edge_combine_block(
            self_ref[...].astype(F32), dec_ref[...].astype(F32),
            src, dst, As_ref[pl.ds(p, 1)], Ae_ref[pl.ds(p, 1)],
        )
        return

    n2_scr, d2e_scr = scratch
    ph = pl.program_id(0)
    i = pl.program_id(1)
    x_dec = dec_ref[...].astype(F32)

    @pl.when(ph == 0)
    def _stats_phase():
        @pl.when(i == 0)
        def _init():
            n2_scr[...] = jnp.zeros_like(n2_scr)
            d2e_scr[...] = jnp.zeros_like(d2e_scr)

        n2_scr[pl.ds(p, 1)] = n2_scr[pl.ds(p, 1)] + _row_sq_sums(x_dec)
        diff = _take_rows(x_dec, src) - _take_rows(x_dec, dst)
        d2e_scr[pl.ds(p, 1)] = d2e_scr[pl.ds(p, 1)] + _row_sq_sums(diff)

    @pl.when(jnp.logical_and(ph == 1, i == 0))
    def _mixing():
        # the SAME edge-factorized eq. 12-14 pipeline as the jnp path (in
        # its masked-reduction spelling), traced in-kernel on the
        # accumulated stats; the factors land in the whole-array
        # VMEM-resident outputs which phase-1 blocks read back
        cfg = drt_mod.DRTConfig(N=N_clip, kappa=kappa, weight_mode=weight_mode)
        A_self, A_e = drt_mod.drt_edge_mixing(
            d2e_scr[...], n2_scr[...], src[0], dst[0], w[0], cfg, K, dense=dense
        )
        As_ref[...] = A_self
        Ae_ref[...] = A_e

    @pl.when(ph == 1)
    def _combine_phase():
        out_ref[...] = _edge_combine_block(
            self_ref[...].astype(F32), x_dec,
            src, dst, As_ref[pl.ds(p, 1)], Ae_ref[pl.ds(p, 1)],
        )


@functools.partial(
    jax.jit,
    static_argnames=(
        "algorithm", "num_layers", "kappa", "N_clip", "weight_mode", "lane",
        "interpret",
    ),
)
def slab_edge_combine(
    block_layer: jax.Array,
    self_slab: jax.Array,
    dec_slab: jax.Array,
    src: jax.Array,
    dst: jax.Array,
    w: jax.Array,
    *,
    algorithm: str = "drt",
    num_layers: int,
    kappa: float = 1e-6,
    N_clip: float = 32.0,
    weight_mode: str = "paper",
    lane: int = LANES,
    interpret: bool | None = None,
):
    """ONE sparse consensus round's slab work in ONE launch (see module doc).

    ``block_layer``: (n_blocks,) int32 — ``SlabLayout.block_layer``.
    ``self_slab``: (K, D) f32 packed current iterates (the full-precision
    self term).  ``dec_slab``: (K, D) f32 decoded neighbour view (pass
    ``self_slab`` again for an exact round).
    ``src``/``dst``/``w``: (E,) padded directed edge list (w == 0 padding).

    Returns ``(combined (K, D) f32, A_self (L, K), A_e (L, E))`` — the
    edge-factorized mixing weights are kernel outputs so the engine can
    densify them for ``A_last``/telemetry without recomputing stats.
    """
    K, D = self_slab.shape
    nb = block_layer.shape[0]
    if nb * lane != D:
        raise ValueError(f"slab width {D} != {nb} blocks x {lane} lanes")
    E = src.shape[0]
    drt = algorithm == "drt"
    if not drt and algorithm != "classical":
        raise ValueError(f"unknown algorithm {algorithm!r}")
    grid = (2, nb) if drt else (1, nb)

    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),  # whole block->layer map
        pl.BlockSpec((K, lane), lambda ph, i: (0, i)),
        pl.BlockSpec((K, lane), lambda ph, i: (0, i)),
        pl.BlockSpec((1, E), lambda ph, i: (0, 0)),
        pl.BlockSpec((1, E), lambda ph, i: (0, 0)),
        pl.BlockSpec((1, E), lambda ph, i: (0, 0)),
    ]
    out_specs = (
        # DRT's phase 0 parks the slab window on block 0 without writing
        # (same trick as slab_encode_combine); classical is single phase and
        # just walks the blocks.  The A_self/A_e windows are the whole array
        # every step, staying VMEM-resident for the per-block reads
        pl.BlockSpec(
            (K, lane),
            (lambda ph, i: (0, ph * i)) if drt else (lambda ph, i: (0, i)),
        ),
        pl.BlockSpec((num_layers, K), lambda ph, i: (0, 0)),
        pl.BlockSpec((num_layers, E), lambda ph, i: (0, 0)),
    )
    out_shape = (
        jax.ShapeDtypeStruct((K, D), F32),
        jax.ShapeDtypeStruct((num_layers, K), F32),
        jax.ShapeDtypeStruct((num_layers, E), F32),
    )
    # in-kernel edge algebra: XLA's gathers/scatters where the body runs
    # interpreted, the masked-reduction spelling Mosaic lowers otherwise
    interpret = resolve_interpret(interpret)
    kernel = functools.partial(
        _edge_kernel, algorithm, float(kappa), float(N_clip), weight_mode,
        num_layers, not interpret,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=(
            [pltpu.VMEM((num_layers, K), F32), pltpu.VMEM((num_layers, E), F32)]
            if drt
            else []
        ),
        interpret=interpret,
    )(
        jnp.asarray(block_layer, jnp.int32),
        self_slab.astype(F32),
        dec_slab.astype(F32),
        jnp.asarray(src, jnp.int32)[None, :],
        jnp.asarray(dst, jnp.int32)[None, :],
        jnp.asarray(w, F32)[None, :],
    )


# ---------------------------------------------------------------------------
# wire-resident CSR round: in-kernel decode + sort-free segment combine
# ---------------------------------------------------------------------------


def _decode_block(mode, wire_refs):
    """This lane block's decoded (K, lane) f32 view, derived from the compact
    wire refs — the ``slab_codec`` decode machinery run in-VMEM (the decoded
    slab never exists in HBM)."""
    if mode in ("exact", "sent"):
        return wire_refs[0][...].astype(F32)
    if mode in _CAST:
        return wire_refs[0][...].astype(F32)
    if mode == "int8":
        q_ref, s_ref, seg_ref = wire_refs
        # int8 round-trips f32 exactly and the one-hot segment matmul places
        # exactly one unit product per column, so q * s_cols matches the jnp
        # slab_decode bit for bit
        return q_ref[...].astype(F32) * _scale_cols(s_ref, seg_ref[...])
    raise ValueError(f"unknown wire mode {mode!r}")


def _csr_combine_block(x_self, dec, nbr, a_self, a_csr):
    """Sort-free per-destination segment combine: the CSR tables are derived
    from the (dst, src)-sorted edge list, so destination k's edges sit at its
    own CSR row and the combine is Dmax gather-accumulate steps — no
    ``.at[dst].add`` scatter, no serialization hazard.  Padding slots carry
    ``a_csr == 0`` (masked on ``valid``), an exact zero contribution.
    ``a_self`` (Kl, 1) and ``a_csr`` (list of Dmax (Kl, 1)) are columns;
    each neighbour gather is a (Kl, K) one-hot matmul."""
    out = x_self * a_self
    K = dec.shape[0]
    for j in range(nbr.shape[1]):
        iota = jax.lax.broadcasted_iota(jnp.int32, (nbr.shape[0], K), 1)
        onehot = (iota == nbr[:, j : j + 1]).astype(F32)  # (Kl, K)
        gathered = jnp.dot(
            onehot, dec, precision=HIGHEST, preferred_element_type=F32
        )
        out = out + a_csr[j] * gathered
    return out


def _edge_encode_kernel(
    mode, algorithm, kappa, N_clip, weight_mode, num_layers, dmax, dense, *refs
):
    if algorithm == "drt":
        *head, out_ref, As_ref, Ae_ref, n2_scr, d2e_scr = refs
    else:
        *head, out_ref, As_ref, Ae_ref = refs
        n2_scr = d2e_scr = None
    bl_ref, base_ref, self_ref, *rest = head
    wire_refs = rest[:-6]
    src_ref, dst_ref, w_ref, nbr_ref, pos_ref, valid_ref = rest[-6:]

    src = src_ref[...]  # (1, E) rows
    dst = dst_ref[...]
    w = w_ref[...]
    K = wire_refs[0].shape[0]  # TOTAL agents (the wire is everyone's rows)
    Kl = self_ref.shape[0]  # this shard's destination rows
    p = bl_ref[pl.program_id(1)]  # this block's DRT layer
    base = base_ref[0]  # first local destination's global index

    def _combine():
        dec = _decode_block(mode, wire_refs)
        rows = jax.lax.broadcasted_iota(jnp.int32, (Kl, 1), 0) + base
        a_self = _pick_cols(As_ref[pl.ds(p, 1)], rows)  # (Kl, 1)
        a_e = Ae_ref[pl.ds(p, 1)]  # (1, E)
        pos = pos_ref[...]
        valid = valid_ref[...]
        a_csr = [
            jnp.where(
                valid[:, j : j + 1] != 0, _pick_cols(a_e, pos[:, j : j + 1]), 0.0
            )
            for j in range(dmax)
        ]
        out_ref[...] = _csr_combine_block(
            self_ref[...].astype(F32), dec, nbr_ref[...], a_self, a_csr
        )

    if algorithm == "classical":
        # single phase: the Metropolis factors are D-free edge algebra —
        # derive them once at block 0 (the same jnp code as the unkerneled
        # path, in its masked-reduction spelling), combine every block
        @pl.when(pl.program_id(1) == 0)
        def _weights():
            m_self, m_e = metropolis_edge_weights(
                src[0], dst[0], w[0], K, dense=dense
            )
            As_ref[...] = jnp.broadcast_to(m_self, As_ref.shape)
            Ae_ref[...] = jnp.broadcast_to(m_e, Ae_ref.shape)

        _combine()
        return

    ph = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(ph == 0)
    def _stats_phase():
        @pl.when(i == 0)
        def _init():
            n2_scr[...] = jnp.zeros_like(n2_scr)
            d2e_scr[...] = jnp.zeros_like(d2e_scr)

        dec = _decode_block(mode, wire_refs)
        n2_scr[pl.ds(p, 1)] = n2_scr[pl.ds(p, 1)] + _row_sq_sums(dec)
        diff = _take_rows(dec, src) - _take_rows(dec, dst)
        d2e_scr[pl.ds(p, 1)] = d2e_scr[pl.ds(p, 1)] + _row_sq_sums(diff)

    @pl.when(jnp.logical_and(ph == 1, i == 0))
    def _mixing():
        cfg = drt_mod.DRTConfig(N=N_clip, kappa=kappa, weight_mode=weight_mode)
        A_self, A_e = drt_mod.drt_edge_mixing(
            d2e_scr[...], n2_scr[...], src[0], dst[0], w[0], cfg, K, dense=dense
        )
        As_ref[...] = A_self
        Ae_ref[...] = A_e

    @pl.when(ph == 1)
    def _combine_phase():
        _combine()


@functools.partial(
    jax.jit,
    static_argnames=(
        "mode", "algorithm", "num_layers", "kappa", "N_clip", "weight_mode",
        "lane", "interpret",
    ),
)
def slab_edge_encode_combine(
    block_layer: jax.Array,
    self_slab: jax.Array,
    wire_operands: tuple,
    src: jax.Array,
    dst: jax.Array,
    w: jax.Array,
    nbr: jax.Array,
    pos: jax.Array,
    valid: jax.Array,
    dst_base: "jax.Array | int" = 0,
    *,
    mode: str,
    algorithm: str = "drt",
    num_layers: int,
    kappa: float = 1e-6,
    N_clip: float = 32.0,
    weight_mode: str = "paper",
    lane: int = LANES,
    interpret: "bool | None" = None,
):
    """ONE wire-resident sparse consensus round in ONE launch (module doc).

    ``block_layer``: (n_blocks,) int32 — ``SlabLayout.block_layer``.
    ``self_slab``: (K_local, D) f32 — this shard's destination rows, the
    full-precision self term (K_local == K off the mesh).
    ``wire_operands``: the compact wire of ALL K agents, mode-dependent —
      * ``mode='int8'``: ``(q (K, D) int8, scales (K, n_segs) f32,
        col_seg (nb, lane) i32)`` — the ``SlabQuant`` wire plus the static
        column->scale-segment map; dequant runs in-kernel;
      * ``mode='bf16' | 'f16'``: ``(wire (K, D) bf16/f16,)`` — the cast wire;
      * ``mode='sent'``: ``(sent (K, D) f32,)`` — the top-k sent slab;
      * ``mode='exact'``: ``(slab (K, D) f32,)`` — an exact round (the wire
        IS the slab; pass ``self_slab`` again off the mesh).
    ``src``/``dst``/``w``: (E,) padded directed edge list (w == 0 padding).
    ``nbr``/``pos``/``valid``: (K_local, Dmax) CSR tables from
    ``csr_from_edges`` (``valid`` any integer/bool dtype), rows matching
    ``self_slab``'s destinations.  ``dst_base``: global index of local
    destination row 0 (traced scalar; ``shard_index * K_local`` on a mesh).

    Returns ``(combined (K_local, D) f32, A_self (L, K), A_e (L, E))``.
    """
    Kl, D = self_slab.shape
    nb = block_layer.shape[0]
    if nb * lane != D:
        raise ValueError(f"slab width {D} != {nb} blocks x {lane} lanes")
    E = src.shape[0]
    dmax = nbr.shape[1]
    drt = algorithm == "drt"
    if not drt and algorithm != "classical":
        raise ValueError(f"unknown algorithm {algorithm!r}")
    grid = (2, nb) if drt else (1, nb)

    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),  # whole block->layer map
        pl.BlockSpec(memory_space=pltpu.SMEM),  # dst_base
        # the self slab is only read by the combine phase: park its window on
        # block 0 through DRT's stats phase (same trick as the output spec)
        # so the round reads the f32 slab ONCE, not once per phase
        pl.BlockSpec(
            (Kl, lane),
            (lambda ph, i: (0, ph * i)) if drt else (lambda ph, i: (0, i)),
        ),
    ]
    operands = [
        jnp.asarray(block_layer, jnp.int32),
        jnp.asarray(dst_base, jnp.int32)[None],
        self_slab.astype(F32),
    ]
    if mode == "int8":
        q, scales, col_seg = wire_operands
        K = q.shape[0]
        n_segs = scales.shape[-1]
        in_specs += [
            pl.BlockSpec((K, lane), lambda ph, i: (0, i)),
            pl.BlockSpec((K, n_segs), lambda ph, i: (0, 0)),
            pl.BlockSpec((None, 1, lane), lambda ph, i: (i, 0, 0)),
        ]
        operands += [
            jnp.asarray(q, jnp.int8),
            scales.astype(F32),
            jnp.asarray(col_seg, jnp.int32).reshape(nb, 1, lane),
        ]
    elif mode in ("exact", "sent") or mode in _CAST:
        (wire,) = wire_operands
        K = wire.shape[0]
        wire = wire.astype(F32) if mode in ("exact", "sent") else wire
        in_specs += [pl.BlockSpec((K, lane), lambda ph, i: (0, i))]
        operands += [wire]
    else:
        raise ValueError(f"unknown wire mode {mode!r}")
    in_specs += [
        pl.BlockSpec((1, E), lambda ph, i: (0, 0)),
        pl.BlockSpec((1, E), lambda ph, i: (0, 0)),
        pl.BlockSpec((1, E), lambda ph, i: (0, 0)),
        pl.BlockSpec((Kl, dmax), lambda ph, i: (0, 0)),
        pl.BlockSpec((Kl, dmax), lambda ph, i: (0, 0)),
        pl.BlockSpec((Kl, dmax), lambda ph, i: (0, 0)),
    ]
    operands += [
        jnp.asarray(src, jnp.int32)[None, :],
        jnp.asarray(dst, jnp.int32)[None, :],
        jnp.asarray(w, F32)[None, :],
        jnp.asarray(nbr, jnp.int32),
        jnp.asarray(pos, jnp.int32),
        jnp.asarray(valid, jnp.int32),
    ]
    out_specs = (
        pl.BlockSpec(
            (Kl, lane),
            (lambda ph, i: (0, ph * i)) if drt else (lambda ph, i: (0, i)),
        ),
        pl.BlockSpec((num_layers, K), lambda ph, i: (0, 0)),
        pl.BlockSpec((num_layers, E), lambda ph, i: (0, 0)),
    )
    out_shape = (
        jax.ShapeDtypeStruct((Kl, D), F32),
        jax.ShapeDtypeStruct((num_layers, K), F32),
        jax.ShapeDtypeStruct((num_layers, E), F32),
    )
    # in-kernel edge algebra: XLA's gathers/scatters where the body runs
    # interpreted, the masked-reduction spelling Mosaic lowers otherwise
    interpret = resolve_interpret(interpret)
    kernel = functools.partial(
        _edge_encode_kernel, mode, algorithm, float(kappa), float(N_clip),
        weight_mode, num_layers, dmax, not interpret,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=(
            [pltpu.VMEM((num_layers, K), F32), pltpu.VMEM((num_layers, E), F32)]
            if drt
            else []
        ),
        interpret=interpret,
    )(*operands)
