"""Flat-slab parameter representation for the consensus hot path.

The per-leaf tree walk (``LayerPartition.pairwise_sq_dists`` / ``combine`` /
``scale_by_layer``) issues one small einsum per leaf per group and re-traverses
the pytree on every consensus round.  On launch-overhead-bound backends that
traversal dominates the combine step.  This module packs an agent-stacked
parameter tree ONCE into a contiguous ``(K, D)`` slab with a static
per-DRT-layer segment layout; all distance statistics, mixing-matrix inputs and
weighted combines then run as a handful of segment matmuls / broadcasts (one
op per top-level *group* instead of one per *leaf*), and the tree is unpacked
once after the last round.

Layout
------
Columns are grouped exactly like :class:`~repro.utils.pytree.LayerPartition`:

* plain group   -- all float leaves flattened and concatenated, padded up to a
  lane multiple (128): ONE layer segment.
* stacked group -- per scan slot ``j``, the slot-``j`` slice of every float
  leaf concatenated, padded to the lane multiple; slot segments are contiguous,
  so the whole group region reshapes to ``(K, n_slots, s_pad)`` for batched
  per-layer matmuls.

Padding columns are zero and are assigned to the layer (and codec segment)
they pad, so every segment reduction (squared norms, Gram products, absmax
scales, top-k thresholds) is unaffected by them.  Non-float leaves are NOT
packed: they pass through ``unpack`` verbatim from the ``like`` tree (the
consensus engines leave them untouched).

Regions: the round-loop working form
------------------------------------
``pack``/``unpack`` expose the single contiguous ``(..., D)`` slab (the wire /
storage form).  Between rounds the engines carry the SAME bytes as *regions*
— a tuple with one contiguous ``(..., n_slots, s_pad)`` buffer per group
(``split``/``join`` convert, ``pack_regions``/``unpack_regions`` go straight
from/to trees).  Every per-round op (Gram, combine, norms, codec transforms)
runs whole-region, so XLA never re-slices or re-concatenates the full slab
inside the round loop — that is where the tree path's per-leaf launch overhead
(and a naive flat-slab implementation's D-sized copies) goes away.

Codec fast paths
----------------
``slab_encode`` / ``slab_decode`` reimplement the built-in ``repro.comm``
codecs on the regions:

* identity / bf16 / f16 -- elementwise per region, bit-identical to the tree
  codec.
* int8  -- absmax scales at the same granularity as the tree codec (per
  (leaf, slot) for stacked groups, per leaf otherwise) from static region
  slices, the same per-leaf counter-based uniform draws
  (:mod:`repro.comm.rng`), quantize/dequantize elementwise per region: wire
  values bit-identical to ``Int8StochasticCodec``.
* topk  -- per-leaf thresholds via the shared (subsampled) rule
  ``repro.comm.codec.topk_threshold`` over static region slices, exactly the
  tree rule, with the error-feedback residual carried as regions; residuals
  match the tree codec bit for bit.

Two encode layouts implement the same wire:

* ``slab_encode`` — ONE agent's regions (the two-phase oracle; the permute
  engine's per-shard path).  Engines used to ``vmap`` this over the agent
  axis; the resulting transposes (``out_axes=1``) and per-leaf batching
  dominated the coded round on CPU.
* ``slab_encode_batched`` — the fused hot path: natively batched over the
  agent axis of the slot-major regions (the agent axis stays where it
  lives, axis 1), scales/uniforms/thresholds computed from static per-leaf
  slices with NO gathers and NO transposes.  Wire bits (values, scales,
  rounding decisions, EF residual) are identical to ``slab_encode`` by
  construction — asserted leaf-for-leaf in ``tests/test_packing.py``.
  (``slab_decode`` is batch-generic and serves both layouts.)

Codecs without a slab fast path (``slab_codec_supported`` is False) — and
parameter trees with any non-float leaf (``slab_template_supported`` is
False: the tree oracle casts those into the distance statistics, the slab
would exclude them) — make the engines fall back to the per-leaf tree path.
``pack``/``unpack`` themselves still handle mixed-dtype trees (non-float
leaves pass through) for standalone use.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm.codec import (
    CastCodec,
    IdentityCodec,
    Int8StochasticCodec,
    TopKCodec,
    _topk_sample_plan,
    topk_threshold,
)
from repro.comm.rng import counter_uniform, key_words, uniform_from_words
from repro.utils.pytree import LayerPartition, sq_dists_from_grams

PyTree = Any
F32 = jnp.float32
# consensus contractions mix f32 parameters: on TPU the default matmul pass
# rounds operands to bf16, which would drop parameter bits every round
HIGHEST = jax.lax.Precision.HIGHEST

LANES = 128  # TPU lane width; layer segments are padded to a multiple of this


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def _is_float(x) -> bool:
    return jnp.issubdtype(jnp.dtype(x.dtype), jnp.floating)


@dataclasses.dataclass(frozen=True)
class LeafPlan:
    """Placement of one template leaf inside its group region."""

    shape: tuple[int, ...]  # unbatched leaf shape (includes the slot axis)
    dtype: Any
    is_float: bool
    local_idx: int  # position in jax.tree.flatten order of the group subtree
    flat_idx: int  # position in jax.tree.flatten order of the FULL tree
    col0: int  # start column within the (slot) segment; floats only
    width: int  # per-slot width (stacked group) or full width (plain)
    scale_per_slot: bool  # int8: one scale per scan slot vs one per leaf
    scale_seg0: int  # first int8 scale-segment id owned by this leaf


@dataclasses.dataclass(frozen=True)
class GroupPlan:
    key: str
    stacked: bool
    n_slots: int
    layer0: int  # first DRT layer index (LayerPartition offset)
    col0: int  # flat-slab column where the group region starts
    s: int  # unpadded per-slot width
    s_pad: int  # lane-padded per-slot width
    leaves: tuple[LeafPlan, ...]

    @property
    def width(self) -> int:
        return self.n_slots * self.s_pad

    @property
    def float_leaves(self) -> tuple[LeafPlan, ...]:
        return tuple(p for p in self.leaves if p.is_float)


class SlabQuant(NamedTuple):
    """Wire form of an int8-quantized slab: per-region int8 values + the
    per-segment f32 scales (one entry per (leaf, slot) / leaf segment)."""

    q: tuple  # tuple of int8 slot-major regions, each (n_slots, *batch, s_pad)
    s: jax.Array  # f32, (*batch, n_scale_segs)


@dataclasses.dataclass(frozen=True, eq=False)
class SlabLayout:
    """Static packing plan: tree <-> ``(..., D)`` slab with layer segments.

    Built once per model (``build_slab_layout`` /
    ``LayerPartition.slab_layout``); every field is static Python/numpy data,
    so jitted functions can close over a layout freely.
    """

    groups: tuple[GroupPlan, ...]
    num_layers: int
    D: int  # total (padded) slab width
    dtype: Any  # slab dtype (float leaves are cast to this on pack)
    layer_slices: tuple[tuple[int, int], ...]  # (start, stop) per DRT layer
    layer_sizes: tuple[int, ...]  # unpadded valid width per DRT layer
    n_tree_leaves: int  # leaf count of the FULL template (rng-split parity)
    col_scale_seg: np.ndarray  # (D,) int32: int8 scale segment per column
    n_scale_segs: int
    lane: int = LANES  # column-block width every layer segment is padded to

    # -- lane-block maps (whole-slab batched kernels) -------------------------

    @property
    def n_blocks(self) -> int:
        return self.D // self.lane

    @functools.cached_property
    def block_layer(self) -> np.ndarray:
        """(D // lane,) int32: the DRT layer owning each lane-wide column
        block.  Layer segments are lane-padded, so a block never straddles a
        layer boundary — the whole-slab kernels that stream the packed
        (K, D) slab through one grid of 128-lane blocks
        (``slab_dequant_combine``, ``slab_source_combine``, and the edge and
        codec kernels) read one layer per block from this map.  The exact
        ``slab_combine`` does not: it runs per group on the regions, whose
        slots are one layer each."""
        out = np.empty(self.n_blocks, np.int32)
        for p, (s, e) in enumerate(self.layer_slices):
            out[s // self.lane : e // self.lane] = p
        return out

    @functools.cached_property
    def col_leaf(self) -> np.ndarray:
        """(D,) int32: FULL-tree flat leaf index owning each column (padding
        columns inherit their slot segment's last float leaf).  Together with
        :attr:`col_idx` this is the static map the fused encode kernels use
        to reproduce the per-leaf counter RNG in-kernel: column ``c``'s
        uniform is ``hash(key_words(leaf_key[col_leaf[c]]), col_idx[c])`` —
        the same bits the tree codec draws for that element."""
        return self._col_rng_maps[0]

    @functools.cached_property
    def col_idx(self) -> np.ndarray:
        """(D,) uint32: each column's row-major linear element index within
        its leaf (0 on padding columns; see :attr:`col_leaf`)."""
        return self._col_rng_maps[1]

    @functools.cached_property
    def _col_rng_maps(self) -> tuple[np.ndarray, np.ndarray]:
        leaf = np.empty(self.D, np.int32)
        idx = np.zeros(self.D, np.uint32)
        for grp in self.groups:
            for j in range(grp.n_slots):
                base = grp.col0 + j * grp.s_pad
                for plan in grp.float_leaves:
                    c0 = base + plan.col0
                    leaf[c0 : c0 + plan.width] = plan.flat_idx
                    idx[c0 : c0 + plan.width] = j * plan.width + np.arange(
                        plan.width, dtype=np.uint32
                    )
                if grp.s_pad > grp.s:
                    leaf[base + grp.s : base + grp.s_pad] = grp.float_leaves[
                        -1
                    ].flat_idx
        return leaf, idx

    def col_maps(self) -> tuple[jax.Array, jax.Array, jax.Array]:
        """:attr:`col_scale_seg`, :attr:`col_leaf` and :attr:`col_idx` as
        ``(n_blocks, lane)`` arrays (int32, int32, uint32), built from fills
        and iotas over the layout's runs of columns, so that a jitted
        caller holds no D-sized constant (three 1.6 GB literals at the width
        of one h2o-danube-3-4b layer)."""
        seg, leaf, idx = [], [], []
        for grp in self.groups:
            last = grp.float_leaves[-1]
            for j in range(grp.n_slots):
                for plan in grp.float_leaves:
                    sid = plan.scale_seg0 + (j if plan.scale_per_slot else 0)
                    seg.append(jnp.full((plan.width,), sid, jnp.int32))
                    leaf.append(jnp.full((plan.width,), plan.flat_idx, jnp.int32))
                    idx.append(
                        j * plan.width + jnp.arange(plan.width, dtype=jnp.uint32)
                    )
                pad = grp.s_pad - grp.s
                if pad:
                    # padding inherits the last leaf's segment and leaf
                    seg.append(jnp.full((pad,), seg[-1][0], jnp.int32))
                    leaf.append(jnp.full((pad,), last.flat_idx, jnp.int32))
                    idx.append(jnp.zeros((pad,), jnp.uint32))
        shape = (self.n_blocks, self.lane)
        return tuple(jnp.concatenate(m).reshape(shape) for m in (seg, leaf, idx))

    # -- batch handling -------------------------------------------------------

    def _batch_shape(self, tree: PyTree) -> tuple[int, ...]:
        for grp in self.groups:
            leaves = jax.tree.leaves(tree[grp.key])
            for plan in grp.float_leaves:
                leaf = leaves[plan.local_idx]
                nb = leaf.ndim - len(plan.shape)
                if nb < 0:
                    raise ValueError(
                        f"leaf {grp.key!r}[{plan.local_idx}] has shape "
                        f"{leaf.shape}, template expects trailing {plan.shape}"
                    )
                return leaf.shape[:nb]
        raise ValueError("layout has no float leaves to pack")

    # -- tree -> regions -> flat slab ----------------------------------------

    def pack_regions(self, tree: PyTree) -> tuple:
        """Pack a parameter tree into per-group regions: a tuple with one
        contiguous SLOT-MAJOR ``(n_slots, *batch, s_pad)`` array per group.
        Leaves may carry any number of leading batch axes (identical across
        leaves) — e.g. the agent axis K, which lands at axis 1.  Slot-major
        order keeps the per-layer batch dimension LEADING in every round-loop
        matmul (measured up to 10x faster than contracting with the slot axis
        in the middle).  Float leaves are cast to the slab dtype; non-float
        leaves are skipped (see ``unpack_regions``)."""
        batch = self._batch_shape(tree)
        regions = []
        for grp in self.groups:
            leaves = jax.tree.leaves(tree[grp.key])
            arrays = [leaves[p.local_idx] for p in grp.float_leaves]
            regions.append(self._pack_group_arrays(grp, arrays, batch))
        return tuple(regions)

    def _pack_group_arrays(self, grp: GroupPlan, arrays, batch: tuple[int, ...]):
        """One group's float-leaf arrays (plan order) -> (n_slots, *batch, s_pad)."""
        parts = []
        for plan, arr in zip(grp.float_leaves, arrays):
            nb = arr.ndim - len(plan.shape)
            if nb < 0 or arr.shape[nb:] != plan.shape or arr.shape[:nb] != batch:
                raise ValueError(
                    f"leaf {grp.key!r}[{plan.local_idx}] has shape {arr.shape}; "
                    f"layout expects {(*batch, *plan.shape)}"
                )
            n = grp.n_slots if grp.stacked else 1
            if n == 1:
                piece = _unit_free_reshape(arr.astype(self.dtype), (*batch, plan.width))
                parts.append(piece[None])
                continue
            piece = arr.astype(self.dtype).reshape(*batch, n, plan.width)
            parts.append(jnp.moveaxis(piece, -2, 0))  # (n, *batch, width)
        pad = grp.s_pad - grp.s
        if pad:
            # lane padding rides along in the concat — a jnp.pad afterwards
            # would re-copy the whole region
            parts.append(
                jnp.zeros((grp.n_slots, *batch, pad), self.dtype)
            )
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=-1)

    def unpack_regions(
        self, regions: tuple, like: PyTree, dtype: Any | None = None
    ) -> PyTree:
        """Inverse of :meth:`pack_regions`.  ``like`` supplies the tree
        structure, original leaf dtypes and the non-float (passthrough)
        leaves; its float leaf VALUES are ignored.  ``dtype`` overrides the
        template leaf dtypes for float leaves — e.g. ``jnp.float32`` when
        unpacking a codec's error-feedback residual, which must stay f32
        regardless of the parameter dtype."""
        batch = regions[0].shape[1:-1]
        out = {}
        for grp, region in zip(self.groups, regions):
            leaves, treedef = jax.tree.flatten(like[grp.key])
            new_leaves = list(leaves)
            for plan in grp.float_leaves:
                piece = jax.lax.slice_in_dim(
                    region, plan.col0, plan.col0 + plan.width, axis=-1
                )  # (n, *batch, width)
                if grp.n_slots == 1:
                    piece = _unit_free_reshape(piece[0], (*batch, *plan.shape))
                else:
                    piece = jnp.moveaxis(piece, 0, -2)  # (*batch, n, width)
                new_leaves[plan.local_idx] = piece.reshape(
                    *batch, *plan.shape
                ).astype(dtype if dtype is not None else plan.dtype)
            out[grp.key] = jax.tree.unflatten(treedef, new_leaves)
        for key in like:
            if key not in out:
                out[key] = like[key]
        return out

    def join(self, regions: tuple) -> jax.Array:
        """Regions -> the contiguous ``(..., D)`` flat slab (batch leading)."""
        batch = regions[0].shape[1:-1]
        return jnp.concatenate(
            [
                jnp.moveaxis(r, 0, -2).reshape(*batch, g.width)
                for g, r in zip(self.groups, regions)
            ],
            axis=-1,
        )

    def split(self, slab: jax.Array) -> tuple:
        """Flat ``(..., D)`` slab (batch leading) -> slot-major regions."""
        batch = slab.shape[:-1]
        out = []
        for grp in self.groups:
            region = jax.lax.slice_in_dim(
                slab, grp.col0, grp.col0 + grp.width, axis=-1
            )
            region = region.reshape(*batch, grp.n_slots, grp.s_pad)
            out.append(jnp.moveaxis(region, -2, 0))
        return tuple(out)

    def pack(self, tree: PyTree) -> jax.Array:
        """Pack a parameter tree into the contiguous ``(..., D)`` slab."""
        return self.join(self.pack_regions(tree))

    def unpack(self, slab: jax.Array, like: PyTree) -> PyTree:
        """Inverse of :meth:`pack` (see ``unpack_regions``)."""
        return self.unpack_regions(self.split(slab), like)

    def pack_uniforms(self, key: jax.Array) -> tuple:
        """U[0,1) draws in region layout, bit-matching the tree int8 codec:
        the key is split over ALL template leaves (floats and passthroughs
        alike, exactly like ``Int8StochasticCodec.encode``) and each float
        leaf's counter-based draw (:func:`repro.comm.rng.counter_uniform`)
        is packed into its columns.  Padding columns get 0."""
        keys = jax.random.split(key, self.n_tree_leaves)
        regions = []
        for grp in self.groups:
            arrays = [
                counter_uniform(keys[p.flat_idx], p.shape)
                for p in grp.float_leaves
            ]
            regions.append(self._pack_group_arrays(grp, arrays, ()))
        return tuple(regions)

    # -- segment reductions ---------------------------------------------------

    def layer_sq_norms(self, regions: tuple) -> jax.Array:
        """Per-DRT-layer squared norms over regions -> ``(L, *batch)`` f32."""
        outs = []
        for region in regions:
            outs.append(jnp.sum(jnp.square(region.astype(F32)), axis=-1))
        return jnp.concatenate(outs, axis=0)

    def gram(self, regions: tuple) -> jax.Array:
        """Per-layer agent Gram matrices from slot-major ``(n_slots, K,
        s_pad)`` regions, of the raw regions and of the regions less agent
        0's, stacked ``(2, L, K, K)``: ONE batched matmul per group each
        (leading batch dim, no transposes).  The second keeps the distances
        that rounding takes from the first once the agents nearly agree
        (:func:`repro.utils.pytree.sq_dists_from_grams`); both follow
        :func:`gram_update`, since a column-stochastic combine maps iterates
        less a fixed vector to iterates less the same vector."""
        grams = []
        for x in (regions, tuple(r.astype(F32) - r[:, :1].astype(F32) for r in regions)):
            grams.append(jnp.concatenate([
                jnp.einsum(
                    "nks,njs->nkj", region, region,
                    precision=HIGHEST, preferred_element_type=F32,
                )
                for region in x
            ], axis=0))
        return jnp.stack(grams)  # (2, L, K, K)

    def pairwise_sq_dists(self, regions: tuple) -> tuple[jax.Array, jax.Array]:
        """All-pairs per-layer squared distances via the Gram trick.
        Returns ``(d2 (L, K, K), n2 (L, K))``."""
        return gram_sq_dists(self.gram(regions))

    def edge_sq_dists(self, regions: tuple, src: jax.Array, dst: jax.Array) -> jax.Array:
        """Per-EDGE per-layer squared distances ``||x_src - x_dst||^2`` over
        a padded directed edge list — ``(L, E)`` f32, O(|E| D) where the
        dense :meth:`pairwise_sq_dists` Gram is O(K^2 D).  Direct differences
        (not the Gram trick): on a sparse graph materializing only the
        realized pairs is the whole point.  Padding edges (src = dst = 0)
        produce exact 0 rows."""
        outs = []
        for region in regions:
            x = region.astype(F32)
            diff = jnp.take(x, src, axis=1) - jnp.take(x, dst, axis=1)
            outs.append(jnp.sum(jnp.square(diff), axis=-1))  # (n, E)
        return jnp.concatenate(outs, axis=0)

    def edge_combine(
        self,
        A_self: jax.Array,
        A_e: jax.Array,
        src: jax.Array,
        dst: jax.Array,
        regions_self: tuple,
        regions_dec: tuple,
    ) -> tuple:
        """Sparse mixing combine: gather-by-edge + scatter-add-by-destination,
        O(|E| D) against :meth:`combine`'s O(K^2 D) matmul.

        ``new[p, k] = A_self[p, k] * self[p, k]
                      + sum_{e: dst[e]==k} A_e[p, e] * dec[p, src[e]]``

        ``regions_self`` carries each agent's OWN (full-precision) regions,
        ``regions_dec`` the decoded neighbour view (the same tuple on an
        exact round) — mirroring the coded dense path's self/off-diagonal
        split.  Padding edges must arrive with ``A_e == 0`` (the weight
        builders guarantee it), making their scatter contribution exact 0.
        """
        out = []
        for grp, reg_s, reg_d in zip(self.groups, regions_self, regions_dec):
            a_self = jax.lax.slice_in_dim(
                A_self, grp.layer0, grp.layer0 + grp.n_slots, axis=0
            )  # (n, K)
            a_e = jax.lax.slice_in_dim(
                A_e, grp.layer0, grp.layer0 + grp.n_slots, axis=0
            )  # (n, E)
            acc = reg_s.astype(F32) * a_self[..., None]
            gathered = jnp.take(reg_d.astype(F32), src, axis=1) * a_e[..., None]
            out.append(acc.at[:, dst].add(gathered))
        return tuple(out)

    # -- CSR (per-destination) sparse round pieces ----------------------------
    #
    # The scatter in :meth:`edge_combine` serializes on CPU backends.  The
    # CSR formulation (``csr_from_edges``) makes the whole sparse round
    # gather-only: ``Dmax`` neighbour gathers shared between the distance
    # stats and the combine, then pure elementwise work.

    def csr_neighbor_rows(self, regions: tuple, nbr: jax.Array) -> list:
        """One gathered neighbour slab per CSR in-slot: ``nbr`` is
        ``(K, Dmax)`` source indices; returns a length-``Dmax`` list of
        region tuples (``regions``-shaped, f32).  Padded slots gather agent
        0's rows — their weights are zero downstream."""
        return [
            tuple(jnp.take(reg.astype(F32), nbr[:, j], axis=1) for reg in regions)
            for j in range(nbr.shape[1])
        ]

    def csr_sq_dists(self, regions: tuple, nbr_rows: list) -> jax.Array:
        """Per-layer squared distances of each agent to each gathered
        in-neighbour — ``(L, K, Dmax)`` f32.  Same per-element differences
        as :meth:`edge_sq_dists` in CSR layout (map between the two with
        ``csr_from_edges``'s ``rank``)."""
        cols = []
        for nbrj in nbr_rows:
            outs = []
            for reg, g in zip(regions, nbrj):
                diff = g - reg.astype(F32)
                outs.append(jnp.sum(jnp.square(diff), axis=-1))  # (n, K)
            cols.append(jnp.concatenate(outs, axis=0))  # (L, K)
        return jnp.stack(cols, axis=-1)

    def csr_combine(
        self,
        A_self: jax.Array,
        a_csr: jax.Array,
        regions_self: tuple,
        nbr_rows: list,
    ) -> tuple:
        """Gather-only sparse combine: ``new[p, k] = A_self[p, k] self[p, k]
        + sum_j a_csr[p, k, j] nbr_rows[j][p, k]`` — no scatter; padded CSR
        slots arrive with ``a_csr == 0``.  ``nbr_rows`` is the same list the
        stats consumed, so XLA gathers each neighbour slab once."""
        out = []
        for gi, (grp, reg_s) in enumerate(zip(self.groups, regions_self)):
            a_self = jax.lax.slice_in_dim(
                A_self, grp.layer0, grp.layer0 + grp.n_slots, axis=0
            )  # (n, K)
            acc = reg_s.astype(F32) * a_self[..., None]
            for j, nbrj in enumerate(nbr_rows):
                a_j = jax.lax.slice_in_dim(
                    a_csr[..., j], grp.layer0, grp.layer0 + grp.n_slots, axis=0
                )
                acc = acc + nbrj[gi] * a_j[..., None]
            out.append(acc)
        return tuple(out)

    # -- weighted combines -----------------------------------------------------

    def combine(self, A: jax.Array, regions: tuple) -> tuple:
        """Per-layer mixing: one batched matmul per group, regions in,
        regions out (nothing is transposed, re-sliced or re-concatenated
        inside the round loop).

        ``A``: (L, K, K) column-stochastic over axis 1;
        ``new[p, k, c] = sum_l A[p, l, k] region[p, l, c]``.
        """
        out = []
        for grp, region in zip(self.groups, regions):
            A_g = A[grp.layer0 : grp.layer0 + grp.n_slots].astype(F32)
            out.append(
                jax.lax.dot_general(
                    A_g, region,
                    (((1,), (1,)), ((0,), (0,))),  # contract l, batch over n
                    precision=HIGHEST,
                    preferred_element_type=F32,
                )  # (n, k, s)
            )
        return tuple(out)

    def combine_unpack(self, A: jax.Array, regions: tuple, like: PyTree) -> PyTree:
        """Fused final combine + unpack: apply the per-layer mixing matrices
        and write each output LEAF directly (one read of the regions, one
        write per leaf) instead of materializing combined regions and then
        unpacking them — saves a full pass over D at the end of an exact
        (uncoded) round-set.  Requires exactly one batch axis (the agents)."""
        batch = regions[0].shape[1:-1]
        if len(batch) != 1:
            raise ValueError("combine_unpack needs a single (agent) batch axis")
        out = {}
        for grp, region in zip(self.groups, regions):
            A_g = A[grp.layer0 : grp.layer0 + grp.n_slots].astype(F32)
            leaves, treedef = jax.tree.flatten(like[grp.key])
            new_leaves = list(leaves)
            for plan in grp.float_leaves:
                piece = jax.lax.slice_in_dim(
                    region, plan.col0, plan.col0 + plan.width, axis=-1
                )  # (n, *batch, width)
                mixed = jax.lax.dot_general(
                    A_g, piece, (((1,), (1,)), ((0,), (0,))),
                    precision=HIGHEST, preferred_element_type=F32,
                )  # (n, *batch=k, width)
                mixed = jnp.moveaxis(mixed, 0, -2)  # (*batch, n, width)
                new_leaves[plan.local_idx] = mixed.reshape(
                    *batch, *plan.shape
                ).astype(plan.dtype)
            out[grp.key] = jax.tree.unflatten(treedef, new_leaves)
        for key in like:
            if key not in out:
                out[key] = like[key]
        return out

    def scale_by_layer(self, weights: jax.Array, regions: tuple) -> tuple:
        """Multiply regions by per-layer weights.

        ``weights``: (..., L) with leading batch axes matching the regions'
        (e.g. (L,) for one agent, (K, L) for per-agent self weights).
        """
        out = []
        for grp, region in zip(self.groups, regions):
            w = jax.lax.slice_in_dim(
                weights, grp.layer0, grp.layer0 + grp.n_slots, axis=-1
            )  # (*batch, n)
            out.append(region * jnp.moveaxis(w, -1, 0)[..., None])
        return tuple(out)


def _unit_free_reshape(x: jax.Array, shape: tuple) -> jax.Array:
    """``x.reshape(shape)`` between one-slot leaves and regions, relaid out
    between the two shapes with their size-1 axes dropped.  On TPU, XLA
    lays a leaf out into another tiling with one fused copy, but lowers
    the same relayout as a reshape whose generated code grows with the
    array where a size-1 axis sits on either side (127 MB for one
    h2o-danube-3-4b embedding at K=2, 186 MB for its layer's group); a
    loaded program's code stays in HBM, where ``peak_hbm_gib`` counts it.
    The barriers keep XLA from folding the size-1 axes back into the
    relayout."""
    core = tuple(d for d in shape if d != 1)
    if core == tuple(d for d in x.shape if d != 1):
        return x.reshape(shape)
    barrier = jax.lax.optimization_barrier
    x = barrier(x.reshape(tuple(d for d in x.shape if d != 1)))
    return barrier(x.reshape(core)).reshape(shape)


def gram_sq_dists(gram: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Distance statistics ``(d2 (L, K, K), n2 (L, K))`` from the
    ``(2, L, K, K)`` Gram stack of :meth:`SlabLayout.gram`:
    ``d2[p,l,k] = n2[p,l] + n2[p,k] - 2 gram[p,l,k]`` (clamped at 0), from
    the centred Gram where the raw one has lost it to rounding."""
    return sq_dists_from_grams(gram[0], gram[1])


def gram_update(gram: jax.Array, A: jax.Array) -> jax.Array:
    """Exact Gram recurrence of one combine round: ``psi' = A^T psi`` per
    layer implies ``G' = A^T G A``.  With an exact (uncoded) exchange this
    lets a whole round-set run on (L, K, K) matrices — one Gram pass before
    the rounds, one combine after — instead of two passes over all D
    parameters per round.  A ``(2, L, K, K)`` :meth:`SlabLayout.gram`
    stack is updated member by member."""
    if gram.ndim == 4:
        return jnp.stack([gram_update(g, A) for g in gram])
    A = A.astype(F32)
    return jnp.einsum(
        "pia,pij,pjb->pab", A, gram, A,
        precision=HIGHEST, preferred_element_type=F32,
    )


def gram_disagreement(gram: jax.Array) -> jax.Array:
    """Network disagreement ``mean_k ||x_k - x_bar||^2`` (summed over
    layers) read off per-layer Gram matrices ``(L, K, K)``.

    Per layer: ``mean_k G[kk] - mean_{kl} G[kl]`` — the telemetry path's
    free ride on the exact consensus recurrence (no extra pass over the D
    parameters; :func:`region_disagreement` is the direct oracle).  Reads
    the raw Gram of a :meth:`SlabLayout.gram` stack."""
    gram = gram[0]
    diag = jnp.diagonal(gram, axis1=1, axis2=2)  # (L, K)
    return jnp.sum(jnp.mean(diag, axis=-1) - jnp.mean(gram, axis=(-2, -1)))


def region_disagreement(regions: tuple) -> jax.Array:
    """Direct network disagreement ``mean_k ||x_k - x_bar||^2`` over
    agent-batched slab regions (leaves ``(n_slots, K, s_pad)``).

    Lane-padding columns are zero across agents, so they cancel against the
    mean and contribute nothing."""
    K = regions[0].shape[1]
    total = jnp.zeros((), F32)
    for region in regions:
        x = region.astype(F32)
        total = total + jnp.sum(jnp.square(x - jnp.mean(x, axis=1, keepdims=True)))
    return total / float(K)


# ---------------------------------------------------------------------------
# layout construction
# ---------------------------------------------------------------------------


def build_slab_layout(
    partition: LayerPartition,
    template: PyTree,
    dtype=F32,
    lane: int = LANES,
) -> SlabLayout:
    """Build the static packing plan for ``template`` (a single-agent tree of
    arrays or ShapeDtypeStructs) under ``partition``'s layer assignment."""
    if not isinstance(template, dict):
        raise TypeError("template must be a top-level dict")
    # full-tree flatten offsets (sorted top-level keys), for rng-split parity
    flat_offsets = {}
    off = 0
    for key in sorted(template):
        flat_offsets[key] = off
        off += len(jax.tree.leaves(template[key]))
    n_tree_leaves = off

    groups: list[GroupPlan] = []
    col = 0
    col_scale: list[np.ndarray] = []
    layer_slices: list[tuple[int, int]] = []
    layer_sizes: list[int] = []
    n_scale = 0

    for g in partition.groups:
        leaves = jax.tree.leaves(template[g.key])
        codec_stacked = g.key.endswith("blocks")  # the wire codecs' rule
        plans: list[LeafPlan] = []
        s = 0
        for i, leaf in enumerate(leaves):
            is_f = _is_float(leaf)
            shape = tuple(int(d) for d in leaf.shape)
            width = (
                int(np.prod(shape[1:], dtype=np.int64))
                if g.stacked
                else int(np.prod(shape, dtype=np.int64))
            )
            if not is_f:
                plans.append(LeafPlan(
                    shape=shape, dtype=jnp.dtype(leaf.dtype), is_float=False,
                    local_idx=i, flat_idx=flat_offsets[g.key] + i,
                    col0=-1, width=0, scale_per_slot=False, scale_seg0=-1,
                ))
                continue
            # int8 scale segments: per (leaf, slot) when the codec treats the
            # group as stacked and the leaf has a per-slot extent; per leaf
            # otherwise (mirrors the tree codec's _quant_scale_axes)
            per_slot = codec_stacked and len(shape) >= 2
            scale_seg0 = n_scale
            n_scale += g.n_slots if per_slot else 1
            plans.append(LeafPlan(
                shape=shape, dtype=jnp.dtype(leaf.dtype), is_float=True,
                local_idx=i, flat_idx=flat_offsets[g.key] + i,
                col0=s, width=width, scale_per_slot=per_slot,
                scale_seg0=scale_seg0,
            ))
            s += width
        float_plans = [p for p in plans if p.is_float]
        if not float_plans:
            # the partition assigned this group DRT layer indices, so skipping
            # it would silently misalign every later group's gram rows
            raise ValueError(
                f"group {g.key!r} has no float leaves but owns DRT layers "
                f"{g.offset}..{g.offset + g.n_slots - 1}; the slab path "
                "requires all-float parameters (use consensus_path='tree')"
            )
        s_pad = _round_up(s, lane)
        pad = s_pad - s
        grp = GroupPlan(
            key=g.key,
            stacked=g.stacked,
            n_slots=g.n_slots,
            layer0=g.offset,
            col0=col,
            s=s,
            s_pad=s_pad,
            leaves=tuple(plans),
        )
        groups.append(grp)
        # per-column int8 scale-segment map (flat-slab order), one slot
        # segment at a time; padding columns inherit the LAST leaf's segment
        for j in range(g.n_slots):
            layer_slices.append((col + j * s_pad, col + (j + 1) * s_pad))
            layer_sizes.append(s)
            scale_cols = np.empty(s_pad, np.int64)
            for plan in float_plans:
                sid = plan.scale_seg0 + (j if plan.scale_per_slot else 0)
                scale_cols[plan.col0 : plan.col0 + plan.width] = sid
            if pad:
                scale_cols[s:] = scale_cols[s - 1]
            col_scale.append(scale_cols)
        col += grp.width

    if not groups:
        raise ValueError("template has no float leaves to pack")
    return SlabLayout(
        groups=tuple(groups),
        num_layers=partition.num_layers,
        D=col,
        dtype=jnp.dtype(dtype),
        layer_slices=tuple(layer_slices),
        layer_sizes=tuple(layer_sizes),
        n_tree_leaves=n_tree_leaves,
        col_scale_seg=np.concatenate(col_scale).astype(np.int32),
        n_scale_segs=n_scale,
        lane=lane,
    )


# ---------------------------------------------------------------------------
# codec fast paths on the regions
# ---------------------------------------------------------------------------


_LAYOUT_CACHE: dict = {}


def cached_slab_layout(
    partition: LayerPartition, template: PyTree, dtype=F32, lane: int = LANES
) -> SlabLayout:
    """Memoized :func:`build_slab_layout` keyed on the partition and the
    template's structure/shapes/dtypes — layout construction walks every leaf
    and builds (D,)-sized numpy maps, so callers that rebuild per trace (e.g.
    ``PermuteConsensus`` inside ``shard_map``) should come through here."""
    leaves, treedef = jax.tree.flatten(template)
    key = (
        partition,
        treedef,
        tuple((tuple(l.shape), str(jnp.dtype(l.dtype))) for l in leaves),
        str(jnp.dtype(dtype)),
        lane,
    )
    hit = _LAYOUT_CACHE.get(key)
    if hit is None:
        if len(_LAYOUT_CACHE) > 64:  # a handful of models per process
            _LAYOUT_CACHE.clear()
        hit = _LAYOUT_CACHE[key] = build_slab_layout(
            partition, template, dtype=dtype, lane=lane
        )
    return hit


def slab_codec_supported(codec) -> bool:
    """True when the codec has a slab fast path (the engines fall back to the
    per-leaf tree path otherwise)."""
    return codec is None or isinstance(
        codec, (IdentityCodec, CastCodec, Int8StochasticCodec, TopKCodec)
    )


def slab_template_supported(tree: PyTree) -> bool:
    """True when the slab hot path reproduces the tree oracle for this
    parameter tree: a top-level dict whose leaves are ALL floating point.
    Non-float leaves are excluded from the slab's distance statistics while
    the tree oracle casts them in, so the engines fall back to the per-leaf
    path rather than silently diverge."""
    if not isinstance(tree, dict):
        return False
    leaves = jax.tree.leaves(tree)
    return bool(leaves) and all(_is_float(l) for l in leaves)


def slab_init_state(codec, layout: SlabLayout) -> tuple:
    """Single-agent codec state in region form (``()`` for stateless codecs)."""
    if isinstance(codec, TopKCodec):
        return tuple(
            jnp.zeros((g.n_slots, g.s_pad), F32) for g in layout.groups
        )
    return ()


def _leaf_slices(grp: GroupPlan, region):
    """Static per-leaf column slices of one group region."""
    for plan in grp.float_leaves:
        yield plan, jax.lax.slice_in_dim(
            region, plan.col0, plan.col0 + plan.width, axis=-1
        )


def wire_out_axes(codec):
    """vmap ``out_axes`` that puts the agent axis where the slot-major
    regions expect it (axis 1) while keeping per-agent scale vectors
    agent-leading."""
    if isinstance(codec, Int8StochasticCodec):
        return SlabQuant(q=1, s=0)
    return 1


def _leaf_scale(plan: LeafPlan, grp: GroupPlan, s_seg: jax.Array):
    """One leaf's int8 scales, broadcastable against its ``(n_slots, *batch,
    width)`` region slice.  ``s_seg``: (*batch, n_scale_segs) in segment-id
    order.  Static slices only — no per-column gather."""
    n = grp.n_slots if plan.scale_per_slot else 1
    s = jax.lax.slice_in_dim(
        s_seg, plan.scale_seg0, plan.scale_seg0 + n, axis=-1
    )  # (*batch, n | 1)
    return jnp.moveaxis(s, -1, 0)[..., None]  # (n | 1, *batch, 1)


def slab_quant_scales(codec, layout: SlabLayout, regions: tuple) -> jax.Array:
    """Per-(leaf, slot) absmax int8 scales in segment-id order, batched over
    any agent axes of the slot-major regions: ``(*batch, n_scale_segs)`` f32.
    Same f32 max reductions as the tree codec — scales are bit-identical."""
    scales = []
    for grp, region in zip(layout.groups, regions):
        for plan, piece in _leaf_slices(grp, region):
            x = piece.astype(F32)  # (n_slots, *batch, width)
            if plan.scale_per_slot:
                absmax = jnp.moveaxis(jnp.max(jnp.abs(x), axis=-1), 0, -1)
            else:
                absmax = jnp.max(jnp.abs(x), axis=(0, -1))[..., None]
            scales.append(jnp.where(absmax > 0, absmax / codec.qmax, 1.0))
    return jnp.concatenate(scales, axis=-1)


def _pad_leaf_parts(grp: GroupPlan, parts: list, end: int, dtype) -> jax.Array:
    """Concatenate per-leaf wire slices back into a full (..., s_pad) region,
    zero-filling the lane padding."""
    pad = grp.s_pad - end
    if pad:
        ref = parts[-1]
        parts.append(jnp.zeros((*ref.shape[:-1], pad), dtype))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=-1)


def slab_encode(codec, layout: SlabLayout, regions: tuple, state, key):
    """Encode ONE agent's regions.  Returns ``(wire, new_state)``.

    Semantics (scale/threshold granularity, rng derivation, residual updates)
    are bit-identical to the tree codec's ``encode`` — see the per-codec notes
    in the module docstring.  This is the two-phase oracle (and the permute
    engine's per-shard path); the gather engine's round loop runs the
    natively-batched :func:`slab_encode_batched` instead of vmapping it.
    """
    if codec is None or isinstance(codec, IdentityCodec):
        return regions, state
    if isinstance(codec, CastCodec):
        return tuple(r.astype(codec.dtype) for r in regions), state
    if isinstance(codec, Int8StochasticCodec):
        if key is None:
            raise ValueError("int8 codec needs an rng key (stochastic rounding)")
        uniforms = layout.pack_uniforms(key)
        s_seg = slab_quant_scales(codec, layout, regions)  # (n_scale_segs,)
        qs = []
        for grp, region, u in zip(layout.groups, regions, uniforms):
            parts, end = [], 0
            for plan, piece in _leaf_slices(grp, region):
                up = jax.lax.slice_in_dim(
                    u, plan.col0, plan.col0 + plan.width, axis=-1
                )
                s = _leaf_scale(plan, grp, s_seg)
                q = jnp.clip(
                    jnp.floor(piece.astype(F32) / s + up),
                    -codec.qmax,
                    codec.qmax,
                )
                parts.append(q.astype(jnp.int8))
                end = plan.col0 + plan.width
            qs.append(_pad_leaf_parts(grp, parts, end, jnp.int8))
        return SlabQuant(q=tuple(qs), s=s_seg), state
    if isinstance(codec, TopKCodec):
        if state is None or (isinstance(state, tuple) and state == ()):
            state = slab_init_state(codec, layout)
        wire, new_state = [], []
        for grp, region, res in zip(layout.groups, regions, state):
            y = region.astype(F32) + res
            ay = jnp.abs(y)
            # per-leaf threshold via the tree codec's shared (subsampled)
            # rule: one threshold per leaf, scan slots included, ties all sent
            sent_parts = []
            prev_end = 0
            for plan, piece in _leaf_slices(grp, ay):
                thresh = topk_threshold(
                    piece.reshape(-1), codec.frac, codec.sample
                )
                ys = jax.lax.slice_in_dim(
                    y, plan.col0, plan.col0 + plan.width, axis=-1
                )
                mask = (piece >= thresh) & (piece > 0.0)
                sent_parts.append(jnp.where(mask, ys, 0.0))
                prev_end = plan.col0 + plan.width
            sent = _pad_leaf_parts(grp, sent_parts, prev_end, F32)
            wire.append(sent)
            new_state.append(y - sent)
        return tuple(wire), tuple(new_state)
    raise NotImplementedError(f"no slab fast path for codec {codec!r}")


def slab_wire_take(codec, wire, idx: jax.Array):
    """Gather agent rows of an ENCODED wire — the wire analogue of
    ``jnp.take(region, idx, axis=1)`` per region.  Feeding the result to
    :func:`slab_decode` reconstructs exactly ``take`` of the decoded slab
    (dequant is per-row), but the gather itself moves compact wire bytes —
    the sparse round's neighbour reads are 2x (bf16) / ~4x (int8) cheaper
    than gathering a materialized f32 slab."""
    if isinstance(wire, SlabQuant):
        return SlabQuant(
            q=tuple(jnp.take(q, idx, axis=1) for q in wire.q),
            s=jnp.take(wire.s, idx, axis=0),  # scales carry K on axis 0
        )
    return tuple(jnp.take(x, idx, axis=1) for x in wire)


def slab_decode(codec, layout: SlabLayout, wire) -> tuple:
    """f32 region reconstruction of an encoded wire (any leading batch):
    static per-leaf slices and broadcasts only, so XLA fuses the dequant into
    its consumers instead of materializing a (K, D) scale gather."""
    if codec is None or isinstance(codec, (IdentityCodec, TopKCodec)):
        return wire
    if isinstance(codec, CastCodec):
        return tuple(r.astype(F32) for r in wire)
    if isinstance(codec, Int8StochasticCodec):
        out = []
        for grp, q in zip(layout.groups, wire.q):
            parts, end = [], 0
            for plan, piece in _leaf_slices(grp, q):
                s = _leaf_scale(plan, grp, wire.s)
                parts.append(piece.astype(F32) * s)
                end = plan.col0 + plan.width
            out.append(_pad_leaf_parts(grp, parts, end, F32))
        return tuple(out)
    raise NotImplementedError(f"no slab fast path for codec {codec!r}")


# ---------------------------------------------------------------------------
# fused batched encode: the gather engine's coded-round hot path
# ---------------------------------------------------------------------------


def leaf_key_words(layout: SlabLayout, keys_K: jax.Array):
    """Per-(agent, leaf) counter-RNG key words ``(w0, w1)``, each
    ``(K, n_tree_leaves)`` uint32 — the batched form of the tree codec's
    per-leaf key split (``split(agent_key, n_tree_leaves)`` per agent)."""
    leaf_keys = jax.vmap(
        lambda k: jax.random.split(k, layout.n_tree_leaves)
    )(keys_K)
    return key_words(leaf_keys)


def _leaf_uniforms(plan: LeafPlan, grp: GroupPlan, w0, w1) -> jax.Array:
    """One leaf's counter uniforms in batched region layout ``(n_slots, K,
    width)``: the same (key word, element index) hash the tree codec draws,
    computed in place — no per-agent vmap, no packing pass."""
    idx = (
        jnp.arange(grp.n_slots, dtype=jnp.uint32)[:, None, None]
        * np.uint32(plan.width)
        + jnp.arange(plan.width, dtype=jnp.uint32)[None, None, :]
    )
    lw0 = w0[:, plan.flat_idx][None, :, None]  # (1, K, 1)
    lw1 = w1[:, plan.flat_idx][None, :, None]
    return uniform_from_words(lw0, lw1, idx)


def slab_encode_batched(
    codec, layout: SlabLayout, regions: tuple, state, keys_K
):
    """Encode ALL agents in one natively-batched pass over the slot-major
    ``(n_slots, K, s_pad)`` regions.  Returns ``(wire, new_state)``.

    Bit-identical to ``vmap(slab_encode)`` over the agent axis (and hence to
    the tree codec) — same scales, same counter uniforms, same thresholds,
    same EF residual — but with the agent axis left in place: no
    ``out_axes=1`` transposes, no per-agent uniform packing, no scale
    gathers.  ``keys_K``: the ``(K,)`` per-agent round keys
    (``fold_in(round_key, agent)``).
    """
    if codec is None or isinstance(codec, IdentityCodec):
        return regions, state
    if isinstance(codec, CastCodec):
        return tuple(r.astype(codec.dtype) for r in regions), state
    if isinstance(codec, Int8StochasticCodec):
        if keys_K is None:
            raise ValueError("int8 codec needs an rng key (stochastic rounding)")
        w0, w1 = leaf_key_words(layout, keys_K)
        s_seg = slab_quant_scales(codec, layout, regions)  # (K, n_segs)
        qs = []
        for grp, region in zip(layout.groups, regions):
            parts, end = [], 0
            for plan, piece in _leaf_slices(grp, region):
                u = _leaf_uniforms(plan, grp, w0, w1)
                s = _leaf_scale(plan, grp, s_seg)
                q = jnp.clip(
                    jnp.floor(piece.astype(F32) / s + u),
                    -codec.qmax,
                    codec.qmax,
                )
                parts.append(q.astype(jnp.int8))
                end = plan.col0 + plan.width
            qs.append(_pad_leaf_parts(grp, parts, end, jnp.int8))
        return SlabQuant(q=tuple(qs), s=s_seg), state
    if isinstance(codec, TopKCodec):
        K = regions[0].shape[1]
        if state is None or (isinstance(state, tuple) and state == ()):
            state = tuple(
                jnp.zeros((g.n_slots, K, g.s_pad), F32) for g in layout.groups
            )
        wire, new_state = [], []
        for grp, region, res in zip(layout.groups, regions, state):
            y = region.astype(F32) + res
            ay = jnp.abs(y)
            sent_parts, prev_end = [], 0
            for plan, piece in _leaf_slices(grp, ay):  # (n_slots, K, width)
                n_el = grp.n_slots * plan.width
                stride, k = _topk_sample_plan(n_el, codec.frac, codec.sample)
                # the SAME elements the tree rule samples (flat[::stride]),
                # addressed in (slot, column) coordinates
                ii = np.arange(0, n_el, stride)
                sub = piece[ii // plan.width, :, ii % plan.width]  # (m, K)
                thresh = jax.lax.top_k(sub.T, k)[0][..., -1]  # (K,)
                ys = jax.lax.slice_in_dim(
                    y, plan.col0, plan.col0 + plan.width, axis=-1
                )
                mask = (piece >= thresh[None, :, None]) & (piece > 0.0)
                sent_parts.append(jnp.where(mask, ys, 0.0))
                prev_end = plan.col0 + plan.width
            sent = _pad_leaf_parts(grp, sent_parts, prev_end, F32)
            wire.append(sent)
            new_state.append(y - sent)
        return tuple(wire), tuple(new_state)
    raise NotImplementedError(f"no slab fast path for codec {codec!r}")
