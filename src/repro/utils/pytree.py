"""Pytree utilities and the DRT layer partition.

The DRT penalty (paper eq. 10) is a product over *layers* p = 1..L.  In this
framework a model's parameters are a nested dict whose top-level keys are either

  * plain groups   -- e.g. ``embed``, ``final_norm``, ``lm_head``: one DRT layer
  * stacked groups -- e.g. ``blocks``: every leaf carries a leading
    ``n_layers`` axis produced by scan-over-layers; each scan slot is one DRT
    layer.

``LayerPartition`` assigns a contiguous layer index range to each top-level key
and provides the per-layer reductions (squared norms, pairwise squared
distances via a Gram-matrix trick) and the per-layer weighted combine used by
both classical diffusion and DRT diffusion.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any

# ---------------------------------------------------------------------------
# elementary tree arithmetic
# ---------------------------------------------------------------------------


def tree_add(a: PyTree, b: PyTree) -> PyTree:
    return jax.tree.map(jnp.add, a, b)


def tree_sub(a: PyTree, b: PyTree) -> PyTree:
    return jax.tree.map(jnp.subtract, a, b)


def tree_scale(s, a: PyTree) -> PyTree:
    return jax.tree.map(lambda x: s * x, a)


def tree_dot(a: PyTree, b: PyTree) -> jax.Array:
    parts = jax.tree.map(lambda x, y: jnp.vdot(x, y), a, b)
    return jnp.sum(jnp.asarray(jax.tree.leaves(parts)))


def tree_sq_norm(a: PyTree) -> jax.Array:
    parts = jax.tree.map(lambda x: jnp.sum(jnp.square(x)), a)
    leaves = jax.tree.leaves(parts)
    return jnp.sum(jnp.stack([jnp.asarray(l, jnp.float32) for l in leaves]))


def tree_cast(a: PyTree, dtype) -> PyTree:
    return jax.tree.map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x, a
    )


def tree_zeros_like(a: PyTree) -> PyTree:
    return jax.tree.map(jnp.zeros_like, a)


def tree_size(a: PyTree) -> int:
    return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(a))


def tree_bytes(a: PyTree) -> int:
    return sum(int(np.prod(l.shape)) * l.dtype.itemsize for l in jax.tree.leaves(a))


# ---------------------------------------------------------------------------
# DRT layer partition
# ---------------------------------------------------------------------------


# The Gram trick d2 = n2_l + n2_k - 2 G_lk on raw iterates keeps only about
# eps * (n2_l + n2_k) of absolute precision.  Below this fraction of
# n2_l + n2_k the raw value may be mostly rounding error (after a local step
# from a common start it is), and the trick on the iterates less agent 0's,
# whose error scales with the distances instead, takes its place.
RAW_D2_MIN_FRACTION = 1e-1


def sq_dists_from_grams(raw: jax.Array, centred: jax.Array):
    """Per-layer ``(d2 (..., K, K), n2 (..., K))`` from the agents' raw
    Gram matrices and the Gram matrices of their iterates less agent 0's
    (see ``RAW_D2_MIN_FRACTION``).  Diagonals are read by an identity mask,
    so the same code runs inside a Pallas kernel."""
    eye = jnp.eye(raw.shape[-1], dtype=raw.dtype)
    n2 = jnp.sum(raw * eye, axis=-1)
    d2 = jnp.maximum(n2[..., :, None] + n2[..., None, :] - 2.0 * raw, 0.0)
    c2 = jnp.sum(centred * eye, axis=-1)
    d2c = jnp.maximum(c2[..., :, None] + c2[..., None, :] - 2.0 * centred, 0.0)
    floor = RAW_D2_MIN_FRACTION * (n2[..., :, None] + n2[..., None, :])
    return jnp.where(d2 > floor, d2, d2c), n2


@dataclasses.dataclass(frozen=True)
class GroupSpec:
    key: str
    stacked: bool
    n_slots: int
    offset: int  # starting DRT layer index


@dataclasses.dataclass(frozen=True)
class LayerPartition:
    """Maps top-level parameter groups to DRT layer indices."""

    groups: tuple[GroupSpec, ...]
    num_layers: int

    # -- construction -------------------------------------------------------

    @staticmethod
    def build(params: PyTree, stacked_keys: Sequence[str] = ()) -> "LayerPartition":
        """Build a partition from a parameter template.

        ``stacked_keys``: top-level keys whose leaves carry a leading
        ``n_layers`` axis.  Keys ending in ``blocks`` are treated as stacked
        by default.
        """
        if not isinstance(params, dict):
            raise TypeError("params template must be a top-level dict")
        groups = []
        offset = 0
        for key in params.keys():
            sub = params[key]
            leaves = jax.tree.leaves(sub)
            if not leaves:
                continue
            stacked = key in stacked_keys or key.endswith("blocks")
            if stacked:
                n = int(leaves[0].shape[0])
                for l in leaves:
                    if int(l.shape[0]) != n:
                        raise ValueError(
                            f"stacked group {key!r}: inconsistent leading axis "
                            f"{l.shape[0]} != {n}"
                        )
            else:
                n = 1
            groups.append(GroupSpec(key=key, stacked=stacked, n_slots=n, offset=offset))
            offset += n
        return LayerPartition(groups=tuple(groups), num_layers=offset)

    # -- flat-slab hot path ---------------------------------------------------

    def slab_layout(self, template: PyTree, dtype=jnp.float32):
        """Static flat-slab packing plan for this partition (the consensus
        hot path packs once per round-set and runs segment reductions on the
        slab; see :mod:`repro.core.packing`).  ``template``: single-agent tree
        of arrays or ShapeDtypeStructs."""
        from repro.core.packing import build_slab_layout  # lazy: avoid cycle

        return build_slab_layout(self, template, dtype=dtype)

    # -- per-layer reductions (reference oracle for the slab path) ------------

    def sq_norms(self, tree: PyTree) -> jax.Array:
        """Per-DRT-layer squared norms: returns ``(L,)`` float32."""
        out = []
        for g in self.groups:
            leaves = jax.tree.leaves(tree[g.key])
            if g.stacked:
                acc = jnp.zeros((g.n_slots,), jnp.float32)
                for l in leaves:
                    acc = acc + jnp.sum(
                        jnp.square(l.astype(jnp.float32)),
                        axis=tuple(range(1, l.ndim)),
                    )
                out.append(acc)
            else:
                acc = jnp.zeros((), jnp.float32)
                for l in leaves:
                    acc = acc + jnp.sum(jnp.square(l.astype(jnp.float32)))
                out.append(acc[None])
        return jnp.concatenate(out)

    def agent_sq_norms(self, tree_K: PyTree) -> jax.Array:
        """Per-agent per-layer squared norms for an agent-stacked tree.

        ``tree_K``: every leaf has leading agent axis K.  Returns ``(L, K)``.
        """
        out = []
        for g in self.groups:
            leaves = jax.tree.leaves(tree_K[g.key])
            if g.stacked:
                # leaf (K, n, ...) -> (n, K)
                acc = None
                for l in leaves:
                    s = jnp.sum(
                        jnp.square(l.astype(jnp.float32)),
                        axis=tuple(range(2, l.ndim)),
                    ).T  # (n, K)
                    acc = s if acc is None else acc + s
                out.append(acc)
            else:
                acc = None
                for l in leaves:
                    s = jnp.sum(
                        jnp.square(l.astype(jnp.float32)),
                        axis=tuple(range(1, l.ndim)),
                    )  # (K,)
                    acc = s if acc is None else acc + s
                out.append(acc[None, :])
        return jnp.concatenate(out, axis=0)

    def pairwise_sq_dists(self, tree_K: PyTree) -> tuple[jax.Array, jax.Array]:
        """All-pairs per-layer squared distances via the Gram trick.

        d2[p, l, k] = || w_k^(p) - w_l^(p) ||^2 ,  n2[p, l] = || w_l^(p) ||^2.

        Uses  d2 = n2_k + n2_l - 2 <w_k, w_l>  so the inner product runs on the
        MXU as a (K, D) x (D, K) matmul per group instead of K^2 elementwise
        differences; where that loses d2 to rounding, the same trick on the
        iterates less agent 0's (:func:`sq_dists_from_grams`).

        Returns ``(d2 (L,K,K), n2 (L,K))``.
        """
        # NOTE: einsums run on the leaves' native dtype with f32 accumulation
        # (preferred_element_type) — materializing f32 *casts* of the operands
        # would double HBM traffic and force f32 all-gathers for bf16 models
        # (measured: 2.3TB/step f32 copies on kimi-k2; see EXPERIMENTS §Perf).
        grams, centred = [], []
        for g in self.groups:
            leaves = jax.tree.leaves(tree_K[g.key])
            acc = cacc = None
            for l in leaves:
                K = l.shape[0]
                flat = l.reshape(K, l.shape[1], -1) if g.stacked else l.reshape(K, -1)
                spec = "knd,jnd->nkj" if g.stacked else "kd,jd->kj"
                gm = jnp.einsum(spec, flat, flat, preferred_element_type=jnp.float32)
                # centred in f32: a narrow dtype would round the differences
                cen = flat.astype(jnp.float32) - flat[:1].astype(jnp.float32)
                cm = jnp.einsum(spec, cen, cen, preferred_element_type=jnp.float32)
                acc = gm if acc is None else acc + gm
                cacc = cm if cacc is None else cacc + cm
            grams.append(acc if g.stacked else acc[None])  # (n, K, K)
            centred.append(cacc if g.stacked else cacc[None])
        return sq_dists_from_grams(
            jnp.concatenate(grams, axis=0), jnp.concatenate(centred, axis=0)
        )

    # -- per-layer weighted combine ------------------------------------------

    def combine(self, A: jax.Array, tree_K: PyTree) -> PyTree:
        """Apply the per-layer mixing matrices.

        ``A``: (L, K, K), column-stochastic over axis 1:
               new_k^(p) = sum_l A[p, l, k] psi_l^(p).
        ``tree_K``: agent-stacked parameter tree (leading K per leaf).
        """
        new = {}
        for g in self.groups:
            sub = tree_K[g.key]
            if g.stacked:
                A_g = A[g.offset : g.offset + g.n_slots]  # (n, K, K)

                def comb_stacked(l, A_g=A_g):
                    out = jnp.einsum(
                        "jlk,lj...->kj...", A_g.astype(jnp.float32), l,
                        preferred_element_type=jnp.float32,
                    )
                    return out.astype(l.dtype)

                new[g.key] = jax.tree.map(comb_stacked, sub)
            else:
                A_g = A[g.offset]  # (K, K)

                def comb(l, A_g=A_g):
                    out = jnp.einsum(
                        "lk,l...->k...", A_g.astype(jnp.float32), l,
                        preferred_element_type=jnp.float32,
                    )
                    return out.astype(l.dtype)

                new[g.key] = jax.tree.map(comb, sub)
        # preserve any empty groups verbatim
        for key in tree_K:
            if key not in new:
                new[key] = tree_K[key]
        return new


    def scale_by_layer(self, weights: jax.Array, tree: PyTree) -> PyTree:
        """Multiply each DRT layer group by a per-layer scalar.

        ``weights``: (L,).  ``tree``: a single agent's parameter tree (no
        leading K).  Used by the neighbour-exchange (ppermute) combine, where
        each agent applies its own column of A locally.
        """
        new = {}
        for g in self.groups:
            sub = tree[g.key]
            if g.stacked:
                w = weights[g.offset : g.offset + g.n_slots]

                def scale_stacked(l, w=w):
                    wb = w.reshape((g.n_slots,) + (1,) * (l.ndim - 1))
                    return (l.astype(jnp.float32) * wb).astype(l.dtype)

                new[g.key] = jax.tree.map(scale_stacked, sub)
            else:
                w = weights[g.offset]
                new[g.key] = jax.tree.map(
                    lambda l, w=w: (l.astype(jnp.float32) * w).astype(l.dtype), sub
                )
        for key in tree:
            if key not in new:
                new[key] = tree[key]
        return new


def layer_partition_fn(stacked_keys: Sequence[str] = ()) -> Callable[[PyTree], LayerPartition]:
    def fn(params: PyTree) -> LayerPartition:
        return LayerPartition.build(params, stacked_keys=stacked_keys)

    return fn
