"""DRT diffusion combination weights (paper §II, eqs. 8-14).

Everything runs in log space: with L >= 30 layers the raw product
``2^(L+1) * prod_p (1 + d2_p / (n2_p + kappa))`` overflows float32, so we carry
``log( a~ )`` and normalize with a shifted exponential (softmax-style).  This
is mathematically identical to the paper's construction — the normalization
(12) is scale invariant per (k, p) column.

Index conventions (matching the paper):
  d2[p, l, k] = || w_k^(p) - w_l^(p) ||^2   (symmetric in l, k)
  n2[p, l]    = || w_l^(p) ||^2
  A[p, l, k]  = weight that agent k applies to psi_l for layer p.
Columns (fixed k, summing over l) are stochastic: sum_l A[p, l, k] = 1.
"""
from __future__ import annotations

import dataclasses
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np

_NEG_INF = -1e30

WeightMode = Literal["paper", "exact_grad"]


@dataclasses.dataclass(frozen=True)
class DRTConfig:
    """Hyper-parameters of the DRT mixing-matrix construction.

    ``N``: clip factor of eq. (13); the paper's experiments use N = 2K (set
    N=None to get that default).  Guarantees min positive entry
    >= 1/((K-1)N+1) (Lemma 1).
    ``kappa``: numerical-stability constant of eq. (10).
    ``weight_mode``: 'paper' implements eq. (14) exactly as printed
    (denominator d2_{p*} + kappa); 'exact_grad' uses the true gradient of the
    penalty in (10) (denominator (n2 + kappa + d2_{p*})).
    """

    N: float | None = None
    kappa: float = 1e-6
    weight_mode: WeightMode = "paper"

    def resolve_N(self, K: int) -> float:
        return float(2 * K) if self.N is None else float(self.N)


def drt_log_unnormalized(
    d2: jax.Array,
    n2: jax.Array,
    C: jax.Array,
    kappa: float,
    weight_mode: WeightMode = "paper",
) -> jax.Array:
    """log a~_{lk}^{(p)} for l != k (eq. 14), -inf on non-edges and diagonal.

    d2: (L, K, K), n2: (L, K), C: (K, K) with C[l, k] > 0 iff l in N_k.
    Returns (L, K, K).
    """
    L = d2.shape[0]
    d2 = d2.astype(jnp.float32)
    n2 = n2.astype(jnp.float32)
    # ratio[p, l, k] = d2[p, l, k] / (||w_l^(p)||^2 + kappa)
    ratio = d2 / (n2[:, :, None] + kappa)
    # log prod_p (1 + ratio) + (L+1) log 2, per (l, k)
    log_prod = jnp.sum(jnp.log1p(ratio), axis=0) + (L + 1) * jnp.log(2.0)  # (K, K)
    if weight_mode == "paper":
        log_denom = jnp.log(d2 + kappa)  # (L, K, K)
    elif weight_mode == "exact_grad":
        # d/dw_k of the (10) penalty pulls a 1/((1 + ratio_{p*}) (n2 + kappa))
        # factor = 1 / (n2 + kappa + d2_{p*}).
        log_denom = jnp.log(n2[:, :, None] + kappa + d2)
    else:
        raise ValueError(f"unknown weight_mode {weight_mode!r}")
    log_a = log_prod[None, :, :] - log_denom + jnp.log(C)[None, :, :]
    K = d2.shape[1]
    eye = jnp.eye(K, dtype=bool)
    edge_mask = (C > 0) & ~eye
    return jnp.where(edge_mask[None], log_a, _NEG_INF)


def drt_clip_and_self(
    log_a: jax.Array,
    C: jax.Array,
    N: float,
) -> jax.Array:
    """Eq. (13): clip off-diagonal entries at N x (smallest positive entry of
    the column), then set the self weight to c_kk/(n_k - 1) x sum of the rest.

    All in log space.  Returns (L, K, K) log a~ including the diagonal.
    """
    K = log_a.shape[1]
    eye = jnp.eye(K, dtype=bool)
    edge_mask = ((C > 0) & ~eye)[None]  # (1, K, K)
    # smallest positive entry per (p, k) column (over l), i.e. min over edges
    log_min = jnp.min(jnp.where(edge_mask, log_a, -_NEG_INF), axis=1, keepdims=True)
    log_clipped = jnp.minimum(log_a, jnp.log(N) + log_min)
    log_clipped = jnp.where(edge_mask, log_clipped, _NEG_INF)
    # self weight: a~_kk = c_kk / (n_k - 1) * sum_{l != k} a~_lk  (logsumexp)
    n_k = jnp.sum(C > 0, axis=0).astype(jnp.float32)  # includes self loop
    c_kk = jnp.diagonal(C).astype(jnp.float32)
    denom = jnp.maximum(n_k - 1.0, 1.0)
    log_sum = jax.nn.logsumexp(jnp.where(edge_mask, log_clipped, _NEG_INF), axis=1)
    log_self = jnp.log(c_kk / denom)[None, :] + log_sum  # (L, K)
    log_full = jnp.where(
        eye[None], jnp.broadcast_to(log_self[:, None, :], log_clipped.shape), log_clipped
    )
    return log_full


def drt_normalize(log_a: jax.Array, C: jax.Array) -> jax.Array:
    """Eq. (12): column normalization, shifted-exp for stability."""
    K = log_a.shape[1]
    mask = (C > 0)[None]
    masked = jnp.where(mask, log_a, _NEG_INF)
    m = jnp.max(masked, axis=1, keepdims=True)
    ex = jnp.where(mask, jnp.exp(masked - m), 0.0)
    return ex / jnp.sum(ex, axis=1, keepdims=True)


def drt_mixing_matrices(
    d2: jax.Array,
    n2: jax.Array,
    C: jax.Array,
    cfg: DRTConfig,
) -> jax.Array:
    """Full eqs. (12)-(14) pipeline: distances -> A_i^(p).

    Returns A of shape (L, K, K), column-stochastic over axis 1, supported on
    the graph of C (Lemma 1 compatibility).
    """
    K = d2.shape[1]
    N = cfg.resolve_N(K)
    C = jnp.asarray(C, jnp.float32)
    log_a = drt_log_unnormalized(d2, n2, C, cfg.kappa, cfg.weight_mode)
    log_full = drt_clip_and_self(log_a, C, N)
    return drt_normalize(log_full, C)


def drt_weights_from_params(partition, params_K, C, cfg: DRTConfig) -> jax.Array:
    """Convenience: agent-stacked params -> per-layer mixing matrices."""
    d2, n2 = partition.pairwise_sq_dists(params_K)
    return drt_mixing_matrices(d2, n2, C, cfg)


# ---------------------------------------------------------------------------
# Sparse (edge-list) factorization of eqs. (12)-(14)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class EdgeSegments:
    """Gathers by edge endpoint and per-destination reductions over a padded
    directed edge list of ``K`` agents.

    ``dense=False`` spells them as XLA gathers and scatter-reduces: O(E),
    what the jnp edge path runs.  ``dense=True`` spells the same operations
    as one-hot matmuls and masked reductions over (K, E) incidence masks:
    O(K E), the only form Mosaic lowers inside a kernel, where K is the
    small in-kernel agent count.  Gathers and min/max agree bit for bit;
    sums agree up to the order of addition at in-degree > 2.
    """

    src: jax.Array
    dst: jax.Array
    K: int
    dense: bool = False

    def _onehot(self, idx):  # (K, E) f32: [k, e] = (idx[e] == k)
        E = idx.shape[-1]
        iota = jax.lax.broadcasted_iota(jnp.int32, (self.K, E), 0)
        return (iota == idx.reshape(1, E)).astype(jnp.float32)

    def _gather(self, x: jax.Array, idx: jax.Array) -> jax.Array:
        """``x[..., idx]``: (..., K) -> (..., E)."""
        if not self.dense:
            return x[..., idx]
        x2 = x.reshape(-1, self.K).astype(jnp.float32)
        out = jnp.dot(
            x2, self._onehot(idx),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
        return out.reshape(*x.shape[:-1], idx.shape[-1])

    def at_src(self, x):
        return self._gather(x, self.src)

    def at_dst(self, x):
        return self._gather(x, self.dst)

    def _reduce(self, v, init, op):
        # v (..., E) -> (..., K), init where no edge lands
        if not self.dense:
            out = jnp.full((*v.shape[:-1], self.K), init, v.dtype)
            return getattr(out.at[..., self.dst], op)(v)
        onehot = self._onehot(self.dst)
        if op == "add":
            v2 = v.reshape(-1, v.shape[-1]).astype(jnp.float32)
            out = jax.lax.dot_general(
                v2, onehot, (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32,
            ) + init
            return out.reshape(*v.shape[:-1], self.K)
        masked = jnp.where(onehot > 0.0, v[..., None, :], init)
        return jnp.min(masked, -1) if op == "min" else jnp.max(masked, -1)

    def segment_sum(self, v, init=0.0):
        return self._reduce(v, init, "add")

    def segment_min(self, v, init):
        return self._reduce(v, init, "min")

    def segment_max(self, v, init):
        return self._reduce(v, init, "max")


def drt_edge_mixing(
    d2_e: jax.Array,
    n2: jax.Array,
    src: jax.Array,
    dst: jax.Array,
    w: jax.Array,
    cfg: DRTConfig,
    K: int,
    dense: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Eqs. (12)-(14) on a padded directed edge list — O(|E| L) not O(K^2 L).

    The edge-list factorization of :func:`drt_mixing_matrices`: instead of
    materializing (L, K, K) log weights, every per-column reduction of the
    dense pipeline (clip min, self-weight logsumexp, normalization sum)
    becomes a segment scatter-reduce keyed on ``dst``.  Numerically (not
    bit-) identical to the dense construction on the realized graph —
    shifted exponentials accumulate in a different order.

    d2_e: (L, E) squared per-layer distances ``||w_src - w_dst||^2`` per edge;
    n2: (L, K) squared norms; src/dst/w: (E,) padded directed edge list
    (``w == 0`` marks padding; ``w`` is the off-diagonal C entry).
    Returns ``(A_self (L, K), A_e (L, E))`` — column-stochastic:
    ``A_self[:, k] + sum_{e: dst[e]==k} A_e[:, e] == 1``; an isolated agent
    gets ``A_self = 1`` (the identity column), matching the dense path.
    ``dense=True`` runs the gathers and segment reductions as
    :class:`EdgeSegments` masked reductions (inside a Pallas kernel).
    """
    L = d2_e.shape[0]
    N = cfg.resolve_N(K)
    seg = EdgeSegments(src, dst, K, dense)
    d2_e = d2_e.astype(jnp.float32)
    n2 = n2.astype(jnp.float32)
    w = jnp.asarray(w, jnp.float32)
    mask = w > 0.0

    # eq. (14) per edge: ratio against the SOURCE agent's layer norms
    n2_src = seg.at_src(n2)  # (L, E)
    ratio = d2_e / (n2_src + cfg.kappa)
    log_prod = jnp.sum(jnp.log1p(ratio), axis=0) + (L + 1) * jnp.log(2.0)  # (E,)
    if cfg.weight_mode == "paper":
        log_denom = jnp.log(d2_e + cfg.kappa)
    elif cfg.weight_mode == "exact_grad":
        log_denom = jnp.log(n2_src + cfg.kappa + d2_e)
    else:
        raise ValueError(f"unknown weight_mode {cfg.weight_mode!r}")
    log_w = jnp.log(jnp.where(mask, w, 1.0))
    log_a = log_prod[None, :] - log_denom + log_w[None, :]  # (L, E)
    log_a = jnp.where(mask[None], log_a, _NEG_INF)

    # eq. (13) clip: min positive entry per (p, dst) column via segment-min
    log_min = seg.segment_min(jnp.where(mask[None], log_a, -_NEG_INF), -_NEG_INF)
    log_clipped = jnp.minimum(log_a, jnp.log(N) + seg.at_dst(log_min))
    log_clipped = jnp.where(mask[None], log_clipped, _NEG_INF)

    # self weight: a~_kk = c_kk/(n_k - 1) * sum over incoming edges
    # (two-pass segment logsumexp: scatter-max shift, then scatter-sum)
    n_k = 1.0 + seg.segment_sum(mask.astype(jnp.float32))
    denom = jnp.maximum(n_k - 1.0, 1.0)
    m1 = seg.segment_max(jnp.where(mask[None], log_clipped, _NEG_INF), _NEG_INF)
    sumexp = seg.segment_sum(
        jnp.where(mask[None], jnp.exp(log_clipped - seg.at_dst(m1)), 0.0)
    )
    log_sum = jnp.where(sumexp > 0.0, m1 + jnp.log(jnp.maximum(sumexp, 1e-30)),
                        _NEG_INF)
    log_self = -jnp.log(denom)[None, :] + log_sum  # c_kk == 1 on support

    # eq. (12) normalize: shifted exp over {self} u {incoming edges}
    m = jnp.maximum(log_self, m1)
    a_self = jnp.exp(log_self - m)
    a_e = jnp.where(mask[None], jnp.exp(log_clipped - seg.at_dst(m)), 0.0)
    colsum = a_self + seg.segment_sum(a_e)
    return a_self / colsum, a_e / seg.at_dst(colsum)


def edge_mixing_dense(
    A_self: jax.Array,
    A_e: jax.Array,
    src: jax.Array,
    dst: jax.Array,
    w: jax.Array,
    K: int,
) -> jax.Array:
    """Densify edge-factorized mixing weights into (L, K, K) — the oracle /
    telemetry bridge (A[p, l, k] = weight agent k applies to psi_l)."""
    mask = jnp.asarray(w, jnp.float32) > 0.0
    L = A_self.shape[0]
    A = jnp.zeros((L, K, K), A_self.dtype)
    A = A.at[:, src, dst].add(jnp.where(mask[None], A_e, 0.0))
    idx = jnp.arange(K)
    return A.at[:, idx, idx].set(A_self)


# ---------------------------------------------------------------------------
# The DRT distance itself (eqs. 8, 9) — used by tests / analysis
# ---------------------------------------------------------------------------


def drt_distance(partition, w_a, w_b, kappa: float = 0.0) -> jax.Array:
    """Linear DRT bound, eq. (8): prod_p (1 + ||da_p|| / ||a_p||) - 1."""
    diff = jax.tree.map(jnp.subtract, w_a, w_b)
    d = jnp.sqrt(partition.sq_norms(diff))
    n = jnp.sqrt(partition.sq_norms(w_b))
    return jnp.exp(jnp.sum(jnp.log1p(d / (n + kappa)))) - 1.0


def drt_sq_bound(partition, w_a, w_b, kappa: float = 0.0) -> jax.Array:
    """Quadratic DRT bound, eq. (9): 2^(L+1) prod_p (1 + d2/n2) + 2.

    Computed in log space, then exponentiated (may be inf for huge L — that is
    the bound's value, not an implementation error).
    """
    diff = jax.tree.map(jnp.subtract, w_a, w_b)
    d2 = partition.sq_norms(diff)
    n2 = partition.sq_norms(w_b)
    L = partition.num_layers
    log_bound = (L + 1) * jnp.log(2.0) + jnp.sum(jnp.log1p(d2 / (n2 + kappa)))
    return jnp.exp(log_bound) + 2.0


def metropolis_layered(A: np.ndarray, L: int) -> jax.Array:
    """Broadcast a static (K, K) mixing matrix to (L, K, K) for the combine."""
    return jnp.broadcast_to(jnp.asarray(A, jnp.float32), (L, *A.shape))
