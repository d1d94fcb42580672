"""Consensus (combine-step) engines.

Two interchangeable implementations of the combination step (3b)/(11):

* ``gather_consensus_step`` — the *paper-faithful baseline* and the reference
  oracle: operate per leaf on the globally agent-stacked tree; under pjit with
  the agent axis sharded over the mesh ``data`` axis this lowers to an
  all-gather of the full parameter set plus a masked per-layer einsum.
  Collective bytes scale with K.

* ``PermuteConsensus`` — the *beyond-paper optimized* engine: for structured
  topologies (ring / hypercube / torus2d / chain) the neighbour exchange is a
  sequence of ``lax.ppermute`` shifts inside ``shard_map``; each agent receives
  exactly its n_k neighbours, computes the DRT statistics locally, and applies
  its own column of A.  Collective bytes scale with n_k instead of K.

Both compute identical mixing matrices (tested against each other).

Hot path: the flat slab
-----------------------
The production path for BOTH engines is the flat-slab representation
(:mod:`repro.core.packing`): the agent-stacked tree is packed ONCE into a
contiguous ``(K, D)`` slab before the round loop, every round's distance
statistics and weighted combine run as per-group segment matmuls on the slab
(plus slab-native codec encode/decode), and the tree is unpacked once after
the last round — ``gather_consensus_rounds`` for the gather engine,
``PermuteConsensus(..., rounds=n)`` for the neighbour-exchange engine.  The
per-leaf tree walk survives as the reference oracle (``path="tree"``) and as
the automatic fallback for codecs without a slab fast path.

One-dispatch round-sets: every per-round loop in
``gather_consensus_rounds`` (the exact Gram recurrence, the coded slab
rounds and the per-leaf tree oracle) is a single ``lax.scan`` over the
``(rounds, K, K)`` mixing stacks, so the trace/compile cost of a round-set
is O(1) in ``rounds``; ``unroll=True`` keeps the Python-loop form as a
bit-identical parity oracle.

Fused coded rounds: a coded round's slab side (encode, decode, distance
stats, combine, self term) runs natively batched over the agent axis
(``packing.slab_encode_batched`` — no per-agent ``vmap`` transposes, no
materialized uniform fields, counter-based rounding RNG, subsampled top-k
thresholds), with the two-phase per-agent encode kept as the wire
bit-parity oracle.

``use_kernels=True`` swaps the slab inner loops for the Pallas kernels from
``repro.kernels``: every CODED round is ONE ``slab_encode_combine`` launch
(in-kernel RNG + scale reconstruction + per-layer Gram accumulation +
in-kernel DRT mixing math + combine + full-precision self term — the wire
and decoded slabs never hit HBM), the exact path makes one
``slab_combine`` launch per group per round-SET, on the regions as they
are, and the permute engine uses
``slab_quant_encode`` / ``slab_source_combine`` with ``drt_dist`` for its
neighbour statistics; on CPU they execute in interpret mode and are
parity-tested against the jnp slab path and the per-slot kernel references.

Everything that crosses the agent boundary goes through a ``repro.comm``
:class:`~repro.comm.WireCodec`: each agent encodes what it publishes once per
round, the wire (tree or slab) moves through the collective, and receivers
decode.  The DRT distance statistics are computed between *decoded* views on
both engines (so the mixing matrices agree codec-for-codec), while each
agent's own combine contribution stays full precision:

    w_k = A_kk * psi_k(f32)  +  sum_{l != k} A_lk * decode(encode(psi_l)).

Round-driving entry points (the trainer, ``gather_consensus_rounds``, the
engine's ``rounds=`` loop) derive the round-r stochastic-codec key as
``fold_in(rng, r)`` and the per-agent key as ``fold_in(round_key, agent)``;
the single-round oracle ``gather_consensus_step`` takes the already-folded
round key.

The legacy ``exchange_dtype=bf16`` argument is a deprecated alias for the
``bf16`` cast codec.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm import CastCodec, IdentityCodec, WireCodec, init_comm_state, make_codec
from repro.comm import collective_bytes_per_step as _codec_bytes_per_step
from repro.core import drt as drt_mod
from repro.core import packing
from repro.core.drt import DRTConfig
from repro.core.dynamic import EdgeStacks, csr_from_edges, metropolis_edge_weights
from repro.core.topology import Topology
# submodule imports (not the repro.faults package root): models/robust have no
# repro.core dependencies, so the consensus <-> faults import graph stays acyclic
from repro.faults import models as faults_models
from repro.faults import robust as faults_robust
from repro.obs import metrics as obs_metrics
from repro.obs import profiling as obs_profiling
from repro.obs.metrics import ConsensusMetrics, ObsConfig
from repro.utils.pytree import LayerPartition

Algorithm = Literal["drt", "classical"]
ConsensusPath = Literal["slab", "tree", "edge"]


def _resolve_codec(codec, exchange_dtype) -> "WireCodec | None":
    """Fold the deprecated ``exchange_dtype`` argument into the codec API."""
    if exchange_dtype is not None:
        if codec is not None:
            raise ValueError("pass either codec or (deprecated) exchange_dtype, not both")
        warnings.warn(
            "exchange_dtype is deprecated; pass codec='bf16' (or a WireCodec) instead",
            DeprecationWarning,
            stacklevel=3,
        )
        return CastCodec(dtype=exchange_dtype, name=str(jnp.dtype(exchange_dtype)))
    if codec is None:
        return None
    return make_codec(codec)


def _require_rng(codec: WireCodec, rng):
    """Stochastic codecs must get a fresh key per round — silently reusing a
    constant would turn the unbiased rounding noise into deterministic bias."""
    if rng is None:
        if getattr(codec, "needs_rng", False):
            raise ValueError(
                f"codec {codec.name!r} is stochastic; pass rng= (a fresh key "
                "per consensus round)"
            )
        return jax.random.key(0)  # deterministic codecs ignore the key
    return rng


def _agent_keys(rng, K: int) -> jax.Array:
    """Per-agent rng keys via fold_in — the SAME derivation the permute
    engine applies with its shard index, so stochastic codecs produce
    bit-identical wire slabs/trees on both engines."""
    return jax.vmap(lambda i: jax.random.fold_in(rng, i))(jnp.arange(K))


def _template_sds(psi_K):
    """Single-agent ShapeDtypeStruct template from an agent-stacked tree."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), psi_K
    )


def _round_stack(mat, rounds: int, name: str):
    """Normalize a mixing structure to a ``(rounds, K, K)`` stack.

    ``mat`` may be a static ``(K, K)`` matrix (broadcast — every round reads
    bit-identical values, so the static path stays bit-identical to the
    pre-stack behavior) or an actual per-round stack from a
    :class:`~repro.core.dynamic.TopologySchedule`.  ``None`` passes through
    (classical-only ``metropolis``).  The stacked form is what the scanned
    round-set consumes as its ``lax.scan`` inputs.
    """
    if mat is None:
        return None
    if mat.ndim == 2:
        return jnp.broadcast_to(mat, (rounds, *mat.shape))
    if mat.ndim == 3:
        if mat.shape[0] != rounds:
            raise ValueError(
                f"per-round {name} stack has {mat.shape[0]} rounds, "
                f"round-set runs {rounds}"
            )
        return mat
    raise ValueError(f"{name} must be (K, K) or (rounds, K, K), got {mat.shape}")


def _scan_rounds(body, carry, xs, rounds: int, unroll: bool):
    """Drive ``rounds`` iterations of ``body`` (a ``lax.scan``-shaped step).

    The default is ONE ``lax.scan`` over the per-round inputs, so the
    round-set traces and compiles O(1) in ``rounds``.  ``unroll=True`` runs
    the SAME body as a Python loop — the trace-time oracle the scanned path
    is parity-tested against (bit-identical by construction: each iteration
    executes the same ops on the same values).  A single round skips the
    scan machinery outright; the per-step production cadence pays no loop
    overhead.

    Returns ``(carry, ys)`` like ``lax.scan``: a body emitting per-round
    outputs (telemetry) gets them stacked along a leading ``(rounds,)`` axis
    on the unrolled path too; a body emitting ``None`` ys returns ``None``.
    """
    if unroll or rounds == 1:
        ys = []
        for r in range(rounds):
            carry, y = body(carry, jax.tree.map(lambda x: x[r], xs))
            ys.append(y)
        if ys[0] is None:
            return carry, None
        return carry, jax.tree.map(lambda *a: jnp.stack(a), *ys)
    return jax.lax.scan(body, carry, xs)


def _tree_net_disagreement(psi_K) -> jax.Array:
    """Network disagreement ``mean_k ||x_k - x_bar||^2`` on an agent-stacked
    tree — the adaptive round budget's gate signal on the tree oracle path.
    Deliberately independent of the :mod:`repro.obs` telemetry producers:
    the control path must trace with ``obs=None``."""
    leaves = jax.tree.leaves(psi_K)
    K = leaves[0].shape[0]
    total = jnp.zeros((), jnp.float32)
    for l in leaves:
        x = l.astype(jnp.float32)
        total = total + jnp.sum(jnp.square(x - jnp.mean(x, axis=0, keepdims=True)))
    return total / float(K)


def _tree_momentum_sq(mom) -> jax.Array:
    """Sum of squares of a (f32) momentum tree, over every leaf."""
    return sum(jnp.sum(jnp.square(m)) for m in jax.tree.leaves(mom))


# ---------------------------------------------------------------------------
# global (gather/einsum) engine — per-leaf reference oracle
# ---------------------------------------------------------------------------


def gather_consensus_step(
    partition: LayerPartition,
    psi_K,
    C: jax.Array,
    cfg: DRTConfig,
    algorithm: Algorithm = "drt",
    metropolis: jax.Array | None = None,
    exchange_dtype=None,
    codec: "WireCodec | str | None" = None,
    codec_state=None,
    rng: jax.Array | None = None,
    publish=None,
    a_transform=None,
):
    """One consensus step on the agent-stacked tree (per-leaf reference path).

    Returns ``(new_K, A)``, or ``(new_K, A, new_codec_state)`` when a
    ``codec`` is passed explicitly (stateful codecs thread their per-agent
    error-feedback residual through ``codec_state``; stateless codecs pass
    ``()`` through).

    ``codec`` compresses the cross-agent exchange (distance statistics + the
    off-diagonal combine); each agent's own contribution stays full precision.
    ``exchange_dtype`` is the deprecated spelling of ``codec='bf16'``.

    ``publish`` (fault injection) substitutes the PUBLISHED view of the
    agent-stacked tree: distance statistics and the off-diagonal combine
    read ``publish`` (through the codec, like honest traffic) while every
    agent's own self term keeps its true ``psi_K`` row.  ``a_transform``
    post-processes the mixing matrices (trust clipping/temperature).  Both
    default to None and then trace the exact pre-fault program.

    This is the reference oracle the slab hot path
    (:func:`gather_consensus_rounds`) is parity-tested against.
    """
    legacy_return = codec is None
    wire_codec = _resolve_codec(codec, exchange_dtype)

    def mixing(psi_for_stats):
        if algorithm == "classical":
            return jnp.broadcast_to(
                metropolis, (partition.num_layers, *metropolis.shape)
            )
        if algorithm == "drt":
            d2, n2 = partition.pairwise_sq_dists(psi_for_stats)
            return drt_mod.drt_mixing_matrices(d2, n2, C, cfg)
        raise ValueError(f"unknown algorithm {algorithm!r}")

    if wire_codec is None or isinstance(wire_codec, IdentityCodec):
        if publish is None:
            # exact exchange: stats and combine on the raw tree
            A = mixing(psi_K)
            if a_transform is not None:
                A = a_transform(A)
            new = partition.combine(A, psi_K)
            if legacy_return:
                return new, A
            return new, A, codec_state if codec_state is not None else ()
        # exact exchange under fault injection: neighbours see the published
        # (poisoned) tree, each agent's self term keeps its true row
        A = mixing(publish)
        if a_transform is not None:
            A = a_transform(A)
        eye = jnp.eye(A.shape[1], dtype=A.dtype)
        off = partition.combine(A * (1.0 - eye)[None], publish)
        diag = jnp.diagonal(A, axis1=1, axis2=2)
        selfed = jax.vmap(
            lambda w_l, tree: partition.scale_by_layer(w_l, tree), in_axes=(1, 0)
        )(diag, psi_K)
        new = jax.tree.map(
            lambda o, s: (o.astype(jnp.float32) + s.astype(jnp.float32)).astype(s.dtype),
            off,
            selfed,
        )
        if legacy_return:
            return new, A
        return new, A, codec_state if codec_state is not None else ()

    K = jax.tree.leaves(psi_K)[0].shape[0]
    if wire_codec.stateful and (codec_state is None or codec_state == ()):
        codec_state = init_comm_state(wire_codec, psi_K)
    elif codec_state is None:
        codec_state = ()

    keys = _agent_keys(_require_rng(wire_codec, rng), K)
    wire_K, new_state = jax.vmap(wire_codec.encode)(
        psi_K if publish is None else publish, codec_state, keys
    )
    psi_hat_K = jax.vmap(wire_codec.decode)(wire_K)
    A = mixing(psi_hat_K)
    if a_transform is not None:
        A = a_transform(A)

    eye = jnp.eye(A.shape[1], dtype=A.dtype)
    off = partition.combine(A * (1.0 - eye)[None], psi_hat_K)  # decoded neighbours
    diag = jnp.diagonal(A, axis1=1, axis2=2)  # (L, K) self weights

    def add_self(o, s_scaled):
        return (o.astype(jnp.float32) + s_scaled.astype(jnp.float32)).astype(
            s_scaled.dtype
        )

    # self term: per-agent per-layer scale of the local full-precision psi
    selfed = jax.vmap(
        lambda w_l, tree: partition.scale_by_layer(w_l, tree), in_axes=(1, 0)
    )(diag, psi_K)
    new = jax.tree.map(add_self, off, selfed)
    if legacy_return:
        return new, A
    return new, A, new_state


# ---------------------------------------------------------------------------
# gather engine — flat-slab hot path (pack once per round-set)
# ---------------------------------------------------------------------------


def _slab_mixing(layout, regions_f32, C, cfg, algorithm, metropolis, num_layers):
    if algorithm == "classical":
        return jnp.broadcast_to(metropolis, (num_layers, *metropolis.shape))
    if algorithm == "drt":
        d2, n2 = layout.pairwise_sq_dists(regions_f32)
        return drt_mod.drt_mixing_matrices(d2, n2, C, cfg)
    raise ValueError(f"unknown algorithm {algorithm!r}")


def _combine_slab_kernels(layout, A, regions):
    """Kernel-backed per-layer combine: one ``slab_combine`` launch per
    group, on the group's region as it is, with that group's mixing
    matrices ``A[layer0 : layer0 + n_slots]``; no joined slab.  Interpret
    mode on CPU."""
    from repro.kernels import slab_combine

    return tuple(
        slab_combine(A[grp.layer0 : grp.layer0 + grp.n_slots], region)
        for grp, region in zip(layout.groups, regions)
    )


def _dequant_combine_slab_kernels(layout, A_off, wire):
    """Fused whole-slab int8 dequantize+combine: ONE grid-based
    ``slab_dequant_combine`` launch per round; per-column scales are
    reconstructed inside the kernel from the static column->scale-segment
    map, so the decoded f32 neighbour slab never materializes.  HBM traffic
    is K x D int8 reads + D f32 writes instead of K x D x 4B dequant
    copies."""
    from repro.kernels import slab_dequant_combine

    A_blocks = jnp.take(
        A_off.astype(jnp.float32), jnp.asarray(layout.block_layer), axis=0
    )
    col_seg, _, _ = _layout_col_maps(layout)
    out = slab_dequant_combine(A_blocks, wire.s, col_seg, layout.join(wire.q))
    return layout.split(out)


def _layout_col_maps(layout):
    """The static per-column maps the fused encode kernels consume, in
    (n_blocks, lane) form: scale segment, owning leaf, intra-leaf index."""
    return layout.col_maps()


def _fused_coded_round(
    layout, regions, wire_codec, res, keys, C_r, metro_r, cfg, algorithm
):
    """ONE ``slab_encode_combine`` launch for this coded round: the kernel
    derives the wire view in-kernel (int8: counter RNG + scale
    reconstruction; bf16/f16: the cast round-trip; top-k: the jnp-thresholded
    sent slab is passed in), accumulates the per-layer Gram matrices, runs
    the DRT mixing math and writes ``A_off^T . dec + diag . self`` — the f32
    wire and decoded neighbour slabs never exist in HBM.  Returns
    ``(regions, res, A)``."""
    from repro.kernels import slab_encode_combine

    K = regions[0].shape[1]
    bl = jnp.asarray(layout.block_layer)
    mix = C_r if algorithm == "drt" else metro_r
    common = dict(
        algorithm=algorithm,
        num_layers=layout.num_layers,
        kappa=cfg.kappa,
        N_clip=cfg.resolve_N(K),
        weight_mode=cfg.weight_mode,
        lane=layout.lane,
    )
    if isinstance(wire_codec, packing.TopKCodec):
        wire, res = packing.slab_encode_batched(
            wire_codec, layout, regions, res, keys
        )
        out, A = slab_encode_combine(
            bl, layout.join(regions), (layout.join(wire),), mix,
            mode="sent", **common,
        )
    elif isinstance(wire_codec, packing.Int8StochasticCodec):
        scales = packing.slab_quant_scales(wire_codec, layout, regions)
        w0, w1 = packing.leaf_key_words(layout, keys)
        col_seg, col_leaf, col_idx = _layout_col_maps(layout)
        out, A = slab_encode_combine(
            bl, layout.join(regions),
            (scales, col_seg, col_leaf, col_idx, w0, w1), mix,
            mode="int8", **common,
        )
    else:  # bf16 / f16 cast codec
        from repro.kernels import slab_cast_combine

        mode = {"bfloat16": "bf16", "float16": "f16"}[
            jnp.dtype(wire_codec.dtype).name
        ]
        out, A = slab_cast_combine(
            bl, layout.join(regions), mix, dtype=mode, **common
        )
    return layout.split(out), res, A


def _permute_quant_encode_kernels(layout, regions, codec, key):
    """Per-shard kernel-backed int8 encode for the permute engine: the local
    (D,) slab goes through ONE ``slab_quant_encode`` launch (in-kernel
    counter RNG + per-column scale reconstruction) — no uniform field, no
    f32 quantization temporaries.  Returns the same ``SlabQuant`` region
    wire as ``packing.slab_encode``, bit for bit."""
    from repro.kernels import slab_quant_encode

    scales = packing.slab_quant_scales(codec, layout, regions)  # (n_segs,)
    w0, w1 = packing.leaf_key_words(layout, key[None])  # (1, n_leaves) each
    col_seg, col_leaf, col_idx = _layout_col_maps(layout)
    q = slab_quant_encode(
        scales[None], col_seg, col_leaf, col_idx, w0, w1,
        layout.join(regions)[None],
    )
    return packing.SlabQuant(q=layout.split(q[0]), s=scales)


def _fused_kernel_supported(wire_codec, algorithm) -> bool:
    """Codecs whose coded round runs as one ``slab_encode_combine`` launch."""
    if algorithm not in ("drt", "classical"):
        return False
    if isinstance(wire_codec, (packing.Int8StochasticCodec, packing.TopKCodec)):
        return True
    if isinstance(wire_codec, CastCodec):
        return jnp.dtype(wire_codec.dtype).name in ("bfloat16", "float16")
    return False


def _combine_slab_per_slot(layout, A, regions):
    """PR 2's per-(group, slot) kernel combine — one ``weighted_combine``
    launch per segment.  Kept as the parity reference for the whole-slab
    batched kernel (``_combine_slab_kernels``), which replaced it on the hot
    path."""
    from repro.kernels import weighted_combine

    out = []
    for grp, region in zip(layout.groups, regions):
        slots = []
        for j in range(grp.n_slots):
            seg = region[j]  # (K, s_pad)
            A_p = A[grp.layer0 + j].astype(jnp.float32)
            slots.append(
                jax.vmap(lambda col, seg=seg: weighted_combine(col, seg), in_axes=1)(A_p)
            )
        out.append(jnp.stack(slots, axis=0))  # (n_slots, K, s_pad)
    return tuple(out)


def _dequant_combine_slab_per_slot(layout, A_off, wire):
    """PR 2's per-(leaf, slot) fused int8 dequantize+combine — kept as the
    parity reference for ``_dequant_combine_slab_kernels``."""
    from repro.kernels import dequant_combine

    out = []
    for grp, q in zip(layout.groups, wire.q):
        slots = []
        for j in range(grp.n_slots):
            A_p = A_off[grp.layer0 + j].astype(jnp.float32)  # (K, K)
            pieces = []
            end = 0
            for plan in grp.float_leaves:
                sid = plan.scale_seg0 + (j if plan.scale_per_slot else 0)
                qs = jax.lax.slice_in_dim(
                    q[j], plan.col0, plan.col0 + plan.width, axis=-1
                )  # (K, width)
                pieces.append(
                    jax.vmap(
                        lambda col, qs=qs, sid=sid: dequant_combine(
                            col, wire.s[:, sid], qs
                        ),
                        in_axes=1,
                    )(A_p)
                )
                end = plan.col0 + plan.width
            piece = pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces, -1)
            if grp.s_pad - end:
                piece = jnp.pad(piece, ((0, 0), (0, grp.s_pad - end)))
            slots.append(piece)
        out.append(jnp.stack(slots, axis=0))  # (n_slots, K, s_pad)
    return tuple(out)


def gather_consensus_rounds(
    partition: LayerPartition,
    psi_K,
    C: jax.Array,
    cfg: DRTConfig,
    *,
    rounds: int = 1,
    algorithm: Algorithm = "drt",
    metropolis: jax.Array | None = None,
    codec: "WireCodec | str | None" = None,
    codec_state=None,
    rng: jax.Array | None = None,
    layout: "packing.SlabLayout | None" = None,
    path: ConsensusPath = "slab",
    edges: "EdgeStacks | None" = None,
    max_in_degree: int | None = None,
    use_kernels: bool = False,
    unroll: bool = False,
    obs: "ObsConfig | None" = None,
    momentum: float = 0.0,
    round_tol: float | None = None,
    faults=None,
    trust_clip: float | None = None,
    trust_temp: float | None = None,
    combine: str = "drt",
):
    """``rounds`` consensus steps with ONE pack/unpack around the whole set.

    The production hot path: the agent-stacked tree is packed into the flat
    slab once, every round runs per-group segment matmuls (and slab-native
    codec encode/decode) on it, and the tree is unpacked once at the end.
    DRT recomputes the mixing matrices each round (time varying); classical
    diffusion reuses the static ``metropolis`` matrix.  For EXACT exchanges
    (no codec / identity) the round loop runs entirely on the (L, K, K) Gram
    matrices via the recurrence ``G' = A_t^T G A_t`` — two passes over the
    parameters total, independent of ``rounds``.

    Scanned round-sets: every per-round loop (Gram recurrence, coded slab
    rounds, the per-leaf tree oracle) is ONE ``lax.scan`` over the
    ``(rounds, K, K)`` mixing stacks, so trace and compile cost are O(1) in
    ``rounds`` instead of O(rounds).  ``unroll=True`` runs the same round
    body as a Python loop — the trace-time parity oracle (bit-identical
    results; it executes the identical ops per round).

    Dynamic graphs: ``C`` and ``metropolis`` may be per-round
    ``(rounds, K, K)`` stacks (from
    :meth:`repro.core.dynamic.TopologySchedule.mixing_stacks`) instead of a
    single ``(K, K)`` matrix — round ``r`` then mixes over graph ``r`` of the
    stack on every path, including the Gram recurrence.

    Returns ``(new_K, A_last, new_codec_state)``.  ``path="tree"`` (or a
    codec without a slab fast path) falls back to scanning the per-leaf
    reference oracle :func:`gather_consensus_step`.

    ``path="edge"`` is the SPARSE hot path: pass ``edges=`` (a per-round
    :class:`~repro.core.dynamic.EdgeStacks` from
    :meth:`~repro.core.dynamic.TopologySchedule.edge_stacks`) and every
    round runs per-edge distance stats, the edge-factorized eq. 12-14
    weights and a sparse combine — O(|E| D) per round instead of the dense
    paths' O(K^2 D), numerically matching the dense result on the realized
    graph (the dense path stays the parity oracle).  Pass
    ``max_in_degree=`` (a static host bound, e.g.
    :attr:`TopologySchedule.max_in_degree`) to run the GATHER-ONLY CSR
    round: neighbour rows are gathered once per round and shared between
    the stats and the combine, with no scatter anywhere (scatters
    serialize on CPU backends); without it the round uses the
    scatter-by-destination oracle.  With ``use_kernels=True`` each round
    is ONE ``slab_edge_combine`` launch.

    Telemetry: with ``obs=`` an :class:`~repro.obs.ObsConfig`, the return
    gains a fourth element — a :class:`~repro.obs.ConsensusMetrics` stack
    with leading ``(rounds,)`` axis emitted as the round scan's ys (see
    :mod:`repro.obs.metrics` for field semantics).  ``obs=None`` (default)
    traces the EXACT pre-telemetry program: the Gram recurrence reuses its
    carried state for the disagreement, the coded path reads its wire/
    decoded slabs, and the fused single-launch kernel round (which keeps
    those in VMEM) is only used when telemetry is off.  The tree oracle
    prices its telemetry by re-deriving the wire (documented oracle cost).

    Consensus control (both knobs ride the scan carry on EVERY path and
    obey the same zero-cost-disable contract as ``obs``: defaults trace
    today's exact jaxpr):

    * ``momentum=beta`` adds a heavy-ball term to the mixing recurrence,
      ``x_{t+1} = A_t-mix(x_t) + beta * (x_t - x_{t-1})`` (Balu et al.,
      arXiv 2010.11166) — the previous iterate joins the carry, and on the
      exact Gram path the recurrence stays in (K, K) coefficient space:
      ``M_{t+1} = M_t A_{t+1} + beta (M_t - M_{t-1})`` with
      ``M_0 = M_{-1} = I`` (the momentum increment has zero column sums, so
      ``M`` stays column-stochastic and the consensus fixed point is
      preserved).
    * ``round_tol=tol`` turns the static ``rounds`` into an ADAPTIVE budget
      (Kong et al., arXiv 2102.04828): the scan still traces ``rounds``
      iterations (compile stays O(1) in rounds), but each round first
      checks the carried disagreement ``mean_k ||x_k - x_bar||^2`` against
      ``tol`` and becomes an identity no-op (sticky, via ``jnp.where`` on
      the carry) once it drops below.  Telemetry's ``effective_rounds``
      reports the realized budget.

    Robustness (all knobs default off with the same jaxpr-bit-identity
    contract; see :mod:`repro.faults`):

    * ``faults=`` a :class:`repro.faults.FaultRealization` (from
      :meth:`FaultPlan.realize`) injects Byzantine attacks and wire faults:
      masked agents PUBLISH a faulted view of their iterate (applied before
      encode, so poison flows through every codec and both DRT phases like
      honest traffic) while their own self term keeps the true iterate;
      per-agent stale masks re-publish the previous round's iterate (slab /
      edge paths; the tree oracle supports attacks but not staleness).
      Drop faults need no engine support — wrap the schedule in
      :class:`repro.faults.DropSchedule` and the realized graphs
      renormalize like churn.
    * ``trust_clip`` / ``trust_temp`` reweight the realized mixing columns
      (cap any neighbour's mass / sharpen by d2 rank; excess clip mass moves
      to the diagonal) on every path including the exact Gram recurrence —
      the reweight is linear in the iterates, so the two-D-pass property
      survives.
    * ``combine='trimmed:<f>' | 'median'`` replaces the weighted combine
      with a coordinate-wise robust baseline over each agent's closed
      neighbourhood (dense slab path only; ``A_last``/telemetry report the
      support-uniform stand-in weights).  Fault injection and non-DRT
      combines route exact round-sets through the per-round slab body (the
      Gram recurrence is linear algebra and cannot express them).
    """
    wire_codec = _resolve_codec(codec, None)
    if path not in ("slab", "tree", "edge"):
        raise ValueError(f"unknown consensus path {path!r}")
    if path == "edge" and edges is None:
        raise ValueError(
            'path="edge" needs edges= (an EdgeStacks round stack from '
            "TopologySchedule.edge_stacks / edge_stacks_from_topology)"
        )
    if path in ("slab", "edge") and not (
        packing.slab_codec_supported(wire_codec)
        and packing.slab_template_supported(psi_K)
    ):
        # the edge path is slab-native; codecs/templates without a slab fast
        # path take the same per-leaf oracle fallback as path="slab"
        path = "tree"
    if rounds < 1:
        raise ValueError(
            f"gather_consensus_rounds needs rounds >= 1, got {rounds}; "
            "skip the call entirely for a consensus-free step"
        )
    beta = float(momentum)
    if not 0.0 <= beta < 1.0:
        raise ValueError(
            f"consensus momentum must be in [0, 1), got {beta}; the heavy-ball "
            "recurrence diverges at beta >= 1"
        )
    use_mom = beta != 0.0
    use_adapt = round_tol is not None
    if use_adapt:
        round_tol = float(round_tol)
        if not round_tol > 0.0:
            raise ValueError(f"round_tol must be > 0, got {round_tol}")
    K = jax.tree.leaves(psi_K)[0].shape[0]
    L = partition.num_layers
    # -- robustness knobs (defaults trace the exact pre-fault jaxpr) --------
    faults_robust.validate_trust_knobs(trust_clip, trust_temp)
    robust_on = trust_clip is not None or trust_temp is not None
    combine_kind, combine_frac = faults_robust.parse_combine(combine)
    if combine_kind != "drt" and path != "slab":
        raise ValueError(
            f"combine={combine!r} needs the dense slab path (robust combines "
            f"sort each agent's full neighbourhood), got path={path!r}"
        )
    f_model = f_mask = f_stale = f_key = None
    if faults is not None:
        f_model = faults.model
        f_mask = faults.mask
        f_stale = faults.stale
        f_key = faults.key
        for name, arr in (("mask", f_mask), ("stale", f_stale)):
            if arr is not None and tuple(arr.shape) != (rounds, K):
                raise ValueError(
                    f"faults.{name} must be (rounds, K) = ({rounds}, {K}), "
                    f"got {tuple(arr.shape)} — realize the plan with the "
                    "round-set's own start/rounds"
                )
        if f_mask is not None and f_model is None:
            raise ValueError("faults with a Byzantine mask need a fault model")
    use_atk = f_mask is not None
    use_stale = f_stale is not None
    use_faults = use_atk or use_stale
    if use_stale and path == "tree":
        raise ValueError(
            "stale-iterate delivery is not supported on the tree oracle path "
            '(use path="slab" or path="edge")'
        )
    if robust_on:
        def _rw_dense(A):
            return faults_robust.reweight_dense(A, trust_clip, trust_temp)
    else:
        _rw_dense = None
    C_stack = _round_stack(C, rounds, "C")
    metro_stack = _round_stack(metropolis, rounds, "metropolis")
    A0 = jnp.zeros((L, K, K), jnp.float32)  # overwritten by round 1
    # control extras ride the END of every scan carry: the stale-publish
    # iterate (slab/edge fault paths), the previous iterate for momentum,
    # then (active, effective-round counter) for the adaptive budget.
    # Disabled knobs append NOTHING — the default carry (and jaxpr) is
    # bit-identical to the uncontrolled program.
    ctl0 = ()
    if use_adapt:
        ctl0 = (jnp.ones((), bool), jnp.zeros((), jnp.float32))

    if path == "tree":
        state = codec_state
        if wire_codec is not None:
            if wire_codec.stateful and (state is None or state == ()):
                state = init_comm_state(wire_codec, psi_K)
            elif state is None:
                state = ()

        if obs is not None:
            template = _template_sds(psi_K)
            idb = float(IdentityCodec().wire_bytes(template))

        def tree_body(carry, xs):
            psi, st, A_prev, *ctl = carry
            r, C_r, metro_r = xs
            if use_mom:
                prev = ctl[0]
            if use_adapt:
                active, eff = ctl[-2], ctl[-1]
                act = active & (_tree_net_disagreement(psi) > round_tol)
                eff = eff + act.astype(jnp.float32)
            round_rng = None
            pub = None
            if use_atk:
                pub = faults_models.apply_fault_tree(
                    f_model, psi, f_mask[r], jax.random.fold_in(f_key, r)
                )
            if wire_codec is None:
                new_psi, A = gather_consensus_step(
                    partition, psi, C_r, cfg,
                    algorithm=algorithm, metropolis=metro_r,
                    publish=pub, a_transform=_rw_dense,
                )
                new_st = st
            else:
                round_rng = jax.random.fold_in(rng, r) if rng is not None else None
                new_psi, A, new_st = gather_consensus_step(
                    partition, psi, C_r, cfg,
                    algorithm=algorithm, metropolis=metro_r,
                    codec=wire_codec, codec_state=st,
                    rng=round_rng,
                    publish=pub, a_transform=_rw_dense,
                )
            mom_sq = jnp.zeros((), jnp.float32)
            if use_mom:
                mom = jax.tree.map(
                    lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
                    psi, prev,
                )
                new_psi = jax.tree.map(
                    lambda n, m: (n.astype(jnp.float32) + beta * m).astype(n.dtype),
                    new_psi, mom,
                )
                if obs is not None:
                    mom_sq = (beta * beta) * _tree_momentum_sq(mom) / float(K)
            if use_adapt:
                # sticky identity no-op once the budget gates off: the carry
                # keeps its pre-round values, so the remaining traced rounds
                # cost flops but change nothing
                new_psi = jax.tree.map(
                    lambda n, o: jnp.where(act, n, o), new_psi, psi
                )
                new_st = jax.tree.map(lambda n, o: jnp.where(act, n, o), new_st, st)
                A = jnp.where(act, A, A_prev)
                if use_mom:
                    prev = jax.tree.map(
                        lambda o, p: jnp.where(act, o, p), psi, prev
                    )
                if obs is not None:
                    mom_sq = jnp.where(act, mom_sq, 0.0)
            elif use_mom:
                prev = psi
            new_ctl = ()
            if use_mom:
                new_ctl += (prev,)
            if use_adapt:
                new_ctl += (act, eff)
            if obs is None:
                return (new_psi, new_st, A, *new_ctl), None
            # oracle-priced telemetry: the slab paths read these quantities
            # off state they already carry; the per-leaf oracle re-derives
            # the wire the step consumed (same keys => bit-identical wire)
            psi_pub = pub if use_atk else psi
            if wire_codec is None:
                send = jnp.asarray(idb, jnp.float32)
                psi_hat = psi_pub
            else:
                keys = _agent_keys(_require_rng(wire_codec, round_rng), K)
                wire_K, _ = jax.vmap(wire_codec.encode)(psi_pub, st, keys)
                send = jnp.mean(
                    obs_metrics.tree_wire_send_bytes(wire_codec, wire_K, template)
                )
                psi_hat = jax.vmap(wire_codec.decode)(wire_K)
            if algorithm == "drt":
                d2, _ = partition.pairwise_sq_dists(psi_hat)
                d2m, d2x = obs_metrics.d2_summaries(d2)
            else:
                d2m = d2x = jnp.zeros((L,), jnp.float32)
            if wire_codec is not None and wire_codec.stateful:
                ef = obs_metrics.tree_mean_sq_norm(new_st)
            else:
                ef = jnp.zeros((), jnp.float32)
            if use_adapt:
                # a gated-off round moves no bytes; the ratio keeps the
                # codec's nominal value
                eff_rounds = eff
                send_w = jnp.where(act, send, 0.0)
            else:
                eff_rounds = (r + 1).astype(jnp.float32)
                send_w = send
            m = ConsensusMetrics(
                disagreement=obs_metrics.tree_disagreement(new_psi),
                layer_d2_mean=d2m,
                layer_d2_max=d2x,
                mix_entropy=obs_metrics.mixing_entropy(A),
                ef_residual=ef,
                wire_send_bytes=send_w,
                wire_recv_bytes=(K - 1.0) * send_w,
                compression_ratio=idb / jnp.maximum(send, 1.0),
                edges=obs_metrics.edge_count(C_r if C_r is not None else metro_r),
                effective_rounds=eff_rounds,
                momentum_norm=mom_sq,
                suspicion=obs_metrics.suspicion_from_A(
                    A, C_r if C_r is not None else metro_r
                ),
                byzantine_weight_mass=(
                    obs_metrics.byzantine_weight_mass(A, f_mask[r])
                    if use_atk
                    else jnp.zeros((), jnp.float32)
                ),
            )
            return (new_psi, new_st, A, *new_ctl), m

        tree_ctl0 = ((psi_K,) if use_mom else ()) + ctl0
        (psi_K, state, A_last, *_), metrics = _scan_rounds(
            tree_body,
            (psi_K, state, A0, *tree_ctl0),
            (jnp.arange(rounds), C_stack, metro_stack),
            rounds,
            unroll,
        )
        state = state if state is not None else ()
        if obs is None:
            return psi_K, A_last, state
        return psi_K, A_last, state, metrics

    if layout is None:
        layout = packing.cached_slab_layout(partition, _template_sds(psi_K))
    # packed ONCE for the whole round-set; carried between rounds as per-group
    # contiguous regions so no round re-slices or re-concatenates the slab
    with obs_profiling.scope(obs, "consensus.pack"):
        regions = layout.pack_regions(psi_K)
    stateful = wire_codec is not None and wire_codec.stateful
    if stateful:
        if codec_state is None or codec_state == ():
            res = tuple(
                jnp.zeros((g.n_slots, K, g.s_pad), jnp.float32)
                for g in layout.groups
            )
        else:
            res = layout.pack_regions(codec_state)
    exact = wire_codec is None or isinstance(wire_codec, IdentityCodec)
    if not exact:
        rng = _require_rng(wire_codec, rng)

    if path == "edge":
        # Sparse edge-list rounds: per-edge stats + edge-factorized mixing +
        # gather/scatter combine — O(|E| D) per round where every dense slab
        # round (and the dense exact Gram pass) is O(K^2 D).  Exact and coded
        # rounds share ONE body: the exact Gram recurrence is deliberately
        # NOT used here — on a sparse graph rounds x O(|E| D) undercuts even
        # the recurrence's one-time O(K^2 D) Gram + combine passes.
        if edges.src.ndim != 2 or edges.src.shape[0] != rounds:
            raise ValueError(
                f"edges stack covers {edges.src.shape[0] if edges.src.ndim == 2 else '?'} "
                f"rounds, round-set runs {rounds}"
            )
        E = edges.src.shape[-1]
        # faults / trust reweighting run the jnp edge round: the fused edge
        # kernels neither apply publish transforms nor re-weight in-kernel
        edge_kernel = (
            use_kernels
            and obs is None
            and algorithm in ("drt", "classical")
            and not use_faults
            and not robust_on
        )
        if obs is not None:
            idb = obs_metrics.slab_identity_bytes(layout)
            send_exact = jnp.asarray(
                obs_metrics.slab_identity_bytes(layout), jnp.float32
            )
        bl = jnp.asarray(layout.block_layer)

        def edge_body(carry, xs):
            regions, res, A_prev, *ctl = carry
            r, src, dst, w = xs
            if use_stale:
                pubprev = ctl[0]
            if use_mom:
                prev = ctl[1] if use_stale else ctl[0]
            if use_adapt:
                active, eff = ctl[-2], ctl[-1]
                act = active & (packing.region_disagreement(regions) > round_tol)
                eff = eff + act.astype(jnp.float32)
            # published view: stale senders re-publish their previous-round
            # iterate, then masked agents' attack rewrites what goes on the
            # wire; the self term below always reads the true `regions`
            pub_src = regions
            if use_stale:
                srow = f_stale[r]
                pub_src = tuple(
                    jnp.where(srow[None, :, None], p, n)
                    for p, n in zip(pubprev, pub_src)
                )
            if use_atk:
                pub_src = faults_models.apply_fault_regions(
                    f_model, pub_src, f_mask[r], jax.random.fold_in(f_key, r)
                )
            if exact:
                new_res, wire = res, None
                with obs_profiling.scope(obs, "consensus.decode"):
                    decoded = pub_src
            else:
                keys = _agent_keys(jax.random.fold_in(rng, r), K)
                with obs_profiling.scope(obs, "consensus.encode"):
                    wire, new_res = packing.slab_encode_batched(
                        wire_codec, layout, pub_src, res, keys
                    )
                # materialize the WIRE, not the decoded slab: the sparse
                # round's gather/stat consumers then re-read compact wire
                # bytes with the (cheap) decode fused in, instead of either
                # a full f32 slab or a per-consumer re-run of the encode
                # chain (XLA duplicates fused producers — ruinous for the
                # int8 stochastic-rounding RNG)
                wire = jax.lax.optimization_barrier(wire)
                with obs_profiling.scope(obs, "consensus.decode"):
                    decoded = packing.slab_decode(wire_codec, layout, wire)
            d2e = None
            if edge_kernel:
                from repro.kernels import (
                    slab_edge_combine,
                    slab_edge_encode_combine,
                )

                kcommon = dict(
                    algorithm=algorithm,
                    num_layers=L,
                    kappa=cfg.kappa,
                    N_clip=cfg.resolve_N(K),
                    weight_mode=cfg.weight_mode,
                    lane=layout.lane,
                )
                # wire-resident fused round: which compact wire operands the
                # kernel can decode in-VMEM (None -> decoded-slab fallback)
                mode = wire_ops = None
                if max_in_degree is not None:
                    if exact:
                        mode, wire_ops = "exact", (layout.join(regions),)
                    elif isinstance(wire_codec, packing.Int8StochasticCodec):
                        col_seg, _, _ = _layout_col_maps(layout)
                        mode = "int8"
                        wire_ops = (layout.join(wire.q), wire.s, col_seg)
                    elif isinstance(wire_codec, packing.TopKCodec):
                        # EF threshold/residual stay in the jnp encode; the
                        # kernel re-reads the compact 'sent' wire
                        mode, wire_ops = "sent", (layout.join(wire),)
                    elif isinstance(wire_codec, CastCodec):
                        mode = {"bfloat16": "bf16", "float16": "f16"}.get(
                            jnp.dtype(wire_codec.dtype).name
                        )
                        if mode is not None:
                            wire_ops = (layout.join(wire),)
                if mode is not None:
                    # ONE slab_edge_encode_combine launch: in-kernel wire
                    # decode in both phases + eq. 12-14 edge factors +
                    # sort-free CSR segment combine — the decoded (K, D)
                    # slab never exists in HBM (int8 streams 2.5 slab
                    # passes/round vs the dense round's 3; see
                    # repro.kernels.traffic)
                    nbr, pos, valid, _ = csr_from_edges(
                        src, dst, w, K, max_in_degree
                    )
                    out, A_self, A_e = slab_edge_encode_combine(
                        bl, layout.join(regions), wire_ops, src, dst, w,
                        nbr, pos, valid, mode=mode, **kcommon,
                    )
                else:
                    # ONE slab_edge_combine launch: gather-by-edge stats +
                    # eq. 12-14 edge factors + scatter-combine (self term
                    # rides along) over the jnp-decoded slab
                    out, A_self, A_e = slab_edge_combine(
                        bl, layout.join(regions), layout.join(decoded),
                        src, dst, w, **kcommon,
                    )
                new_regions = layout.split(out)
            else:
                csr = None
                if max_in_degree is not None:
                    # gather-only round: per-destination CSR tables derived
                    # in-graph from the sorted edge list (D-free algebra),
                    # Dmax neighbour gathers shared by stats and combine —
                    # no scatter anywhere (scatters serialize on CPU)
                    nbr, pos, valid, rank = csr_from_edges(
                        src, dst, w, K, max_in_degree
                    )
                    if exact:
                        nbr_rows = layout.csr_neighbor_rows(decoded, nbr)
                    else:
                        # gather COMPACT wire rows and decode after: dequant
                        # is per-row, so decode(take(wire)) == take(decoded)
                        # bit for bit, but the neighbour reads move 2x (bf16)
                        # / ~4x (int8) fewer bytes than an f32 slab gather
                        nbr_rows = [
                            packing.slab_decode(
                                wire_codec, layout,
                                packing.slab_wire_take(
                                    wire_codec, wire, nbr[:, j]
                                ),
                            )
                            for j in range(max_in_degree)
                        ]
                    csr = (pos, valid, nbr_rows)
                if algorithm == "drt":
                    n2 = layout.layer_sq_norms(decoded)
                    if csr is not None:
                        d2_csr = layout.csr_sq_dists(decoded, nbr_rows)
                        d2e = jnp.where(
                            (w > 0.0)[None], d2_csr[:, dst, rank], 0.0
                        )
                    else:
                        d2e = layout.edge_sq_dists(decoded, src, dst)
                    A_self, A_e = drt_mod.drt_edge_mixing(
                        d2e, n2, src, dst, w, cfg, K
                    )
                elif algorithm == "classical":
                    m_self, m_e = metropolis_edge_weights(src, dst, w, K)
                    A_self = jnp.broadcast_to(m_self[None], (L, K))
                    A_e = jnp.broadcast_to(m_e[None], (L, E))
                else:
                    raise ValueError(f"unknown algorithm {algorithm!r}")
                if robust_on:
                    A_self, A_e = faults_robust.reweight_edge(
                        A_self, A_e, dst, K, trust_clip, trust_temp
                    )
                with obs_profiling.scope(obs, "consensus.combine"):
                    if csr is not None:
                        pos, valid, nbr_rows = csr
                        a_csr = jnp.where(valid[None], A_e[:, pos], 0.0)
                        new_regions = layout.csr_combine(
                            A_self, a_csr, regions, nbr_rows
                        )
                    else:
                        new_regions = layout.edge_combine(
                            A_self, A_e, src, dst, regions, decoded
                        )
            # densified (L, K, K) mixing matrices: tiny K^2 algebra for the
            # A_last return / telemetry entropy, no D-sized work
            A = drt_mod.edge_mixing_dense(A_self, A_e, src, dst, w, K)
            mom_sq = jnp.zeros((), jnp.float32)
            if use_mom:
                mom = jax.tree.map(
                    lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
                    regions, prev,
                )
                new_regions = jax.tree.map(
                    lambda n, m_: (n.astype(jnp.float32) + beta * m_).astype(n.dtype),
                    new_regions, mom,
                )
                if obs is not None:
                    mom_sq = (beta * beta) * _tree_momentum_sq(mom) / float(K)
            if use_adapt:
                new_regions = jax.tree.map(
                    lambda n, o: jnp.where(act, n, o), new_regions, regions
                )
                new_res = jax.tree.map(lambda n, o: jnp.where(act, n, o), new_res, res)
                A = jnp.where(act, A, A_prev)
                if use_mom:
                    prev = jax.tree.map(lambda o, p: jnp.where(act, o, p), regions, prev)
                if use_stale:
                    pubprev = jax.tree.map(
                        lambda o, p: jnp.where(act, o, p), regions, pubprev
                    )
                if obs is not None:
                    mom_sq = jnp.where(act, mom_sq, 0.0)
            else:
                if use_mom:
                    prev = regions
                if use_stale:
                    pubprev = regions
            new_ctl = ()
            if use_stale:
                new_ctl += (pubprev,)
            if use_mom:
                new_ctl += (prev,)
            if use_adapt:
                new_ctl += (act, eff)
            if obs is None:
                return (new_regions, new_res, A, *new_ctl), None
            mask = (w > 0.0).astype(jnp.float32)
            n_dir = jnp.sum(mask)  # realized DIRECTED edge count
            if d2e is not None:
                # edge-RESTRICTED distance summaries: the stats the sparse
                # round actually computed (the dense paths report all-pairs)
                d2m = jnp.sum(d2e * mask[None], axis=1) / jnp.maximum(n_dir, 1.0)
                d2x = jnp.max(d2e * mask[None], axis=1)
            else:
                d2m = d2x = jnp.zeros((L,), jnp.float32)
            if stateful:
                ef = (
                    sum(jnp.sum(jnp.square(t.astype(jnp.float32))) for t in new_res)
                    / float(K)
                )
            else:
                ef = jnp.zeros((), jnp.float32)
            if exact:
                send = send_exact
            else:
                send = jnp.mean(
                    obs_metrics.slab_wire_send_bytes(wire_codec, layout, wire)
                )
            if use_adapt:
                eff_rounds = eff
                send_w = jnp.where(act, send, 0.0)
            else:
                eff_rounds = (r + 1).astype(jnp.float32)
                send_w = send
            m = ConsensusMetrics(
                disagreement=packing.region_disagreement(new_regions),
                layer_d2_mean=d2m,
                layer_d2_max=d2x,
                mix_entropy=obs_metrics.mixing_entropy(A),
                ef_residual=ef,
                # neighbour-only receive volume: mean in-degree x send — the
                # sparse wire's honest number (dense paths bill (K-1) x send)
                wire_recv_bytes=(n_dir / float(K)) * send_w,
                wire_send_bytes=send_w,
                compression_ratio=idb / jnp.maximum(send, 1.0),
                edges=n_dir / 2.0,
                effective_rounds=eff_rounds,
                momentum_norm=mom_sq,
                suspicion=obs_metrics.suspicion_from_A(
                    A,
                    jnp.zeros((K, K), jnp.float32).at[src, dst].add(mask),
                ),
                byzantine_weight_mass=(
                    obs_metrics.byzantine_weight_mass(A, f_mask[r])
                    if use_atk
                    else jnp.zeros((), jnp.float32)
                ),
            )
            return (new_regions, new_res, A, *new_ctl), m

        edge_ctl0 = (
            ((regions,) if use_stale else ())
            + ((regions,) if use_mom else ())
            + ctl0
        )
        (regions, res, A_last, *_), metrics = _scan_rounds(
            edge_body,
            (regions, res if stateful else (), A0, *edge_ctl0),
            (jnp.arange(rounds), edges.src, edges.dst, edges.w),
            rounds,
            unroll,
        )
        with obs_profiling.scope(obs, "consensus.unpack"):
            new_K = layout.unpack_regions(regions, like=psi_K)
        if stateful:
            like = codec_state if codec_state not in (None, ()) else psi_K
            res_tree = layout.unpack_regions(res, like=like, dtype=jnp.float32)
            if obs is None:
                return new_K, A_last, res_tree
            return new_K, A_last, res_tree, metrics
        state0 = codec_state if codec_state is not None else ()
        if obs is None:
            return new_K, A_last, state0
        return new_K, A_last, state0, metrics

    if exact and not use_faults and combine_kind == "drt":
        # Exact exchange: the combine is linear, so the whole round-set runs
        # on the (L, K, K) Gram matrices — ONE Gram pass over the slab before
        # the loop (psi' = A_t^T psi per layer implies G' = A_t^T G A_t, which
        # holds per round for a CHANGING mixing matrix too), tiny (K, K)
        # algebra per round, and ONE combine with the accumulated mixing
        # product at the end.  Two passes over the D parameters total,
        # independent of the round count, vs two per round on the tree path.
        # The accumulated product starts from the exact identity: I @ A is
        # bit-identical to A, so seeding the scan carry costs nothing.
        # (Trust reweighting is linear — clip A, then M' = M A — so it stays
        # on this path; faults and robust combines are NONLINEAR in the
        # iterates and route through the per-round slab body below instead.)
        eyeL = jnp.broadcast_to(jnp.eye(K, dtype=jnp.float32), (L, K, K))
        metrics = None
        if algorithm not in ("classical", "drt"):
            raise ValueError(f"unknown algorithm {algorithm!r}")
        if use_mom or use_adapt:
            # Consensus control in COEFFICIENT space: with the round-set
            # state written as x_t = M_t-combine of the initial regions, the
            # heavy-ball recurrence x' = A-mix(x) + beta (x - x_prev)
            # becomes M' = M A + beta (M - M_prev) with M_0 = M_{-1} = I,
            # and every Gram-derived statistic (DRT distances, the adaptive
            # gate's disagreement, telemetry) is gram_update(G0, M) from the
            # CONSTANT initial Gram — the exact path keeps its two-D-pass
            # property under control.  beta (M - M_prev) has zero column
            # sums, so M stays column-stochastic and the consensus fixed
            # point is untouched.
            G0 = layout.gram(regions)
            if obs is not None:
                send = jnp.asarray(
                    obs_metrics.slab_identity_bytes(layout), jnp.float32
                )

            def exact_body(carry, xs):
                if use_mom:
                    M, M_prev, A_prev, *ctl = carry
                else:
                    M, A_prev, *ctl = carry
                r, C_r, metro_r = xs
                need_G = use_adapt or algorithm == "drt" or obs is not None
                Gt = packing.gram_update(G0, M) if need_G else None
                if use_adapt:
                    active, eff = ctl[-2], ctl[-1]
                    act = active & (packing.gram_disagreement(Gt) > round_tol)
                    eff = eff + act.astype(jnp.float32)
                d2 = None
                if algorithm == "drt" or obs is not None:
                    d2, n2 = packing.gram_sq_dists(Gt)
                if algorithm == "classical":
                    A = jnp.broadcast_to(metro_r, (L, K, K))
                else:
                    A = drt_mod.drt_mixing_matrices(d2, n2, C_r, cfg)
                if robust_on:
                    A = _rw_dense(A)
                M_new = jnp.einsum("pij,pjk->pik", M, A, precision=packing.HIGHEST)
                mom_sq = jnp.zeros((), jnp.float32)
                if use_mom:
                    dM = M - M_prev
                    M_new = M_new + beta * dM
                    if obs is not None:
                        # ||beta * momentum term||^2 summed over agents and
                        # layers: beta^2 tr(dM^T G0 dM), no D-sized work
                        mom_sq = (
                            (beta * beta)
                            * jnp.sum(
                                jnp.diagonal(
                                    packing.gram_update(G0[0], dM), axis1=1, axis2=2
                                )
                            )
                            / float(K)
                        )
                new_Mp = M if use_mom else None
                if use_adapt:
                    M_new = jnp.where(act, M_new, M)
                    A = jnp.where(act, A, A_prev)
                    if use_mom:
                        new_Mp = jnp.where(act, M, M_prev)
                    if obs is not None:
                        mom_sq = jnp.where(act, mom_sq, 0.0)
                new_carry = (M_new,) + ((new_Mp,) if use_mom else ()) + (A,)
                if use_adapt:
                    new_carry += (act, eff)
                if obs is None:
                    return new_carry, None
                d2m, d2x = obs_metrics.d2_summaries(d2)
                if use_adapt:
                    eff_rounds = eff
                    send_w = jnp.where(act, send, 0.0)
                else:
                    eff_rounds = (r + 1).astype(jnp.float32)
                    send_w = send
                m = ConsensusMetrics(
                    disagreement=packing.gram_disagreement(
                        packing.gram_update(G0, M_new)
                    ),
                    layer_d2_mean=d2m,
                    layer_d2_max=d2x,
                    mix_entropy=obs_metrics.mixing_entropy(A),
                    ef_residual=jnp.zeros((), jnp.float32),
                    wire_send_bytes=send_w,
                    wire_recv_bytes=(K - 1.0) * send_w,
                    compression_ratio=jnp.ones((), jnp.float32),
                    edges=obs_metrics.edge_count(
                        C_r if C_r is not None else metro_r
                    ),
                    effective_rounds=eff_rounds,
                    momentum_norm=mom_sq,
                    suspicion=obs_metrics.suspicion_from_A(
                        A, C_r if C_r is not None else metro_r
                    ),
                    byzantine_weight_mass=jnp.zeros((), jnp.float32),
                )
                return new_carry, m

            carry0 = (eyeL,) + ((eyeL,) if use_mom else ()) + (A0,) + ctl0
            (M, *rest), metrics = _scan_rounds(
                exact_body,
                carry0,
                (jnp.arange(rounds), C_stack, metro_stack),
                rounds,
                unroll,
            )
            A_last = rest[1] if use_mom else rest[0]
        elif obs is not None:
            # telemetry rides the Gram recurrence: the carried (L, K, K)
            # Gram delivers the disagreement (post-round diagonal trick) and
            # the pre-mix d2 summaries without touching the D parameters.
            # Classical gains the G carry ONLY here — obs=None keeps its
            # bare (M, A) carry and today's exact jaxpr.
            send = jnp.asarray(obs_metrics.slab_identity_bytes(layout), jnp.float32)

            def exact_body(carry, xs):
                G, M, _ = carry
                r, C_r, metro_r = xs
                d2, n2 = packing.gram_sq_dists(G)
                if algorithm == "classical":
                    A = jnp.broadcast_to(metro_r, (L, K, K))
                else:
                    A = drt_mod.drt_mixing_matrices(d2, n2, C_r, cfg)
                if robust_on:
                    A = _rw_dense(A)
                G2 = packing.gram_update(G, A)
                d2m, d2x = obs_metrics.d2_summaries(d2)
                m = ConsensusMetrics(
                    disagreement=packing.gram_disagreement(G2),
                    layer_d2_mean=d2m,
                    layer_d2_max=d2x,
                    mix_entropy=obs_metrics.mixing_entropy(A),
                    ef_residual=jnp.zeros((), jnp.float32),
                    wire_send_bytes=send,
                    wire_recv_bytes=(K - 1.0) * send,
                    compression_ratio=jnp.ones((), jnp.float32),
                    edges=obs_metrics.edge_count(
                        C_r if C_r is not None else metro_r
                    ),
                    effective_rounds=(r + 1).astype(jnp.float32),
                    momentum_norm=jnp.zeros((), jnp.float32),
                    suspicion=obs_metrics.suspicion_from_A(
                        A, C_r if C_r is not None else metro_r
                    ),
                    byzantine_weight_mass=jnp.zeros((), jnp.float32),
                )
                return (G2, jnp.einsum("pij,pjk->pik", M, A, precision=packing.HIGHEST), A), m

            (_, M, A_last), metrics = _scan_rounds(
                exact_body,
                (layout.gram(regions), eyeL, A0),
                (jnp.arange(rounds), C_stack, metro_stack),
                rounds,
                unroll,
            )
        elif algorithm == "classical":

            def exact_body(carry, xs):
                M, _ = carry
                _, _, metro_r = xs
                A = jnp.broadcast_to(metro_r, (L, K, K))
                if robust_on:
                    A = _rw_dense(A)
                return (jnp.einsum("pij,pjk->pik", M, A, precision=packing.HIGHEST), A), None

            (M, A_last), _ = _scan_rounds(
                exact_body,
                (eyeL, A0),
                (jnp.arange(rounds), C_stack, metro_stack),
                rounds,
                unroll,
            )
        else:

            def exact_body(carry, xs):
                G, M, _ = carry
                _, C_r, _ = xs
                d2, n2 = packing.gram_sq_dists(G)
                A = drt_mod.drt_mixing_matrices(d2, n2, C_r, cfg)
                if robust_on:
                    A = _rw_dense(A)
                return (
                    packing.gram_update(G, A),
                    jnp.einsum("pij,pjk->pik", M, A, precision=packing.HIGHEST),
                    A,
                ), None

            (_, M, A_last), _ = _scan_rounds(
                exact_body,
                (layout.gram(regions), eyeL, A0),
                (jnp.arange(rounds), C_stack, metro_stack),
                rounds,
                unroll,
            )
        with obs_profiling.scope(obs, "consensus.combine"):
            if use_kernels:
                regions = _combine_slab_kernels(layout, M, regions)
                new_K = layout.unpack_regions(regions, like=psi_K)
            else:
                # fused combine+unpack: one read of the regions, one write per leaf
                new_K = layout.combine_unpack(M, regions, like=psi_K)
        state0 = codec_state if codec_state is not None else ()
        if obs is None:
            return new_K, A_last, state0
        return new_K, A_last, state0, metrics

    # the fully-fused kernel round keeps the wire / decoded slabs / Gram in
    # VMEM — nothing observable — so telemetry routes coded rounds through
    # the partially-fused path (everything still one combine launch); fault
    # injection, trust reweighting and robust combines likewise need the
    # published/decoded slab and the mixing matrices in HBM
    fused_kernel = (
        use_kernels
        and not exact
        and _fused_kernel_supported(wire_codec, algorithm)
        and obs is None
        and not use_faults
        and not robust_on
        and combine_kind == "drt"
    )
    if obs is not None:
        idb = obs_metrics.slab_identity_bytes(layout)

    def coded_body(carry, xs):
        regions, res, A_prev, *ctl = carry
        r, C_r, metro_r = xs
        if use_stale:
            pubprev = ctl[0]
        if use_mom:
            prev = ctl[1] if use_stale else ctl[0]
        if use_adapt:
            active, eff = ctl[-2], ctl[-1]
            act = active & (packing.region_disagreement(regions) > round_tol)
            eff = eff + act.astype(jnp.float32)
        # published view (see edge_body): stale re-publish, then the attack
        pub_src = regions
        if use_stale:
            srow = f_stale[r]
            pub_src = tuple(
                jnp.where(srow[None, :, None], p, n)
                for p, n in zip(pubprev, pub_src)
            )
        if use_atk:
            pub_src = faults_models.apply_fault_regions(
                f_model, pub_src, f_mask[r], jax.random.fold_in(f_key, r)
            )
        wire = None
        d2 = None
        if exact:
            # reachable only under faults / non-DRT combine: exact exchange
            # per round on the slab (the linear Gram recurrence cannot
            # express a nonlinear publish or combine)
            new_res = res
            decoded = pub_src
            keys = None
        else:
            keys = _agent_keys(jax.random.fold_in(rng, r), K)
        if fused_kernel:
            # ONE Pallas launch per coded round: encode + Gram + mixing +
            # combine + self term, wire slabs never materialized in HBM;
            # control (momentum / round gating) applies to its OUTPUTS, so
            # the kernel composes with both knobs unchanged
            new_regions, new_res, A = _fused_coded_round(
                layout, regions, wire_codec, res, keys, C_r, metro_r, cfg,
                algorithm,
            )
        else:
            if not exact:
                # natively-batched encode over the agent axis (bit-identical
                # wire to vmapping the per-agent two-phase oracle, without
                # its transposes)
                with obs_profiling.scope(obs, "consensus.encode"):
                    wire, new_res = packing.slab_encode_batched(
                        wire_codec, layout, pub_src, res, keys
                    )
                with obs_profiling.scope(obs, "consensus.decode"):
                    decoded = packing.slab_decode(wire_codec, layout, wire)  # f32
            if combine_kind != "drt":
                # coordinate-wise robust combine over the decoded published
                # values (own decoded value included); the support-uniform A
                # is the A_last / telemetry stand-in
                A = faults_robust.support_uniform(C_r, L)
                with obs_profiling.scope(obs, "consensus.combine"):
                    new_regions = faults_robust.robust_combine(
                        C_r, decoded, combine_kind, combine_frac
                    )
            else:
                if obs is not None and algorithm == "drt":
                    # same stats _slab_mixing computes — held for telemetry
                    d2, n2 = layout.pairwise_sq_dists(decoded)
                    A = drt_mod.drt_mixing_matrices(d2, n2, C_r, cfg)
                else:
                    A = _slab_mixing(
                        layout, decoded, C_r, cfg, algorithm, metro_r, L
                    )
                if robust_on:
                    A = _rw_dense(A)
                eye = jnp.eye(K, dtype=A.dtype)
                A_off = A * (1.0 - eye)[None]
                with obs_profiling.scope(obs, "consensus.combine"):
                    if use_kernels:
                        # codec outside the fused slab_encode_combine family
                        # (e.g. a custom cast dtype): keep the PR-4
                        # whole-slab combine kernel rather than silently
                        # ignoring use_kernels
                        off = _combine_slab_kernels(layout, A_off, decoded)
                    else:
                        off = layout.combine(A_off, decoded)
                    diag = jnp.diagonal(A, axis1=1, axis2=2)  # (L, K)
                    selfed = layout.scale_by_layer(diag.T, regions)  # f32 self
                    new_regions = jax.tree.map(jnp.add, off, selfed)
        mom_sq = jnp.zeros((), jnp.float32)
        if use_mom:
            mom = jax.tree.map(
                lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
                regions, prev,
            )
            new_regions = jax.tree.map(
                lambda n, m_: (n.astype(jnp.float32) + beta * m_).astype(n.dtype),
                new_regions, mom,
            )
            if obs is not None:
                mom_sq = (beta * beta) * _tree_momentum_sq(mom) / float(K)
        if use_adapt:
            new_regions = jax.tree.map(
                lambda n, o: jnp.where(act, n, o), new_regions, regions
            )
            new_res = jax.tree.map(lambda n, o: jnp.where(act, n, o), new_res, res)
            A = jnp.where(act, A, A_prev)
            if use_mom:
                prev = jax.tree.map(lambda o, p: jnp.where(act, o, p), regions, prev)
            if use_stale:
                pubprev = jax.tree.map(
                    lambda o, p: jnp.where(act, o, p), regions, pubprev
                )
            if obs is not None:
                mom_sq = jnp.where(act, mom_sq, 0.0)
        else:
            if use_mom:
                prev = regions
            if use_stale:
                pubprev = regions
        new_ctl = ()
        if use_stale:
            new_ctl += (pubprev,)
        if use_mom:
            new_ctl += (prev,)
        if use_adapt:
            new_ctl += (act, eff)
        if obs is None:
            return (new_regions, new_res, A, *new_ctl), None
        if d2 is not None:
            d2m, d2x = obs_metrics.d2_summaries(d2)
        else:
            # classical coded rounds have no distance stats in flight and
            # telemetry does not add a Gram pass for them
            d2m = d2x = jnp.zeros((L,), jnp.float32)
        if stateful:
            ef = (
                sum(jnp.sum(jnp.square(t.astype(jnp.float32))) for t in new_res)
                / float(K)
            )
        else:
            ef = jnp.zeros((), jnp.float32)
        if exact:
            send = jnp.asarray(idb, jnp.float32)
        else:
            send = jnp.mean(
                obs_metrics.slab_wire_send_bytes(wire_codec, layout, wire)
            )
        if use_adapt:
            eff_rounds = eff
            send_w = jnp.where(act, send, 0.0)
        else:
            eff_rounds = (r + 1).astype(jnp.float32)
            send_w = send
        m = ConsensusMetrics(
            disagreement=packing.region_disagreement(new_regions),
            layer_d2_mean=d2m,
            layer_d2_max=d2x,
            mix_entropy=obs_metrics.mixing_entropy(A),
            ef_residual=ef,
            wire_send_bytes=send_w,
            wire_recv_bytes=(K - 1.0) * send_w,
            compression_ratio=idb / jnp.maximum(send, 1.0),
            edges=obs_metrics.edge_count(C_r if C_r is not None else metro_r),
            effective_rounds=eff_rounds,
            momentum_norm=mom_sq,
            suspicion=obs_metrics.suspicion_from_A(
                A, C_r if C_r is not None else metro_r
            ),
            byzantine_weight_mass=(
                obs_metrics.byzantine_weight_mass(A, f_mask[r])
                if use_atk
                else jnp.zeros((), jnp.float32)
            ),
        )
        return (new_regions, new_res, A, *new_ctl), m

    coded_ctl0 = (
        ((regions,) if use_stale else ())
        + ((regions,) if use_mom else ())
        + ctl0
    )
    (regions, res, A_last, *_), metrics = _scan_rounds(
        coded_body,
        (regions, res if stateful else (), A0, *coded_ctl0),
        (jnp.arange(rounds), C_stack, metro_stack),
        rounds,
        unroll,
    )

    with obs_profiling.scope(obs, "consensus.unpack"):
        new_K = layout.unpack_regions(regions, like=psi_K)
    if stateful:
        like = codec_state if codec_state not in (None, ()) else psi_K
        # the error-feedback residual stays f32 whatever the param dtype
        res_tree = layout.unpack_regions(res, like=like, dtype=jnp.float32)
        if obs is None:
            return new_K, A_last, res_tree
        return new_K, A_last, res_tree, metrics
    state0 = codec_state if codec_state is not None else ()
    if obs is None:
        return new_K, A_last, state0
    return new_K, A_last, state0, metrics


# ---------------------------------------------------------------------------
# permutation decomposition of structured topologies
# ---------------------------------------------------------------------------


def permutation_decomposition(topology: Topology) -> list[np.ndarray] | None:
    """Decompose the neighbour exchange into agent permutations.

    Returns a list of permutation arrays ``perm`` with ``perm[src] = dst``,
    one per exchange round; after round r agent k holds the tree of agent
    ``inv_perm[k]``.  Returns None when no structured decomposition is known
    (caller falls back to the gather engine).
    """
    K = topology.num_agents
    name = topology.name
    if name == "ring":
        # shift by +1: agent j sends to (j+1) % K
        plus = (np.arange(K) + 1) % K
        minus = (np.arange(K) - 1) % K
        return [plus] if K == 2 else [plus, minus]
    if name == "chain":
        return None  # not a permutation (endpoints) — gather engine
    if name == "hypercube":
        d = int(np.log2(K))
        return [np.arange(K) ^ (1 << b) for b in range(d)]
    if name == "torus2d":
        s = int(round(np.sqrt(K)))
        idx = np.arange(K)
        r, c = idx // s, idx % s
        perms = [
            ((r + 1) % s) * s + c,
            ((r - 1) % s) * s + c,
            r * s + (c + 1) % s,
            r * s + (c - 1) % s,
        ]
        # dedupe (s == 2 makes +1 and -1 identical)
        out, seen = [], set()
        for p in perms:
            key = tuple(p.tolist())
            if key not in seen:
                seen.add(key)
                out.append(p)
        return out
    if name == "full":
        return [np.roll(np.arange(K), -s) for s in range(1, K)]
    return None


def matching_decomposition(topology: Topology) -> list[np.ndarray]:
    """Decompose ANY graph's edge set into matchings via greedy proper edge
    coloring (at most ``2*max_degree - 1`` rounds).

    Each matching is returned as an involutive permutation; agents unmatched
    in a round map to THEMSELVES (``perm[k] = k``) — the permute engine masks
    the resulting self-receives out of the mixing weights, so irregular
    graphs (chain endpoints, churn-realized topologies, single matchings)
    become ppermute-able.  Every undirected edge lands in exactly one
    matching, i.e. each agent receives each neighbour exactly once across the
    rounds.
    """
    K = topology.num_agents
    A = topology.adjacency
    classes: list[list[tuple[int, int]]] = []
    used: list[np.ndarray] = []  # per class: endpoint already matched?
    for i in range(K):
        for j in range(i + 1, K):
            if not A[i, j]:
                continue
            for c in range(len(classes)):
                if not used[c][i] and not used[c][j]:
                    classes[c].append((i, j))
                    used[c][i] = used[c][j] = True
                    break
            else:
                classes.append([(i, j)])
                u = np.zeros(K, dtype=bool)
                u[i] = u[j] = True
                used.append(u)
    perms = []
    for cls in classes:
        p = np.arange(K)
        for i, j in cls:
            p[i], p[j] = j, i
        perms.append(p)
    return perms


@dataclasses.dataclass(frozen=True)
class PermuteConsensus:
    """Neighbour-exchange consensus engine for use inside ``shard_map``.

    The agent axis must be a mesh axis named ``axis_name`` with exactly one
    agent per shard (leading axis 1 inside the shard).

    ``path="slab"`` (the default hot path) packs the local tree into a flat
    (D,) slab once per call, runs all ``rounds`` exchange rounds on it (the
    wire slab is one or two contiguous buffers per ``ppermute`` instead of one
    per leaf) and unpacks once; ``path="tree"`` is the per-leaf reference
    oracle.  ``use_kernels`` swaps the slab inner loops for Pallas kernels:
    ``slab_quant_encode`` for the int8 encode (in-kernel RNG + scale
    reconstruction), ``drt_dist`` for the neighbour statistics and
    ``slab_source_combine`` for the one-launch {self}+neighbours combine.

    With a ``codec`` the published slab/tree is encoded ONCE per round, the
    wire is ppermuted each exchange round and decoded on arrival; calling the
    engine then returns ``(combined, new_codec_state)`` instead of just the
    tree.  ``exchange_dtype`` remains as the deprecated alias for the cast
    codec.

    Dynamic graphs: with a ``schedule``
    (:class:`~repro.core.dynamic.TopologySchedule`) the engine RE-DERIVES the
    exchange decomposition per round from ``schedule.topology_at(start_round
    + r)``.  Realized graphs without a structured decomposition (churned
    rings, single matchings, chains) fall back to
    :func:`matching_decomposition`; agents unmatched in an exchange round
    "receive" themselves and are masked out of the mixing weights, so a
    dropped agent keeps its own iterate exactly.  Because the decomposition
    is host-side Python, ``start_round`` must be a concrete int — dynamic
    schedules under a fully-jitted step belong on the gather engine.
    """

    partition: LayerPartition
    topology: Topology
    cfg: DRTConfig
    axis_name: str = "data"
    algorithm: Algorithm = "drt"
    # mesh axes the parameters are sharded over WITHIN an agent (e.g.
    # ('model',) for tensor parallelism): per-layer squared norms are partial
    # sums on each shard and must be psum'd over these axes
    norm_reduce_axes: tuple[str, ...] = ()
    exchange_dtype: object | None = None  # deprecated: use codec="bf16"
    codec: "WireCodec | str | None" = None
    path: ConsensusPath = "slab"
    use_kernels: bool = False
    # optional repro.core.dynamic.TopologySchedule (duck-typed: needs
    # .topology_at(t) and .num_agents); None keeps the static topology
    schedule: object | None = None
    # consensus control — same semantics (and zero-cost-disable contract) as
    # gather_consensus_rounds: momentum=beta adds the heavy-ball term
    # x' = A-mix(x) + beta (x - x_prev) per round; round_tol=tol turns
    # rounds= into an adaptive budget gated on the global disagreement
    # (one D-sized psum per round, the same price the obs disagreement pays)
    momentum: float = 0.0
    round_tol: float | None = None
    # robust aggregation — trust clipping/temperature applied to the local
    # mixing column (same semantics as gather_consensus_rounds: clip excess
    # moves to the self weight, columns stay stochastic).  Fault INJECTION
    # is gather-only: the permute engine never holds the (K, D) stack, so
    # Byzantine publication faults belong on consensus_impl='gather'.
    trust_clip: float | None = None
    trust_temp: float | None = None

    def _round_topology(self, start_round: int, r: int) -> Topology:
        if self.schedule is None:
            return self.topology
        return self.schedule.topology_at(start_round + r)

    def _round_ctx(self, start_round: int, r: int, static_ctx):
        """(topology, perms, inv_srcs, Cmat) for round ``r`` — the memoized
        ``static_ctx`` when the engine has no schedule (the decomposition is
        loop invariant there; re-deriving it per round would redo the
        O(K^2) edge coloring and host->device constants every round of every
        trace)."""
        if static_ctx is not None:
            return static_ctx
        topo = self._round_topology(start_round, r)
        perms, inv_srcs = self._round_perms(topo)
        return topo, perms, inv_srcs, jnp.asarray(topo.c_matrix(), jnp.float32)

    @staticmethod
    def _round_perms(topo: Topology):
        """Per-round exchange structure: ``(perms, inv_srcs)`` where perms
        are ppermute (src, dst) pair lists and ``inv_srcs[e][k]`` is the
        agent whose tree k receives in exchange ``e`` (``k`` itself for a
        masked phantom pair)."""
        decomp = permutation_decomposition(topo)
        if decomp is None:
            decomp = matching_decomposition(topo)
        perms = [[(int(s), int(p[s])) for s in range(len(p))] for p in decomp]
        inv_srcs = []
        for p in decomp:
            inv = np.empty(len(p), np.int64)
            inv[p] = np.arange(len(p))
            inv_srcs.append(jnp.asarray(inv))
        return perms, inv_srcs

    def _mix_weights(self, topo: Topology, d2, n2, cw, srcs, my):
        """Local column of A from stacked neighbour stats.

        ``d2``/``n2``: (n_nbrs, L) per-neighbour per-layer stats; ``cw``:
        (n_nbrs,) edge weights — 0 marks a masked phantom pair (an agent
        unmatched in that exchange round received its own tree), which gets
        combine weight 0; ``srcs``: (n_nbrs,) source agent ids.
        Returns ``(w_self (L,), w_nbrs (n_nbrs, L))``.
        """
        n_nbrs, L = d2.shape
        mask = cw > 0  # (n_nbrs,)
        if self.algorithm == "classical":
            M = jnp.asarray(topo.metropolis(), jnp.float32)
            w_nbrs = jnp.where(mask[:, None], M[srcs, my][:, None], 0.0)
            w_nbrs = jnp.broadcast_to(w_nbrs, (n_nbrs, L))
            w_self = jnp.broadcast_to(M[my, my][None], (L,))
            return self._reweight(w_self, w_nbrs)
        kappa = self.cfg.kappa
        N = self.cfg.resolve_N(topo.num_agents)
        log_prod = jnp.sum(jnp.log1p(d2 / (n2 + kappa)), axis=1, keepdims=True) + (
            L + 1
        ) * jnp.log(2.0)
        if self.cfg.weight_mode == "paper":
            log_denom = jnp.log(d2 + kappa)
        else:
            log_denom = jnp.log(n2 + kappa + d2)
        neg_inf = drt_mod._NEG_INF
        log_a = (
            log_prod - log_denom + jnp.log(jnp.where(mask, cw, 1.0))[:, None]
        )  # (n_nbrs, L)
        log_a = jnp.where(mask[:, None], log_a, neg_inf)
        # smallest positive per layer — over REAL neighbours only
        log_min = jnp.min(jnp.where(mask[:, None], log_a, -neg_inf), axis=0)
        log_a = jnp.minimum(log_a, jnp.log(N) + log_min)
        Cmat = jnp.asarray(topo.c_matrix(), jnp.float32)
        c_kk = Cmat[my, my]
        n_eff = jnp.sum(mask)  # surviving neighbourhood size
        log_self = jnp.where(
            n_eff > 0,
            jnp.log(c_kk / jnp.maximum(n_eff, 1))
            + jax.nn.logsumexp(log_a, axis=0),
            0.0,  # isolated agent: self weight 1, everything else masked
        )
        # normalize over {self} + surviving neighbours per layer
        log_all = jnp.concatenate([log_self[None], log_a], axis=0)
        m = jnp.max(log_all, axis=0, keepdims=True)
        ex = jnp.exp(log_all - m)
        a_all = ex / jnp.sum(ex, axis=0, keepdims=True)  # (1+n_nbrs, L)
        return self._reweight(a_all[0], a_all[1:])

    def _reweight(self, w_self, w_nbrs):
        """Trust clipping/temperature on the local mixing column; identity
        (no extra ops in the trace) when both knobs are off."""
        if self.trust_clip is None and self.trust_temp is None:
            return w_self, w_nbrs
        return faults_robust.reweight_local(
            w_self, w_nbrs, self.trust_clip, self.trust_temp
        )

    def __call__(
        self,
        psi_local,
        codec_state=None,
        rng: jax.Array | None = None,
        *,
        rounds: int = 1,
        start_round: int = 0,
        obs: "ObsConfig | None" = None,
    ):
        """psi_local: single-agent tree (leaves WITHOUT leading agent axis).

        Must be called inside shard_map with ``axis_name`` bound.  Runs
        ``rounds`` consensus rounds (pack/encode once per round, exchange,
        combine) and returns the combined single-agent tree — or
        ``(combined, new_codec_state)`` when the engine has a codec.

        With a ``schedule``, round ``r`` exchanges over
        ``schedule.topology_at(start_round + r)``; ``start_round`` must be a
        concrete Python int (the decomposition is re-derived on the host).

        Telemetry: with ``obs=`` an :class:`~repro.obs.ObsConfig` the return
        gains a trailing per-round :class:`~repro.obs.ConsensusMetrics`
        stack (this shard's LOCAL view for distances/entropy/wire; the
        disagreement is the GLOBAL ``mean_k ||x_k - x_bar||^2``, which costs
        one D-sized ``psum`` per round — the engine's one non-free metric).
        Fully-churned rounds still emit a row (zero wire volume, zero
        entropy).  ``obs=None`` traces the exact pre-telemetry program.
        """
        if rounds < 1:
            raise ValueError(
                f"PermuteConsensus needs rounds >= 1, got {rounds}; skip the "
                "call entirely for a consensus-free step"
            )
        if not 0.0 <= float(self.momentum) < 1.0:
            raise ValueError(
                f"consensus momentum must be in [0, 1), got {self.momentum}; "
                "the heavy-ball recurrence diverges at beta >= 1"
            )
        if self.round_tol is not None and not float(self.round_tol) > 0.0:
            raise ValueError(f"round_tol must be > 0, got {self.round_tol}")
        faults_robust.validate_trust_knobs(self.trust_clip, self.trust_temp)
        if self.schedule is not None:
            if not isinstance(start_round, (int, np.integer)):
                raise TypeError(
                    "PermuteConsensus re-derives its ppermute decomposition "
                    "per round on the host; start_round must be a concrete "
                    "Python int.  Dynamic schedules driven by a traced step "
                    "need consensus_impl='gather'."
                )
            if self.schedule.num_agents != self.topology.num_agents:
                raise ValueError(
                    f"schedule K={self.schedule.num_agents} != topology "
                    f"K={self.topology.num_agents}"
                )
        wire_codec = _resolve_codec(self.codec, self.exchange_dtype)
        path = self.path
        if path == "slab" and not (
            packing.slab_codec_supported(wire_codec)
            and packing.slab_template_supported(psi_local)
        ):
            path = "tree"
        start_round = int(start_round) if self.schedule is not None else 0
        if path == "tree":
            return self._call_tree(
                psi_local, codec_state, rng, rounds, wire_codec, start_round, obs
            )
        return self._call_slab(
            psi_local, codec_state, rng, rounds, wire_codec, start_round, obs
        )

    # -- slab hot path -------------------------------------------------------

    def _call_slab(
        self, psi_local, codec_state, rng, rounds, wire_codec, start_round, obs=None
    ):
        part = self.partition
        ax = self.axis_name
        my = jax.lax.axis_index(ax)
        has_codec = self.codec is not None
        if wire_codec is not None and isinstance(wire_codec, IdentityCodec):
            wire_codec = None  # identity: exact exchange
        # the layout is built from the LOCAL shard shapes at trace time (and
        # memoized — retraces reuse it), so tensor-parallel shards pack their
        # own slice; per-layer norms are partial sums psum'd over
        # norm_reduce_axes exactly like the tree path
        layout = packing.cached_slab_layout(
            part, jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), psi_local)
        )
        regions = layout.pack_regions(psi_local)  # packed once per round-set
        stateful = wire_codec is not None and wire_codec.stateful
        res = ()
        if stateful:
            if codec_state is None or codec_state == ():
                res = packing.slab_init_state(wire_codec, layout)
            else:
                res = layout.pack_regions(codec_state)
        if wire_codec is not None:
            base_rng = _require_rng(wire_codec, rng)
        beta = float(self.momentum)
        use_mom = beta != 0.0
        use_adapt = self.round_tol is not None
        tol = float(self.round_tol) if use_adapt else None
        K_glob = self.topology.num_agents
        if use_mom:
            prev = regions
        if use_adapt:
            active = jnp.ones((), bool)
            eff = jnp.zeros((), jnp.float32)

        def _global_disagreement(regs):
            # the engine never holds the full agent stack, so the global
            # mean_k ||x_k - x_bar||^2 costs one D-sized psum — the price
            # both the obs disagreement and the adaptive gate pay here
            loc = jnp.zeros((), jnp.float32)
            for t in regs:
                x = t.astype(jnp.float32)
                xbar = jax.lax.psum(x, ax) / K_glob
                loc = loc + jnp.sum(jnp.square(x - xbar))
            for a in self.norm_reduce_axes:
                loc = jax.lax.psum(loc, a)
            return jax.lax.psum(loc, ax) / K_glob

        def _norms(regs):
            n = layout.layer_sq_norms(regs)
            for a in self.norm_reduce_axes:
                n = jax.lax.psum(n, a)
            return n

        def _stats(self_hat, recv):
            if self.use_kernels:
                from repro.kernels import drt_dist

                pairs = []
                for grp, a, b in zip(layout.groups, self_hat, recv):
                    for j in range(grp.n_slots):
                        pairs.append(drt_dist(a[j], b[j]))
                st = jnp.stack(pairs)  # (L, 2)
                d2, n2 = st[:, 0], st[:, 1]
                for a in self.norm_reduce_axes:
                    d2 = jax.lax.psum(d2, a)
                    n2 = jax.lax.psum(n2, a)
                return d2, n2
            diff = jax.tree.map(jnp.subtract, self_hat, recv)
            return _norms(diff), _norms(recv)

        if obs is not None:
            obs_ms = []
            L_part = part.num_layers
            idb = obs_metrics.slab_identity_bytes(layout)

            def _round_metrics(regs, wire, res_now, topo, n_ex, stats, eff_rounds, mom_sq):
                """stats: (d2s, cws, w_all) stacks, or None on a no-edge round."""
                if wire_codec is not None:
                    per_wire = obs_metrics.slab_wire_send_bytes(
                        wire_codec, layout, wire
                    )
                else:
                    per_wire = jnp.asarray(idb, jnp.float32)
                if stats is not None:
                    d2s, cws, w_all = stats
                    d2m, d2x = obs_metrics.neighbour_d2_summaries(d2s, cws > 0)
                    ent = obs_metrics.column_entropy(w_all)
                else:
                    d2m = d2x = jnp.zeros((L_part,), jnp.float32)
                    ent = jnp.zeros((), jnp.float32)
                if stateful:
                    ef = jnp.asarray(
                        sum(
                            jnp.sum(jnp.square(t.astype(jnp.float32)))
                            for t in res_now
                        ),
                        jnp.float32,
                    )
                else:
                    ef = jnp.zeros((), jnp.float32)
                vol = n_ex * per_wire  # one send + one receive per exchange
                return ConsensusMetrics(
                    disagreement=_global_disagreement(regs),
                    layer_d2_mean=d2m,
                    layer_d2_max=d2x,
                    mix_entropy=ent,
                    ef_residual=ef,
                    wire_send_bytes=vol,
                    wire_recv_bytes=vol,
                    compression_ratio=idb / jnp.maximum(per_wire, 1.0),
                    edges=jnp.asarray(
                        float(np.sum(topo.adjacency)) / 2.0, jnp.float32
                    ),
                    effective_rounds=jnp.asarray(eff_rounds, jnp.float32),
                    momentum_norm=jnp.asarray(mom_sq, jnp.float32),
                    # gather-engine fields: the permute engine only sees its
                    # own column of A, so the received-weight audit is not
                    # computable from a single shard
                    suspicion=jnp.zeros((K_glob,), jnp.float32),
                    byzantine_weight_mass=jnp.zeros((), jnp.float32),
                )

        static = self.schedule is None or getattr(self.schedule, "static", False)
        static_ctx = self._round_ctx(start_round, 0, None) if static else None
        for r in range(rounds):
            topo, perms, inv_srcs, Cmat = self._round_ctx(start_round, r, static_ctx)
            regions0, res0 = regions, res
            if use_adapt and perms:
                # pre-round gate on the carried iterate: sticky off, charged
                # only when the round would actually exchange
                act = active & (_global_disagreement(regions) > tol)
                active = act
                eff = eff + act.astype(jnp.float32)
            if wire_codec is not None:
                key = jax.random.fold_in(jax.random.fold_in(base_rng, r), my)
                with obs_profiling.scope(obs, "consensus.encode"):
                    if self.use_kernels and isinstance(
                        wire_codec, packing.Int8StochasticCodec
                    ):
                        # kernel-backed encode: ONE slab_quant_encode launch
                        # (in-kernel RNG + scale reconstruction); bit-identical
                        # wire to the jnp slab encode
                        wire = _permute_quant_encode_kernels(
                            layout, regions, wire_codec, key
                        )
                    else:
                        wire, res = packing.slab_encode(
                            wire_codec, layout, regions, res, key
                        )
                # pin the compressed representation across the wire: without
                # the barrier XLA hoists the f32 up-convert above the
                # collective-permute, silently un-compressing it
                wire = jax.lax.optimization_barrier(wire)
                self_hat = packing.slab_decode(wire_codec, layout, wire)
            else:
                wire = regions
                self_hat = regions
            if not perms:
                # fully-churned round (no edges anywhere): every agent keeps
                # its iterate; a stateful codec's residual still advanced.
                # Control treats it as skipped: no momentum step, no budget
                # charge, prev untouched.
                if obs is not None:
                    obs_ms.append(
                        _round_metrics(
                            regions, wire, res, topo, 0.0, None,
                            eff if use_adapt else float(r + 1), 0.0,
                        )
                    )
                continue

            recvs, d2s, n2s, cws, srcs = [], [], [], [], []
            for perm, inv in zip(perms, inv_srcs):
                # the wire is one contiguous buffer per GROUP (plus one scale
                # vector for int8): a handful of collective launches instead
                # of one per leaf
                recv_wire = jax.tree.map(
                    lambda x: jax.lax.ppermute(x, ax, perm), wire
                )
                if wire_codec is not None:
                    recv_wire = jax.lax.optimization_barrier(recv_wire)
                    recv = packing.slab_decode(wire_codec, layout, recv_wire)
                else:
                    recv = recv_wire
                d2, n2 = _stats(self_hat, recv)
                src = inv[my]
                recvs.append(recv)
                d2s.append(d2)
                n2s.append(n2)
                # cw = 0 marks a phantom pair: an agent left unmatched by a
                # matching round receives its own tree and must not weight it
                cws.append(jnp.where(src != my, Cmat[src, my], 0.0))
                srcs.append(src)

            w_self, w_nbrs = self._mix_weights(
                topo, jnp.stack(d2s), jnp.stack(n2s), jnp.stack(cws),
                jnp.stack(srcs), my,
            )
            w_all = jnp.concatenate([w_self[None], w_nbrs], axis=0)  # (1+n, L)
            if self.use_kernels:
                from repro.kernels import slab_source_combine

                # ONE whole-slab launch per round over the 1+n flat source
                # slabs (self = full precision), per-block weights gathered
                # from the static block->layer map
                srcs_slab = [layout.join(regions)] + [
                    layout.join(rv) for rv in recvs
                ]
                w_blocks = jnp.take(
                    w_all.astype(jnp.float32),
                    jnp.asarray(layout.block_layer),
                    axis=1,
                ).T  # (n_blocks, 1+n)
                regions = layout.split(slab_source_combine(w_blocks, srcs_slab))
            else:
                out_regions = []
                for gi, grp in enumerate(layout.groups):
                    srcs_g = jnp.stack(
                        [regions[gi]] + [rv[gi] for rv in recvs]
                    )  # (1+n, n_slots, s_pad); self = full precision
                    w_g = jax.lax.slice_in_dim(
                        w_all, grp.layer0, grp.layer0 + grp.n_slots, axis=-1
                    )  # (1+n, n_slots)
                    out_regions.append(jnp.sum(w_g[..., None] * srcs_g, axis=0))
                regions = tuple(out_regions)
            mom_sq = jnp.zeros((), jnp.float32)
            if use_mom:
                mom = jax.tree.map(
                    lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
                    regions0, prev,
                )
                regions = jax.tree.map(
                    lambda n, m_: (n.astype(jnp.float32) + beta * m_).astype(n.dtype),
                    regions, mom,
                )
                if obs is not None:
                    # local-shard view, like the other non-disagreement fields
                    mom_sq = (beta * beta) * _tree_momentum_sq(mom)
            if use_adapt:
                regions = jax.tree.map(
                    lambda n, o: jnp.where(act, n, o), regions, regions0
                )
                res = jax.tree.map(lambda n, o: jnp.where(act, n, o), res, res0)
                if use_mom:
                    prev = jax.tree.map(
                        lambda o, p: jnp.where(act, o, p), regions0, prev
                    )
                if obs is not None:
                    mom_sq = jnp.where(act, mom_sq, 0.0)
            elif use_mom:
                prev = regions0
            if obs is not None:
                obs_ms.append(
                    _round_metrics(
                        regions, wire, res, topo, float(len(perms)),
                        (jnp.stack(d2s), jnp.stack(cws), w_all),
                        eff if use_adapt else float(r + 1), mom_sq,
                    )
                )

        with obs_profiling.scope(obs, "consensus.unpack"):
            out = layout.unpack_regions(regions, like=psi_local)
        metrics = None
        if obs is not None:
            metrics = (
                obs_metrics.stack_metrics(obs_ms)
                if obs_ms
                else obs_metrics.empty_metrics(part.num_layers, K_glob)
            )
        if has_codec:
            if stateful:
                like = codec_state if codec_state not in (None, ()) else psi_local
                # the error-feedback residual stays f32 whatever the param dtype
                res_tree = layout.unpack_regions(res, like=like, dtype=jnp.float32)
                if obs is None:
                    return out, res_tree
                return out, res_tree, metrics
            state0 = codec_state if codec_state is not None else ()
            if obs is None:
                return out, state0
            return out, state0, metrics
        if obs is None:
            return out
        return out, metrics

    # -- per-leaf reference oracle -------------------------------------------

    def _call_tree(
        self, psi_local, codec_state, rng, rounds, wire_codec, start_round, obs=None
    ):
        part = self.partition
        ax = self.axis_name
        my = jax.lax.axis_index(ax)
        has_codec = self.codec is not None
        if wire_codec is not None and isinstance(wire_codec, IdentityCodec):
            wire_codec = None  # identity: take the exact legacy path
        if wire_codec is not None:
            base_rng = _require_rng(wire_codec, rng)

        def _norms(tree):
            n = part.sq_norms(tree)
            for a in self.norm_reduce_axes:
                n = jax.lax.psum(n, a)
            return n

        beta = float(self.momentum)
        use_mom = beta != 0.0
        use_adapt = self.round_tol is not None
        tol = float(self.round_tol) if use_adapt else None
        K_glob = self.topology.num_agents
        if use_mom:
            prev = psi_local
        if use_adapt:
            active = jnp.ones((), bool)
            eff = jnp.zeros((), jnp.float32)

        def _global_disagreement(tree):
            loc = jnp.zeros((), jnp.float32)
            for t in jax.tree.leaves(tree):
                x = t.astype(jnp.float32)
                xbar = jax.lax.psum(x, ax) / K_glob
                loc = loc + jnp.sum(jnp.square(x - xbar))
            for a in self.norm_reduce_axes:
                loc = jax.lax.psum(loc, a)
            return jax.lax.psum(loc, ax) / K_glob

        if obs is not None:
            obs_ms = []
            L_part = part.num_layers
            template = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), psi_local
            )
            idb = float(IdentityCodec().wire_bytes(template))

            def _round_metrics(tree, wire, state_now, topo, n_ex, stats, eff_rounds, mom_sq):
                if wire_codec is not None:
                    per_wire = obs_metrics.tree_wire_send_bytes(
                        wire_codec, wire, template
                    )
                else:
                    per_wire = jnp.asarray(idb, jnp.float32)
                if stats is not None:
                    d2s, cws, w_all = stats
                    d2m, d2x = obs_metrics.neighbour_d2_summaries(d2s, cws > 0)
                    ent = obs_metrics.column_entropy(w_all)
                else:
                    d2m = d2x = jnp.zeros((L_part,), jnp.float32)
                    ent = jnp.zeros((), jnp.float32)
                ef = jnp.asarray(
                    sum(
                        jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in jax.tree.leaves(state_now)
                    ),
                    jnp.float32,
                )
                vol = n_ex * per_wire
                return ConsensusMetrics(
                    disagreement=_global_disagreement(tree),
                    layer_d2_mean=d2m,
                    layer_d2_max=d2x,
                    mix_entropy=ent,
                    ef_residual=ef,
                    wire_send_bytes=vol,
                    wire_recv_bytes=vol,
                    compression_ratio=idb / jnp.maximum(per_wire, 1.0),
                    edges=jnp.asarray(
                        float(np.sum(topo.adjacency)) / 2.0, jnp.float32
                    ),
                    effective_rounds=jnp.asarray(eff_rounds, jnp.float32),
                    momentum_norm=jnp.asarray(mom_sq, jnp.float32),
                    # gather-engine fields (see the slab-path comment)
                    suspicion=jnp.zeros((K_glob,), jnp.float32),
                    byzantine_weight_mass=jnp.zeros((), jnp.float32),
                )

        new_state = codec_state
        if (
            (use_mom or use_adapt)
            and wire_codec is not None
            and wire_codec.stateful
            and (new_state is None or new_state == ())
        ):
            # materialize the EF state before the loop so the adaptive
            # where-mask sees the same pytree structure on both sides of
            # round 1 (control-off keeps the lazy in-loop init and its jaxpr)
            new_state = wire_codec.init_state(psi_local)
        static = self.schedule is None or getattr(self.schedule, "static", False)
        static_ctx = self._round_ctx(start_round, 0, None) if static else None
        for r in range(rounds):
            topo, perms, inv_srcs, Cmat = self._round_ctx(start_round, r, static_ctx)
            psi0, state0 = psi_local, new_state
            if use_adapt and perms:
                act = active & (_global_disagreement(psi_local) > tol)
                active = act
                eff = eff + act.astype(jnp.float32)
            if wire_codec is not None:
                if wire_codec.stateful and (new_state is None or new_state == ()):
                    new_state = wire_codec.init_state(psi_local)
                key = jax.random.fold_in(jax.random.fold_in(base_rng, r), my)
                wire, new_state = wire_codec.encode(psi_local, new_state, key)
                # pin the compressed representation across the wire: without the
                # barriers XLA hoists the f32 up-convert above the
                # collective-permute (the CPU backend has no native bf16 dot),
                # silently un-compressing it
                wire = jax.lax.optimization_barrier(wire)
                psi_self_hat = wire_codec.decode(wire)
            else:
                wire = psi_local
                psi_self_hat = psi_local
            if not perms:
                # fully-churned round: keep the iterate; control treats it as
                # skipped (no momentum step, no budget charge)
                if obs is not None:
                    obs_ms.append(
                        _round_metrics(
                            psi_local, wire, new_state, topo, 0.0, None,
                            eff if use_adapt else float(r + 1), 0.0,
                        )
                    )
                continue

            # --- exchange: collect neighbour trees + their per-layer stats --
            recvs, d2s, n2s, cws, srcs = [], [], [], [], []
            for perm, inv in zip(perms, inv_srcs):
                recv_wire = jax.tree.map(lambda x: jax.lax.ppermute(x, ax, perm), wire)
                if wire_codec is not None:
                    recv_wire = jax.lax.optimization_barrier(recv_wire)
                    recv = wire_codec.decode(recv_wire)
                else:
                    recv = recv_wire
                diff = jax.tree.map(
                    lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
                    psi_self_hat,
                    recv,
                )
                # which agent did we receive from? inverse permutation at `my`
                src = inv[my]
                recvs.append(recv)
                d2s.append(_norms(diff))
                n2s.append(_norms(recv))
                cws.append(jnp.where(src != my, Cmat[src, my], 0.0))
                srcs.append(src)

            w_self, w_nbrs = self._mix_weights(
                topo, jnp.stack(d2s), jnp.stack(n2s), jnp.stack(cws),
                jnp.stack(srcs), my,
            )

            # --- combine ----------------------------------------------------
            out = part.scale_by_layer(w_self, psi_local)
            for recv, w in zip(recvs, w_nbrs):
                scaled = part.scale_by_layer(w, recv)
                out = jax.tree.map(jnp.add, out, scaled)
            psi_local = out
            mom_sq = jnp.zeros((), jnp.float32)
            if use_mom:
                mom = jax.tree.map(
                    lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
                    psi0, prev,
                )
                psi_local = jax.tree.map(
                    lambda n, m_: (n.astype(jnp.float32) + beta * m_).astype(n.dtype),
                    psi_local, mom,
                )
                if obs is not None:
                    mom_sq = (beta * beta) * _tree_momentum_sq(mom)
            if use_adapt:
                psi_local = jax.tree.map(
                    lambda n, o: jnp.where(act, n, o), psi_local, psi0
                )
                new_state = jax.tree.map(
                    lambda n, o: jnp.where(act, n, o), new_state, state0
                )
                if use_mom:
                    prev = jax.tree.map(lambda o, p: jnp.where(act, o, p), psi0, prev)
                if obs is not None:
                    mom_sq = jnp.where(act, mom_sq, 0.0)
            elif use_mom:
                prev = psi0
            if obs is not None:
                w_all = jnp.concatenate([w_self[None], w_nbrs], axis=0)
                obs_ms.append(
                    _round_metrics(
                        psi_local, wire, new_state, topo, float(len(perms)),
                        (jnp.stack(d2s), jnp.stack(cws), w_all),
                        eff if use_adapt else float(r + 1), mom_sq,
                    )
                )
        metrics = None
        if obs is not None:
            metrics = (
                obs_metrics.stack_metrics(obs_ms)
                if obs_ms
                else obs_metrics.empty_metrics(part.num_layers, K_glob)
            )
        if has_codec:
            state0 = new_state if new_state is not None else ()
            if obs is None:
                return psi_local, state0
            return psi_local, state0, metrics
        if obs is None:
            return psi_local
        return psi_local, metrics


def collective_bytes_per_step(
    topology: Topology,
    param_bytes,
    engine: str,
    codec: "WireCodec | str | None" = None,
) -> dict[str, int]:
    """Analytic collective volume of ONE consensus step, per agent.

    Thin shim over :func:`repro.comm.collective_bytes_per_step` — pass a
    single-agent parameter tree (instead of raw bytes) plus a ``codec`` for
    codec-aware accounting; the legacy int ``param_bytes`` form keeps
    reporting full-precision volume.
    """
    return _codec_bytes_per_step(topology, param_bytes, engine, codec=codec)
