"""Grouped matrix products over the experts one chip holds (Pallas, TPU).

``moe_gmm(lhs, rhs, group_sizes)`` multiplies the rows of ``lhs`` (m, k),
sorted by expert (the first ``group_sizes[0]`` rows go to expert 0, the
next ``group_sizes[1]`` to expert 1, ...), by their expert's weights
``rhs[g]`` (G, k, n).  Only the first ``sum(group_sizes)`` rows are routed
rows: the grid's row axis has a dynamic extent, the tiles that hold routed
rows, so the tiles past them cost nothing, and the output's rows past the
routed ones are never written.  Their values are undefined; the caller
selects them away (``repro.models.moe`` does, in both directions).

It has a custom VJP.  The input gradient is ``moe_gmm`` again, with the
weights transposed in the kernel; the weight gradient is ``tgmm``, the
per-expert ``lhs^T @ grad`` over each expert's rows, zero for an expert
with no rows.  The forward and the input gradient run as Pallas calls named
``moe_gmm`` and the weight gradient as one named ``moe_tgmm``, so that the
device trace shows them by name.  Accumulation is in float32 in VMEM.

Under ``vmap`` (the trainer's agent axis) each call runs once per agent,
in a loop (:func:`_per_agent`): the grid's row extent differs from agent
to agent.  The loop calls the same named function, so that the trace keeps
the name (Pallas's own batching rule would wrap the call in an anonymous
one).

Adapted from JAX's megablox kernels
(``jax.experimental.pallas.ops.tpu.megablox``, Apache-2.0): the same group
metadata and tiling, without the sharded-group offsets, with named calls
and tiles sized from the operand widths.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import resolve_interpret

F32 = jnp.float32
ROW_TILE = 128  # rows of a tile: the MXU's height, and the padding a group costs at most
VMEM_LIMIT = 48 * 2**20
_RHS_TILE_BYTES = 4 * 2**20  # one buffered weight tile of the forward
_ACC_TILE = 1024  # side of the weight gradient's float32 accumulator tile


def _tile(dim: int, cap: int) -> int:
    """The whole ``dim`` if it is at most ``cap``, else the largest multiple
    of 128 up to ``cap`` that divides it."""
    if dim <= cap:
        return dim
    for t in range(cap - cap % 128, 0, -128):
        if dim % t == 0:
            return t
    raise ValueError(f"no tile of at most {cap} divides {dim} in multiples of 128")


def _group_metadata(group_sizes, m: int, tm: int, visit_empty: bool):
    """Which row tile and which group each step of the row axis works on.

    Returns ``(offsets (G+1,), group_ids (T,), tile_ids (T,))`` and the
    number of steps to run, with ``T = m / tm + G - 1`` the most any
    routing needs.  A tile that two groups share is visited once by each;
    ``visit_empty`` gives an empty group one step (the weight gradient must
    write its zeros)."""
    G = group_sizes.shape[0]
    tiles_m = m // tm
    ends = jnp.cumsum(group_sizes)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends]).astype(jnp.int32)
    starts = offsets[:-1]
    tiles = ((ends + tm - 1) // tm) - (starts // tm)
    tiles = jnp.where(group_sizes == 0, 0, tiles)
    if visit_empty:
        tiles = jnp.where(group_sizes == 0, 1, tiles)
    steps = tiles_m + G - 1
    group_ids = jnp.repeat(jnp.arange(G, dtype=jnp.int32), tiles, total_repeat_length=steps)
    # each tile is visited once by the group that starts in it or owns its
    # first row, and once more by each group that starts inside it
    shared = (starts % tm != 0) & (group_sizes > 0)
    if visit_empty:
        shared = shared | (group_sizes == 0)
    partial_ids = jnp.where(shared, starts // tm, tiles_m)
    visits = jnp.histogram(partial_ids, bins=tiles_m, range=(0, tiles_m - 1))[0] + 1
    tile_ids = jnp.repeat(
        jnp.arange(tiles_m, dtype=jnp.int32), visits.astype(jnp.int32), total_repeat_length=steps
    )
    return (offsets, group_ids, tile_ids), jnp.sum(tiles).astype(jnp.int32)


def _row_mask(grid_id, meta, tm: int, width: int):
    """Rows of this step's tile that belong to its group."""
    offsets, group_ids, tile_ids = meta
    g = group_ids[grid_id]
    row = jax.lax.broadcasted_iota(jnp.int32, (tm, width), 0) + tile_ids[grid_id] * tm
    return (row >= offsets[g]) & (row < offsets[g + 1])


def _per_agent(fn):
    """``fn`` with a batching rule that maps it over the batch axis, one
    call per agent."""
    fn_vmap = jax.custom_batching.custom_vmap(fn)

    @fn_vmap.def_vmap
    def _rule(axis_size, in_batched, *args):
        args = [a if b else jnp.broadcast_to(a, (axis_size, *a.shape)) for a, b in zip(args, in_batched)]
        return jax.lax.map(lambda xs: fn(*xs), args), True

    return fn_vmap


def _gmm(lhs, rhs, group_sizes, *, transpose_rhs: bool, interpret: bool):
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm = ROW_TILE
    tk = _tile(k, 2048)
    tn = _tile(n, max(128, _RHS_TILE_BYTES // (tk * rhs.dtype.itemsize)))
    meta, n_steps = _group_metadata(group_sizes, m, tm, visit_empty=False)
    tiles_k = k // tk

    def kernel(meta, lhs_ref, rhs_ref, out_ref, acc_ref):
        grid_id, k_i = pl.program_id(1), pl.program_id(2)

        @pl.when(k_i == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        dims = (((1,), (1,)), ((), ())) if transpose_rhs else (((1,), (0,)), ((), ()))
        acc_ref[...] += jax.lax.dot_general(
            lhs_ref[...], rhs_ref[...], dims, preferred_element_type=F32
        )

        @pl.when(k_i == tiles_k - 1)
        def _():
            mask = _row_mask(grid_id, meta, tm, tn)
            out_ref[...] = jnp.where(
                mask, acc_ref[...], out_ref[...].astype(F32)
            ).astype(out_ref.dtype)

    def lhs_map(n_i, grid_id, k_i, meta):
        return meta[2][grid_id], k_i

    def rhs_map(n_i, grid_id, k_i, meta):
        g = meta[1][grid_id]
        return (g, n_i, k_i) if transpose_rhs else (g, k_i, n_i)

    def out_map(n_i, grid_id, k_i, meta):
        return meta[2][grid_id], n_i

    rhs_block = (None, tn, tk) if transpose_rhs else (None, tk, tn)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[pl.BlockSpec((tm, tk), lhs_map), pl.BlockSpec(rhs_block, rhs_map)],
            out_specs=pl.BlockSpec((tm, tn), out_map),
            grid=(n // tn, n_steps, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), F32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        interpret=interpret,
        name="moe_gmm",
    )(meta, lhs, rhs)


def tgmm(lhs, grad, group_sizes, out_dtype, *, interpret: bool):
    """``out[g] = lhs[rows of g]^T @ grad[rows of g]``: (G, k, n) from
    ``lhs`` (m, k) and ``grad`` (m, n); zero for an empty group."""
    m, k = lhs.shape
    n = grad.shape[1]
    G = group_sizes.shape[0]
    tm = ROW_TILE
    tk, tn = _tile(k, _ACC_TILE), _tile(n, _ACC_TILE)
    meta, n_steps = _group_metadata(group_sizes, m, tm, visit_empty=True)

    def kernel(meta, lhs_ref, grad_ref, out_ref, acc_ref):
        offsets, group_ids, _ = meta
        grid_id = pl.program_id(2)
        g = group_ids[grid_id]
        prev = group_ids[jnp.maximum(grid_id - 1, 0)]
        last = grid_id == pl.num_programs(2) - 1
        nxt = group_ids[jnp.where(last, grid_id, grid_id + 1)]

        @pl.when((grid_id == 0) | (prev != g))
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.when(offsets[g + 1] > offsets[g])
        def _():
            # rows of other groups (and undefined rows past the routed
            # ones) are selected away before the product, never multiplied
            x = jnp.where(_row_mask(grid_id, meta, tm, tk), lhs_ref[...].astype(F32), 0.0)
            y = jnp.where(_row_mask(grid_id, meta, tm, tn), grad_ref[...].astype(F32), 0.0)
            acc_ref[...] += jax.lax.dot(
                x.swapaxes(0, 1).astype(lhs_ref.dtype),
                y.astype(grad_ref.dtype),
                preferred_element_type=F32,
            )

        @pl.when(last | (nxt != g))
        def _():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)

    def lhs_map(n_i, k_i, grid_id, meta):
        return meta[2][grid_id], k_i

    def grad_map(n_i, k_i, grid_id, meta):
        return meta[2][grid_id], n_i

    def out_map(n_i, k_i, grid_id, meta):
        return meta[1][grid_id], k_i, n_i

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((G, k, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[pl.BlockSpec((tm, tk), lhs_map), pl.BlockSpec((tm, tn), grad_map)],
            out_specs=pl.BlockSpec((None, tk, tn), out_map),
            grid=(n // tn, k // tk, n_steps),
            scratch_shapes=[pltpu.VMEM((tk, tn), F32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        interpret=interpret,
        name="moe_tgmm",
    )(meta, lhs, grad)


@functools.lru_cache(maxsize=None)
def _launch(name: str, transpose_rhs: bool, interpret: bool, out_dtype=None):
    """The jitted call named ``name`` (the name its kernel carries in the
    compiled program), batched one agent at a time."""
    if name == "moe_gmm":
        body = lambda x, w, gs: _gmm(x, w, gs, transpose_rhs=transpose_rhs, interpret=interpret)
    else:
        body = lambda x, g, gs: tgmm(x, g, gs, out_dtype, interpret=interpret)
    body.__name__ = body.__qualname__ = name
    return _per_agent(jax.jit(body))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def moe_gmm(lhs, rhs, group_sizes, interpret: bool | None = None):
    """``lhs`` (m, k) rows sorted by group, ``rhs`` (G, k, n), ``group_sizes``
    (G,) int32 with ``m`` a multiple of :data:`ROW_TILE` -> (m, n) in
    ``lhs.dtype``; rows past ``sum(group_sizes)`` undefined."""
    return _launch("moe_gmm", False, resolve_interpret(interpret))(lhs, rhs, group_sizes)


def _fwd(lhs, rhs, group_sizes, interpret):
    return moe_gmm(lhs, rhs, group_sizes, interpret), (lhs, rhs, group_sizes)


def _bwd(interpret, res, grad):
    lhs, rhs, group_sizes = res
    interpret = resolve_interpret(interpret)
    d_lhs = _launch("moe_gmm", True, interpret)(grad, rhs, group_sizes)
    d_rhs = _launch("moe_tgmm", False, interpret, rhs.dtype)(lhs, grad, group_sizes)
    return d_lhs, d_rhs, None


moe_gmm.defvjp(_fwd, _bwd)
