"""Mosaic compiles of the main-path kernels for a TPU v5e chip.

Each kernel is lowered with ``interpret=False`` and compiled for a described
(not attached) ``v5e:2x2`` topology at the paper job's widths: K=16 agents,
the ResNet-20 (width 16) slab of D=309,248 columns, ring edges; and the
exact combine once more at one h2o-danube-3-4b layer's width (K=2, one
region of 154,836,480 columns), where its tile is widest, with four K=2
slots to a block, and at K=64, the most agents the exact path serves; and
the held experts' grouped matmuls at the kanana cell's widths.  Nothing
runs; a compile that passes proves Mosaic accepts the block shapes, the
in-kernel ops and the VMEM budget, and ``tpu_custom_call`` in the compiled
text proves a Mosaic kernel (not the interpreter) is in the program.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler's library.  Keep every such
compile in this one file.
"""
from __future__ import annotations

import os
import signal

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import kernels as kk
from repro.core.dynamic import edge_stacks_from_topology, max_in_degree_from_topology
from repro.core.packing import build_slab_layout
from repro.core.topology import ring
from repro.kernels.moe_gmm import moe_gmm
from repro.models.resnet import init_resnet20
from repro.utils.pytree import LayerPartition

K = 16

I32, U32, F32, I8, BF16 = jnp.int32, jnp.uint32, jnp.float32, jnp.int8, jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # loading the TPU compiler's library installs a crash handler that
    # dumps a stack trace on SIGTERM; a test run stopped by its time limit
    # would print it into the progress output.  A stopped worker exits
    # quietly instead.
    signal.signal(signal.SIGTERM, signal.SIG_DFL)
    return desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one: keep the cache off around them."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def paper(one_chip):
    """Shapes of the paper job's kernel operands, on one described chip."""
    template = jax.eval_shape(lambda k: init_resnet20(k, width=16), jax.random.key(0))
    layout = build_slab_layout(LayerPartition.build(template), template)
    edges = edge_stacks_from_topology(ring(K), 1)

    def s(shape, dtype=F32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    nb, lane = layout.n_blocks, layout.lane
    E = edges.src.shape[-1]
    dmax = max_in_degree_from_topology(ring(K))
    return dict(
        D=layout.D, L=layout.num_layers, nb=nb, s=s,
        cols=(s((nb, lane), I32), s((nb, lane), I32), s((nb, lane), U32)),
        keys=(s((K, layout.n_tree_leaves), U32),) * 2,
        scales=s((K, layout.n_scale_segs)),
        edges=(s((E,), I32), s((E,), I32), s((E,))),
        csr=(s((K, dmax), I32),) * 3,
    )


def _cases(p):
    """name -> (kernel call, operand shapes) at the paper widths."""
    s, D, L, nb = p["s"], p["D"], p["L"], p["nb"]
    col_seg, col_leaf, col_idx = p["cols"]
    w0, w1 = p["keys"]
    src, dst, w = p["edges"]
    nbr, pos, valid = p["csr"]
    slab, q, bl, mix = s((K, D)), s((K, D), I8), s((nb,), I32), s((K, K))
    return {
        "slab_combine": (  # the widest region: stage 3, 3 slots of 78,080
            lambda a, x: kk.slab_combine(a, x, interpret=False),
            (s((3, K, K)), s((3, K, 78080))),
        ),
        "slab_combine_danube": (  # one h2o-danube-3-4b layer's region, K=2
            lambda a, x: kk.slab_combine(a, x, interpret=False),
            (s((1, 2, 2)), s((1, 2, 154_836_480))),
        ),
        "slab_combine_k2_stacked": (  # four K=2 slots to one (8, tile) block
            lambda a, x: kk.slab_combine(a, x, interpret=False),
            (s((4, 2, 2)), s((4, 2, 78080))),
        ),
        "slab_combine_k64": (  # the widest region at K=64, one slot a block
            lambda a, x: kk.slab_combine(a, x, interpret=False),
            (s((3, 64, 64)), s((3, 64, 78080))),
        ),
        "slab_dequant_combine": (
            lambda a, sc, cs, qq: kk.slab_dequant_combine(a, sc, cs, qq, interpret=False),
            (s((nb, K, K)), p["scales"], col_seg, q),
        ),
        "slab_source_combine": (
            lambda wb, *xs: kk.slab_source_combine(wb, xs, interpret=False),
            (s((nb, 3)), s((D,)), s((D,)), s((D,))),
        ),
        "slab_quant_encode": (
            lambda *a: kk.slab_quant_encode(*a, interpret=False),
            (s((1, p["scales"].shape[1])), col_seg, col_leaf, col_idx,
             s((1, w0.shape[1]), U32), s((1, w1.shape[1]), U32), s((1, D))),
        ),
        "slab_encode_combine_int8": (
            lambda b, x, *ops: kk.slab_encode_combine(
                b, x, ops[:-1], ops[-1], mode="int8", num_layers=L,
                interpret=False,
            ),
            (bl, slab, p["scales"], col_seg, col_leaf, col_idx, w0, w1, mix),
        ),
        "slab_encode_combine_bf16": (
            lambda b, x, c: kk.slab_encode_combine(
                b, x, (), c, mode="bf16", num_layers=L, interpret=False
            ),
            (bl, slab, mix),
        ),
        "slab_edge_encode_combine_exact": (
            lambda b, x, *e: kk.slab_edge_encode_combine(
                b, x, (x,), *e, mode="exact", num_layers=L, interpret=False
            ),
            (bl, slab, src, dst, w, nbr, pos, valid),
        ),
        "slab_edge_encode_combine_int8": (
            lambda b, x, qq, sc, cs, *e: kk.slab_edge_encode_combine(
                b, x, (qq, sc, cs), *e, mode="int8", num_layers=L,
                interpret=False,
            ),
            (bl, slab, q, p["scales"], col_seg, src, dst, w, nbr, pos, valid),
        ),
        "drt_dist": (
            lambda x, y: kk.drt_dist(x, y, interpret=False),
            (s((78080,)), s((78080,))),  # one stage-3 slot
        ),
        "moe_gmm": (  # the kanana cell's held experts: forward and both gradients, per agent
            _held_experts_step,
            (s((2, 49152, 2048), BF16), s((2, 8, 2048, 1536), BF16), s((2, 8, 768, 2048), BF16),
             s((2, 8), I32)),
        ),
    }


def _held_experts_step(x, w_in, w_down, sizes):
    """The SiLU-gated held experts over a 49,152-row buffer (8,192 tokens x
    top-6), d 2048, f 768, 8 groups, K=2 agents: ``moe_gmm`` forward and
    input gradient, ``moe_tgmm`` weight gradient."""

    def loss(x, w_in, w_down, sizes):
        h = moe_gmm(x, w_in, sizes, False)
        y = moe_gmm(jax.nn.silu(h[:, :768]) * h[:, 768:], w_down, sizes, False)
        return jnp.sum(y.astype(F32))

    return jax.vmap(jax.grad(loss, (0, 1, 2)))(x, w_in, w_down, sizes)


@pytest.mark.parametrize(
    "name",
    [
        "slab_combine",
        "slab_combine_danube",
        "slab_combine_k2_stacked",
        "slab_combine_k64",
        "slab_dequant_combine",
        "slab_source_combine",
        "slab_quant_encode",
        "slab_encode_combine_int8",
        "slab_encode_combine_bf16",
        "slab_edge_encode_combine_exact",
        "slab_edge_encode_combine_int8",
        "drt_dist",
        "moe_gmm",
    ],
)
def test_kernel_compiles_for_v5e(name, paper, no_compile_cache):
    assert paper["D"] == 309_248  # the paper job's slab width
    fn, shapes = _cases(paper)[name]
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
