"""The system under test, built from a cell's files through the program's
public trainer API (``repro.core.DecentralizedTrainer``)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from harness.spec import Cell


def topology(cell: Cell):
    """The traffic's graph: a topology's name, or ``{"name", "edges"}`` with
    an undirected edge list."""
    from repro.core import Topology, make_topology

    K, graph = cell.config["num_agents"], cell.traffic["topology"]
    if isinstance(graph, str):
        return make_topology(graph, K)
    adjacency = np.zeros((K, K), bool)
    for l, k in graph["edges"]:
        adjacency[l, k] = adjacency[k, l] = True
    return Topology(graph["name"], adjacency)


def build_trainer(cell: Cell):
    from repro.core import DecentralizedTrainer, DRTConfig, TrainerConfig
    from repro.optim import momentum

    cfg, traffic = cell.config, cell.traffic
    if cfg["optimizer"] != "momentum":
        raise ValueError(f"unsupported optimizer {cfg['optimizer']!r}")
    model = cell.model
    return DecentralizedTrainer(
        model.loss_fn(cfg),
        model.program_init(cfg),
        momentum(cfg["lr"], cfg["momentum"]),
        topology(cell),
        TrainerConfig(
            algorithm=traffic["algorithm"],
            consensus_steps=traffic["rounds"],
            drt=DRTConfig(N=cfg["drt_N"]),
            codec=traffic["codec"],
            consensus_path=traffic["consensus_path"],
            use_kernels=traffic["use_kernels"],
        ),
    )


def make_state_fn(trainer, init_params):
    """``key -> DecentralizedState``: the bench's weights from the seed,
    the same for every agent (as ``trainer.init`` has it), with the
    optimizer's and codec's initial state; one jitted call on the device.  Builds the trainer's layer partition
    from the weights' shapes first."""
    from repro.core import DecentralizedState

    K = trainer.K
    p0 = jax.eval_shape(init_params, jax.random.key(0))
    trainer.build_partition(
        jax.tree.map(lambda x: jax.ShapeDtypeStruct((K, *x.shape), x.dtype), p0)
    )

    def state(key):
        params = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (K, *x.shape)).copy(), init_params(key)
        )
        return DecentralizedState(
            params,
            trainer.optimizer.init(params),
            jnp.zeros((), jnp.int32),
            trainer.init_comm(params),
        )

    return jax.jit(state)
