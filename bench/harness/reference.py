"""The plain training reference that decides ``correct``.

It repeats what the timed call does to the agents, from the same weights
and batches (both made by the benchmark from the seed), with nothing of the
program: per local step, every agent's loss and gradient (the model's own
plain loss, ``bench/reference/<model>.py``) and a heavy-ball momentum
update; per call, ``rounds`` DRT combination rounds (paper eqs. 12-14).

A DRT layer is a top-level group of the parameters, or, for a group whose
name ends in ``blocks``, each slot of its leading axis (one residual block
or decoder layer).  Distances between agents are summed differences, not a
Gram trick.  ``Policy`` sets the precision: float32 with ``HIGHEST``
matmuls for the reference, and the next precision down for the control.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
LOWER = {"float32": "bfloat16", "bfloat16": "int8"}


@dataclasses.dataclass(frozen=True)
class Policy:
    """``dtype``: operands and activations; ``state``: parameters, momentum
    and the round-set; ``quant``: ``"int8"`` rounds every matmul operand to
    a symmetric per-tensor int8 grid."""

    dtype: object = jnp.float32
    state: object = jnp.float32
    precision: object = HIGHEST
    quant: str | None = None

    def operand(self, x):
        x = x.astype(self.dtype)
        if self.quant == "int8":
            scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
            x = (jnp.round(x / scale) * scale).astype(self.dtype)
        return x


REFERENCE = Policy()


def control_policy(cfg) -> Policy:
    """The reference one precision below what the configuration states:
    its compute type and its parameter type each one step down."""
    compute = LOWER[cfg["compute_dtype"]]
    state = jnp.dtype(LOWER[cfg["param_dtype"]])
    if compute == "int8":
        return Policy(jnp.bfloat16, state, jax.lax.Precision.DEFAULT, "int8")
    return Policy(jnp.dtype(compute), state, jax.lax.Precision.DEFAULT)


def ring(K: int) -> np.ndarray:
    """C of the paper: 1 where l is a neighbour of k or l == k."""
    C = np.eye(K)
    for k in range(K):
        C[(k + 1) % K, k] = C[(k - 1) % K, k] = 1.0
    return C


def graph(spec, K: int) -> np.ndarray:
    """C for the traffic's graph: ``"ring"``, or ``{"name", "edges"}``."""
    if isinstance(spec, str):
        if spec != "ring":
            raise ValueError(f"the reference knows no graph {spec!r}; give its edges")
        return ring(K)
    C = np.eye(K)
    for l, k in spec["edges"]:
        C[l, k] = C[k, l] = 1.0
    return C


def layer_stats(params):
    """``d2 (P, K, K)``: squared distance between agents l and k on DRT
    layer p; ``n2 (P, K)``: agent l's squared norm on it."""
    d2, n2 = [], []
    for key in params:
        leaves = jax.tree.leaves(params[key])
        K = leaves[0].shape[0]
        n = leaves[0].shape[1] if key.endswith("blocks") else 1
        dk = jnp.zeros((n, K, K), leaves[0].dtype)
        nk = jnp.zeros((n, K), leaves[0].dtype)
        for x in leaves:
            x = x.reshape(K, n, -1)
            dist = jax.lax.map(lambda row: jnp.sum(jnp.square(row[None] - x), axis=-1), x)
            dk = dk + jnp.transpose(dist, (2, 0, 1))  # (l, k, n) -> (n, l, k)
            nk = nk + jnp.sum(jnp.square(x), axis=-1).T
        d2.append(dk)
        n2.append(nk)
    return jnp.concatenate(d2), jnp.concatenate(n2)


def drt_weights(d2, n2, C: np.ndarray, N: float, kappa: float):
    """Eqs. (12)-(14): ``A[p, l, k]``, the weight agent k gives agent l on
    layer p; columns sum to 1.  In log space, which changes nothing but the
    range: the product over layers can pass float32's."""
    P, K = n2.shape
    eye = np.eye(K, dtype=bool)
    edge = jnp.asarray((C > 0) & ~eye)
    f32 = jnp.float32
    d2, n2 = d2.astype(f32), n2.astype(f32)
    log_prod = jnp.sum(jnp.log1p(d2 / (n2[:, :, None] + kappa)), axis=0) + (P + 1) * np.log(2.0)
    log_a = log_prod[None] - jnp.log(d2 + kappa) + jnp.log(jnp.where(edge, C, 1.0))[None]
    log_a = jnp.where(edge[None], log_a, -jnp.inf)
    log_min = jnp.min(jnp.where(edge[None], log_a, jnp.inf), axis=1, keepdims=True)
    log_a = jnp.minimum(log_a, np.log(N) + log_min)  # eq. (13): clip at N x the least
    n_k = (C > 0).sum(axis=0)
    log_self = np.log(np.diag(C) / np.maximum(n_k - 1, 1)) + jax.nn.logsumexp(log_a, axis=1)
    log_a = jnp.where(jnp.asarray(eye)[None], log_self[:, None, :], log_a)
    return jax.nn.softmax(log_a, axis=1)  # eq. (12), over l


def combine(A, params, policy: Policy):
    """Agent k's new layer p: the sum over l of ``A[p, l, k]`` times agent
    l's layer p."""
    out, p = {}, 0
    for key in params:
        leaves, treedef = jax.tree.flatten(params[key])
        if key.endswith("blocks"):
            n = leaves[0].shape[1]
            a = A[p : p + n].astype(policy.state)
            mixed = [jnp.einsum("nlk,ln...->kn...", a, x, precision=HIGHEST) for x in leaves]
        else:
            n = 1
            a = A[p].astype(policy.state)
            mixed = [jnp.einsum("lk,l...->k...", a, x, precision=HIGHEST) for x in leaves]
        out[key] = jax.tree.unflatten(treedef, [m.astype(policy.state) for m in mixed])
        p += n
    return out


def leaf_norms(tree) -> dict[str, float]:
    """Each leaf's Euclidean norm, summed in float32."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for x in jax.tree.leaves(t)])(tree)
    return {jax.tree_util.keystr(path): float(v) for (path, _), v in zip(flat, norms)}


def train(loss, cfg, traffic, params0, call_batches, policy: Policy = REFERENCE):
    """Follow the first ``len(call_batches)`` calls from ``params0`` (one
    agent's weights; every agent starts from them).  ``call_batches[c]``
    holds call c's batches with leading ``(L, K)`` axes.

    Returns ``losses (calls, L)`` (the agents' mean loss per local step),
    ``momentum``: the leaf norms of the optimizer's momentum after the first
    call, and ``params``: the agents' parameters after the last."""
    K, lr, beta = cfg["num_agents"], cfg["lr"], cfg["momentum"]
    C = graph(traffic["topology"], K)
    st = policy.state
    params = jax.tree.map(lambda x: jnp.broadcast_to(x.astype(st)[None], (K, *x.shape)), params0)
    mom = jax.tree.map(jnp.zeros_like, params)

    def agent_loss(p, batch):
        return loss(p, batch, policy, cfg)

    @jax.jit(donate_argnums=(0, 1))
    def local_step(params, mom, batch):
        losses, grads = jax.vmap(jax.value_and_grad(agent_loss))(params, batch)
        mom = jax.tree.map(lambda m, g: (beta * m + g.astype(st)).astype(st), mom, grads)
        params = jax.tree.map(lambda p, m: (p - lr * m).astype(st), params, mom)
        return params, mom, jnp.mean(losses.astype(jnp.float32))

    @jax.jit(donate_argnums=(0,))
    def drt_round(params):
        d2, n2 = layer_stats(params)
        A = drt_weights(d2, n2, C, cfg["drt_N"], cfg["drt_kappa"])
        return combine(A, params, policy)

    losses, momentum = [], None
    for c, batches in enumerate(call_batches):
        for i in range(traffic["local_steps"]):
            params, mom, l = local_step(params, mom, jax.tree.map(lambda x: x[i], batches))
            losses.append(float(l))
        if c == 0:
            momentum = leaf_norms(mom)
        for _ in range(traffic["rounds"]):
            params = drt_round(params)
    losses = np.asarray(losses).reshape(len(call_batches), traffic["local_steps"])
    return {"losses": losses, "momentum": momentum, "params": params}


def change_norms(params_K, p0) -> dict[str, float]:
    """Each leaf's norm of the agents' change from the shared start ``p0``."""
    flat = jax.tree_util.tree_flatten_with_path(params_K)[0]
    norms = jax.jit(
        lambda t, s: [
            jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32) - y.astype(jnp.float32)[None])))
            for x, y in zip(jax.tree.leaves(t), jax.tree.leaves(s))
        ]
    )(params_K, p0)
    return {jax.tree_util.keystr(path): float(v) for (path, _), v in zip(flat, norms)}
