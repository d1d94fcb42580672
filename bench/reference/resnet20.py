"""Plain reference of the ResNet-20 agent's loss, in ``jax.numpy``.

It follows the model as configured: GroupNorm (8 groups) in place of
BatchNorm; each stage's input zero-padded to the stage's width before its
first block; that block's 3x3 convolution and its 1x1 shortcut strided by 2
in stages 2 and 3; global average pooling and a linear head; mean softmax
cross-entropy.  It imports nothing of the program.

``policy.dtype`` is the type every activation is held in; every
convolution and matmul operand goes through ``policy.operand`` and runs at
``policy.precision``: float32 at ``HIGHEST`` for the reference, bfloat16
for the control.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

GROUPS = 8
EPS = 1e-5


def _conv(x, w, stride, policy):
    return jax.lax.conv_general_dilated(
        policy.operand(x), policy.operand(w), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=policy.precision,
    )


def _group_norm(x, w, b):
    n, h, wd, c = x.shape
    g = min(GROUPS, c)
    xg = x.reshape(n, h, wd, g, c // g)
    mean = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
    var = jnp.mean(jnp.square(xg - mean), axis=(1, 2, 4), keepdims=True)
    xg = (xg - mean) / jnp.sqrt(var + EPS)
    return xg.reshape(n, h, wd, c) * w.astype(x.dtype) + b.astype(x.dtype)


def _block(p, x, stride, policy):
    h = jax.nn.relu(_group_norm(_conv(x, p["conv1"], stride, policy), p["gn1_w"], p["gn1_b"]))
    h = _group_norm(_conv(h, p["conv2"], 1, policy), p["gn2_w"], p["gn2_b"])
    short = _conv(x, p["proj"], stride, policy) if stride != 1 else x
    return jax.nn.relu(h + short)


def logits(params, images, policy):
    x = images.astype(policy.dtype)
    stem = params["stem"]
    x = jax.nn.relu(_group_norm(_conv(x, stem["conv"], 1, policy), stem["gn_w"], stem["gn_b"]))
    for i, key in enumerate(("stage1_blocks", "stage2_blocks", "stage3_blocks")):
        stage = params[key]
        width = stage["gn1_w"].shape[-1]
        if x.shape[-1] < width:
            x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, width - x.shape[-1])))
        for b in range(stage["gn1_w"].shape[0]):
            block = {k: v[b] for k, v in stage.items()}
            x = _block(block, x, 2 if (i > 0 and b == 0) else 1, policy)
    pooled = jnp.mean(x, axis=(1, 2))
    head = params["head"]
    head_out = jnp.dot(policy.operand(pooled), policy.operand(head["w"]), precision=policy.precision)
    return head_out + head["b"].astype(policy.dtype)


def loss(params, batch, policy, cfg):
    z = logits(params, batch["images"], policy)
    z = z - jnp.max(z, axis=-1, keepdims=True)
    logz = jnp.log(jnp.sum(jnp.exp(z), axis=-1))
    picked = jnp.take_along_axis(z, batch["labels"][:, None], axis=-1)[:, 0]
    return jnp.mean(logz - picked)
