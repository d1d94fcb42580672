"""ResNet-20 agents on the CIFAR-like task of the paper's §IV: the binding
of the program (``repro.models.resnet``) to the benchmark, the weights and
data the benchmark makes from the seed, and the model's FLOP count.

The weights and the data are the benchmark's own, made on the device from
the seed in one jitted call each, in the program's parameter layout: the
plain reference (``bench/reference/resnet20.py``) starts from the same
weights and reads the same batches, and takes nothing the program made.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

STAGES = ("stage1_blocks", "stage2_blocks", "stage3_blocks")
BLOCKS_PER_STAGE = 3


def loss_fn(cfg):
    from repro.models.resnet import resnet20_loss

    return lambda params, batch, rng: resnet20_loss(params, batch)


def program_init(cfg):
    from repro.models.resnet import init_resnet20

    return lambda key: init_resnet20(
        key, width=cfg["width"], num_classes=cfg["num_classes"]
    )


# ---------------------------------------------------------------------------
# weights: He-normal convolutions, GroupNorm at identity, a small head
# ---------------------------------------------------------------------------


def _he(key, shape):  # HWIO
    fan_in = shape[0] * shape[1] * shape[2]
    return jax.random.normal(key, shape, jnp.float32) * math.sqrt(2.0 / fan_in)


def init_params(cfg):
    """``key -> params`` of one agent.  Every block of a stage has the
    stage's width; the stage input is zero-padded to it, so block 0's
    ``proj`` is the strided shortcut and the other blocks' ``proj`` is zero
    and unused (stage 1 has none)."""
    w = cfg["width"]
    widths = (w, 2 * w, 4 * w)

    def stage(key, c, has_proj):
        ks = jax.random.split(key, 3 * BLOCKS_PER_STAGE)
        blocks = []
        for b in range(BLOCKS_PER_STAGE):
            k1, k2, k3 = ks[3 * b : 3 * b + 3]
            proj = _he(k3, (1, 1, c, c)) if (b == 0 and has_proj) else jnp.zeros((1, 1, c, c))
            blocks.append({
                "conv1": _he(k1, (3, 3, c, c)),
                "gn1_w": jnp.ones((c,)), "gn1_b": jnp.zeros((c,)),
                "conv2": _he(k2, (3, 3, c, c)),
                "gn2_w": jnp.ones((c,)), "gn2_b": jnp.zeros((c,)),
                "proj": proj,
            })
        return jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)

    def init(key):
        ks = jax.random.split(key, 5)
        return {
            "stem": {
                "conv": _he(ks[0], (3, 3, cfg["channels"], w)),
                "gn_w": jnp.ones((w,)), "gn_b": jnp.zeros((w,)),
            },
            "stage1_blocks": stage(ks[1], widths[0], False),
            "stage2_blocks": stage(ks[2], widths[1], True),
            "stage3_blocks": stage(ks[3], widths[2], True),
            "head": {
                "w": jax.random.normal(ks[4], (widths[2], cfg["num_classes"])) * 0.01,
                "b": jnp.zeros((cfg["num_classes"],)),
            },
        }

    return init


# ---------------------------------------------------------------------------
# data: the §IV non-IID CIFAR-like task, made on the device
# ---------------------------------------------------------------------------


def make_data(cfg):
    """``key -> data``: per agent a shard of ``n_k`` images drawn from 5-8
    of the classes (the §IV partition), each image a class prototype (a
    coarse 8x8 grid upsampled to the image size), shifted circularly by up
    to ``max_shift`` pixels, plus Gaussian noise.  Shards are padded to the
    largest size; ``counts`` says how many rows of each are real.  An image
    is held as one row of ``s * s * ch`` values, so that a call's rows are
    gathered without moving the whole set."""
    K, C, P = cfg["num_agents"], cfg["num_classes"], cfg["prototypes_per_class"]
    s, ch, n_max = cfg["image_size"], cfg["channels"], cfg["max_samples_per_agent"]
    shift = cfg["max_shift"]

    def one_image(proto, dx, dy, noise):
        return jnp.roll(jnp.roll(proto, dx, axis=0), dy, axis=1) + noise

    def shard(key, protos):
        kc, kn, kk, kl, kp, ks, kz = jax.random.split(key, 7)
        n_cls = jax.random.randint(
            kc, (), cfg["min_classes_per_agent"], cfg["max_classes_per_agent"] + 1
        )
        classes = jax.random.permutation(kk, C)
        count = jax.random.randint(
            kn, (), cfg["min_samples_per_agent"], cfg["max_samples_per_agent"] + 1
        )
        labels = classes[jax.random.randint(kl, (n_max,), 0, n_cls)]
        proto = jax.random.randint(kp, (n_max,), 0, P)
        dxy = jax.random.randint(ks, (n_max, 2), -shift, shift + 1)
        noise = cfg["noise"] * jax.random.normal(kz, (n_max, s, s, ch))
        images = jax.vmap(one_image)(protos[labels, proto], dxy[:, 0], dxy[:, 1], noise)
        return images.reshape(n_max, s * s * ch), labels.astype(jnp.int32), count

    def data(key):
        kp, ka = jax.random.split(key)
        coarse = jax.random.normal(kp, (C, P, 8, 8, ch))
        protos = coarse.repeat(s // 8, axis=2).repeat(s // 8, axis=3)
        images, labels, counts = jax.vmap(shard, in_axes=(0, None))(
            jax.random.split(ka, K), protos
        )
        return {"images": images, "labels": labels, "counts": counts}

    return data


def select(cfg, traffic):
    """``(data, key) -> batches``: each agent's ``L * B`` rows for one call,
    drawn from its own shard without replacement, as leading ``(L, K, B)``
    axes.  Every seed does the same work: ``L`` is fixed in the traffic."""
    L, B = traffic["local_steps"], cfg["batch_size"]
    n_max = cfg["max_samples_per_agent"]
    image = (cfg["image_size"], cfg["image_size"], cfg["channels"])
    if L * B > cfg["min_samples_per_agent"]:
        raise ValueError(f"{L} x {B} rows a call do not fit the smallest shard")

    def rows(key, count):
        u = jax.random.uniform(key, (n_max,))
        u = jnp.where(jnp.arange(n_max) < count, u, 2.0)
        return jnp.argsort(u)[: L * B].reshape(L, B)

    def pick(data, key):
        K = data["counts"].shape[0]
        idx = jax.vmap(rows)(jax.random.split(key, K), data["counts"])  # (K, L, B)
        flat = jnp.swapaxes(idx, 0, 1) + n_max * jnp.arange(K)[None, :, None]  # (L, K, B)
        images = data["images"].reshape(K * n_max, -1)[flat]
        return {
            "images": images.reshape(*flat.shape, *image),
            "labels": data["labels"].reshape(-1)[flat],
        }

    return pick


def samples_per_call(cfg, traffic) -> int:
    """Images trained on by all agents in one call."""
    return traffic["local_steps"] * cfg["num_agents"] * cfg["batch_size"]


def flops_per_sample(cfg) -> float:
    """Model FLOPs of one training image: forward and backward, 3 x the
    forward's 2 x MACs, over the model as published (a block-0 convolution
    reads the previous stage's width; the program's zero-padded channels
    are its own)."""
    w, s, ch = cfg["width"], cfg["image_size"], cfg["channels"]
    macs = 9 * ch * w * s * s  # stem
    c_in, hw = w, s
    for i, c in enumerate((w, 2 * w, 4 * w)):
        stride = 1 if i == 0 else 2
        hw //= stride
        for b in range(BLOCKS_PER_STAGE):
            first = 9 * (c_in if b == 0 else c) * c * hw * hw
            macs += first + 9 * c * c * hw * hw
            if b == 0 and stride == 2:
                macs += c_in * c * hw * hw  # 1x1 strided shortcut
        c_in = c
    macs += 4 * w * cfg["num_classes"]  # head
    return 3 * 2 * macs
