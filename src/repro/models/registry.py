"""Architecture registry: name -> ModelBundle of pure functions.

The bundle is the single integration surface used by the decentralized
trainer, the serving stack, the dry-run launcher and the smoke tests.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable

from repro.models import enc_dec, transformer as tf, vlm
from repro.models.config import ModelConfig

PyTree = Any


@dataclasses.dataclass(frozen=True)
class ModelBundle:
    cfg: ModelConfig
    init: Callable  # (key) -> params
    loss: Callable  # (params, batch, rng) -> scalar
    forward: Callable  # (params, batch) -> logits
    prefill: Callable  # (params, batch, max_len) -> (logits, caches, pos)
    decode_step: Callable  # (params, token, caches, pos) -> (logits, caches)
    # (params, batch, rng) -> (loss, stats), marked ``has_aux`` for the
    # trainer, which then returns the stats in its metrics; None where the
    # model has no counters
    loss_and_stats: Callable | None = None


def with_stats(fn: Callable) -> Callable:
    """Mark a ``(params, batch, rng) -> (loss, stats)`` loss:
    ``DecentralizedTrainer.local_step`` differentiates the loss alone and
    returns the stats in its metrics."""
    fn.has_aux = True
    return fn


def build_bundle(cfg: ModelConfig) -> ModelBundle:
    if cfg.family == "audio":
        return ModelBundle(
            cfg=cfg,
            init=partial(enc_dec.init_encdec_params, cfg=cfg),
            loss=partial(enc_dec.encdec_loss, cfg=cfg),
            forward=lambda params, batch: enc_dec.decoder_forward(
                params, batch["tokens"], enc_dec.encode(params, batch["audio_embeds"], cfg), cfg
            ),
            prefill=lambda params, batch, max_len: enc_dec.encdec_prefill(
                params, batch, cfg, max_len
            ),
            decode_step=partial(enc_dec.encdec_decode_step, cfg=cfg),
        )
    if cfg.family == "vlm":
        return ModelBundle(
            cfg=cfg,
            init=partial(vlm.init_vlm_params, cfg=cfg),
            loss=partial(vlm.vlm_loss, cfg=cfg),
            forward=lambda params, batch: vlm.vlm_forward(params, batch, cfg)[0],
            prefill=lambda params, batch, max_len: vlm.vlm_prefill(
                params, batch, cfg, max_len
            ),
            decode_step=partial(tf.decode_step, cfg=cfg),
        )
    # dense / moe / ssm / hybrid all share the generic decoder engine
    return ModelBundle(
        cfg=cfg,
        init=partial(tf.init_decoder_params, cfg=cfg),
        loss=partial(tf.lm_loss, cfg=cfg),
        forward=lambda params, batch: tf.forward(params, batch["tokens"], cfg)[0],
        prefill=lambda params, batch, max_len: tf.prefill(
            params, batch["tokens"], cfg, max_len
        ),
        decode_step=partial(tf.decode_step, cfg=cfg),
        loss_and_stats=with_stats(partial(tf.lm_loss_and_stats, cfg=cfg)),
    )


_REGISTRY: dict[str, Callable[[], ModelConfig]] = {}


def register(name: str, cfg_fn: Callable[[], ModelConfig]) -> None:
    _REGISTRY[name] = cfg_fn


def get_config(name: str, **overrides) -> ModelConfig:
    _ensure_configs_loaded()
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; have {sorted(_REGISTRY)}")
    cfg = _REGISTRY[name]()
    return dataclasses.replace(cfg, **overrides) if overrides else cfg


def get_bundle(name: str, **overrides) -> ModelBundle:
    return build_bundle(get_config(name, **overrides))


def list_archs() -> list[str]:
    _ensure_configs_loaded()
    return sorted(_REGISTRY)


_loaded = False


def _ensure_configs_loaded():
    global _loaded
    if not _loaded:
        import repro.configs  # noqa: F401  (registers all archs on import)

        _loaded = True
