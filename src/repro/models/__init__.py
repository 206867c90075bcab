"""Unified model API over all six assigned families.

``build_model(cfg)`` returns a :class:`Model` bundle of pure functions with a
single batch convention, so the trainer / server / dry-run / swarm layers are
architecture-agnostic:

  train: batch = {tokens, labels[, patch_embeds | frames]}
  decode: (params, tokens[B,1], caches, cache_pos) -> (logits, new_caches)
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import cnn  # noqa: F401  (paper's model, used by examples)
from repro.models.encdec import (
    decode_step as _encdec_decode, forward_encdec, init_encdec, make_encdec_cache,
)
from repro.models.layers import softmax_xent
from repro.models.transformer import (
    forward_lm, init_lm, lm_accuracy, lm_hidden, lm_xent, make_lm_cache,
    project_frontend,
)


@dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    init: Callable[[Any], Any]
    loss_fn: Callable[..., Any]           # (params, batch, remat) -> (loss, metrics)
    decode: Callable[..., Any]            # (params, tokens, caches, cache_pos)
    init_cache: Callable[..., Any]        # (batch_size, max_len) -> caches
    prefill: Optional[Callable[..., Any]] = None
    # (params, batch) -> share of tokens whose largest logit is the label
    accuracy: Optional[Callable[..., Any]] = None


def _lm_model(cfg: ModelConfig) -> Model:
    def loss_fn(params, batch, remat=True):
        x, aux, _ = lm_hidden(params, cfg, batch["tokens"], remat=remat)
        xent = lm_xent(params, cfg, x, batch["labels"], batch.get("mask"))
        return xent + aux, {"xent": xent, "aux": aux}

    def accuracy(params, batch):
        x, _, _ = lm_hidden(params, cfg, batch["tokens"])
        return lm_accuracy(params, cfg, x, batch["labels"], batch.get("mask"))

    def prefill(params, batch, caches):
        logits, _, caches = forward_lm(
            params, cfg, batch["tokens"], caches=caches,
            cache_pos=jnp.int32(0), remat=False)
        return logits[:, -1:], caches

    def decode(params, tokens, caches, cache_pos):
        logits, _, caches = forward_lm(
            params, cfg, tokens, caches=caches, cache_pos=cache_pos, remat=False)
        return logits, caches

    return Model(cfg, lambda key: init_lm(key, cfg), loss_fn, decode,
                 lambda b, m: make_lm_cache(cfg, b, m), prefill, accuracy)


def _vlm_model(cfg: ModelConfig) -> Model:
    """LM backbone consuming stub patch embeddings + text tokens."""

    def _embeds(params, batch):
        from repro.models.layers import dtype_of, embed
        tok = embed(params["embed"], batch["tokens"], dtype_of(cfg.compute_dtype))
        patches = project_frontend(params, cfg, batch["patch_embeds"].astype(tok.dtype))
        return jnp.concatenate([patches, tok], axis=1)

    def loss_fn(params, batch, remat=True):
        x = _embeds(params, batch)
        logits, aux, _ = forward_lm(params, cfg, embeds=x, remat=remat)
        txt_logits = logits[:, cfg.n_patches:]
        xent = softmax_xent(txt_logits, batch["labels"], batch.get("mask"))
        return xent + aux, {"xent": xent, "aux": aux}

    def prefill(params, batch, caches):
        x = _embeds(params, batch)
        logits, _, caches = forward_lm(params, cfg, embeds=x, caches=caches,
                                       cache_pos=jnp.int32(0), remat=False)
        return logits[:, -1:], caches

    def decode(params, tokens, caches, cache_pos):
        logits, _, caches = forward_lm(params, cfg, tokens, caches=caches,
                                       cache_pos=cache_pos, remat=False)
        return logits, caches

    return Model(cfg, lambda key: init_lm(key, cfg), loss_fn, decode,
                 lambda b, m: make_lm_cache(cfg, b, m), prefill)


def _encdec_model(cfg: ModelConfig) -> Model:
    def loss_fn(params, batch, remat=True):
        logits, aux = forward_encdec(params, cfg, batch["frames"],
                                     batch["tokens"], remat=remat)
        xent = softmax_xent(logits, batch["labels"], batch.get("mask"))
        return xent + aux, {"xent": xent, "aux": aux}

    def decode(params, tokens, caches, cache_pos):
        logits, _, caches = _encdec_decode(params, cfg, tokens, caches, cache_pos)
        return logits, caches

    return Model(cfg, lambda key: init_encdec(key, cfg), loss_fn, decode,
                 lambda b, m: make_encdec_cache(cfg, b, m))


def build_model(cfg: ModelConfig) -> Model:
    if cfg.is_encdec:
        return _encdec_model(cfg)
    if cfg.family == "vlm":
        return _vlm_model(cfg)
    return _lm_model(cfg)
