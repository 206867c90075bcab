"""A LoRA swarm of a registry language model: sites that adapt one frozen
base model to their own text without pooling it.

The base (every weight of `models.build_model`'s model, the adapters'
scales included) is held once on the device and handed to every round as
`SwarmSession`'s ``shared`` argument, which the sites' vmap does not map
over. Only each site's LoRA adapters (``lora_A``/``lora_B`` on the
projections `core.lora.inject_lora` targets, float32) and their AdamW
state are stacked, trained and exchanged (``payload="lora"``). The gate
scores next-token accuracy on each site's validation text.

    session = lm_swarm_session(model_cfg, train_cfg, swarm_cfg, sizes,
                               seed=0)
    log = session.round((tokens, labels), (val_tokens, val_labels))

``tokens``/``labels`` are ``[T, N, B, S]`` int32 (labels are the tokens
shifted by one), the validation pair ``[N, V, S]``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import tracing
from repro.configs.base import ModelConfig, SwarmConfig, TrainConfig
from repro.core.lora import attach_payload, inject_lora, split_payload
from repro.core.session import SwarmSession
from repro.models import build_model
from repro.optim import adamw_init, adamw_update, make_schedule


def _trained(path: str) -> bool:
    """The leaves each site trains and sends: the adapters' two factors
    (their scale stays with the frozen base)."""
    return path.endswith(("/lora_A", "/lora_B"))


def init_base_and_adapters(model_cfg: ModelConfig, key, rank: int = 16,
                           alpha: float = 32.0):
    """(base, adapters) from ``key``: the model's weights with the adapters'
    scales (`models.build_model`'s init), and a flat ``{path: float32}``
    dict of every adapter factor (``lora_A`` drawn, ``lora_B`` zero)."""
    k_model, k_lora = jax.random.split(key)
    params = jax.jit(build_model(model_cfg).init)(k_model)
    params = inject_lora(params, k_lora, rank=rank, alpha=alpha)
    adapters, base = split_payload(params, _trained)
    return base, {k: v.astype(jnp.float32) for k, v in adapters.items()}


def make_fns(model_cfg: ModelConfig, train_cfg: TrainConfig):
    """(train_step, eval_fn) of one site, each taking the frozen base last.

    ``train_step(adapters, opt_state, (tokens, labels), step, base)``: the
    model's loss (rematerialized per layer), its gradient with respect to
    the adapters only, flowing back through the frozen weights, and AdamW
    on the adapters. ``eval_fn(adapters, (tokens, labels), base)``: the
    share of tokens whose largest logit is the next token."""
    model = build_model(model_cfg)
    sched = make_schedule(train_cfg)

    def train_step(adapters, opt_state, batch, step, base):
        tokens, labels = batch

        def loss(a):
            return model.loss_fn(attach_payload(a, base),
                                 {"tokens": tokens, "labels": labels},
                                 remat=True)[0]

        value, grads = jax.value_and_grad(loss)(adapters)
        adapters, opt_state = adamw_update(adapters, grads, opt_state,
                                           train_cfg,
                                           sched(opt_state["count"]))
        return adapters, opt_state, {"loss": value}

    def eval_fn(adapters, val, base):
        tokens, labels = val
        return model.accuracy(attach_payload(adapters, base),
                              {"tokens": tokens, "labels": labels})

    return train_step, eval_fn


def lm_swarm_session(model_cfg: ModelConfig, train_cfg: TrainConfig,
                     swarm_cfg: SwarmConfig, data_sizes, *, seed: int,
                     **session_kw) -> SwarmSession:
    """The swarm's `SwarmSession` on the engine backend: every site starts
    from the same adapters with fresh AdamW state, the base shared. Weights
    come from ``seed``."""
    if swarm_cfg.payload != "lora":
        raise ValueError('an LM LoRA swarm exchanges adapters only: '
                         'payload="lora"')
    train_step, eval_fn = make_fns(model_cfg, train_cfg)
    with tracing.span("session.build"):
        base, adapters = init_base_and_adapters(
            model_cfg, jax.random.key(seed), swarm_cfg.lora_rank,
            swarm_cfg.lora_alpha)
        tracing.annotate(
            model=model_cfg.name, layer_kinds=list(model_cfg.layer_kinds),
            base_bytes=sum(x.nbytes for x in jax.tree.leaves(base)),
            adapters=sum(x.size for x in adapters.values()))
        return SwarmSession(swarm_cfg, train_step, eval_fn, params=adapters,
                            opt_state=adamw_init(adapters),
                            data_sizes=data_sizes, seed=seed, shared=base,
                            **session_kw)
