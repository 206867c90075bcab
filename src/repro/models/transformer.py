"""Decoder-only LM assembly covering dense / moe / ssm / hybrid / vlm families.

Layers are **stacked and scanned** (`jax.lax.scan` over a leading L axis) so
that 60+-layer production configs lower and compile quickly for the 80-way
dry-run matrix. Mixed per-layer attention windows (sliding-window layers with
periodic full-attention layers, à la Hymba) are carried as a scanned int array.

An interleaved stack (``cfg.layer_kinds``, Granite-4.0-H) holds each run of
consecutive layers of one kind stacked (``params["layers"]`` is a list,
one entry per run of `layer_runs`) and scans each run in turn. Its layers
carry device names: ``lm.mixer`` (the Mamba-2 mixer, with the scan itself
under ``lm.ssd``), ``lm.attn``, ``lm.mlp``; the final norm, logits and loss
are ``lm.head``.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.attention import attention, init_attention, make_cache
from repro.models.layers import (
    dtype_of, embed, init_embedding, init_mlp, init_norm, mlp, rmsnorm, unembed,
    init_linear, linear, softmax_xent,
)
from repro.models.moe import init_moe, moe
from repro.models.ssm import init_ssm, make_ssm_state, ssm_block
from repro.sharding.rules import constrain_block_params, logical_shard


# ---------------------------------------------------------------------------
# per-layer block
# ---------------------------------------------------------------------------

def init_block(key, cfg: ModelConfig):
    ks = jax.random.split(key, 8)
    fam = cfg.family
    if fam == "ssm":
        return {"ssm_norm": init_norm(cfg.d_model, cfg), "ssm": init_ssm(ks[0], cfg)}
    p = {
        "attn_norm": init_norm(cfg.d_model, cfg),
        "attn": init_attention(ks[0], cfg),
        "mlp_norm": init_norm(cfg.d_model, cfg),
    }
    if fam == "moe":
        p["moe"] = init_moe(ks[1], cfg)
    else:
        p["mlp"] = init_mlp(ks[1], cfg)
    if fam == "hybrid":
        # Hymba-style parallel heads: attn ∥ ssm within the same block,
        # combined with learnable per-branch output scales (β).
        p["ssm"] = init_ssm(ks[2], cfg)
        p["beta_attn"] = jnp.ones((cfg.d_model,), dtype_of(cfg.param_dtype))
        p["beta_ssm"] = jnp.ones((cfg.d_model,), dtype_of(cfg.param_dtype))
    return p


def block_apply(p, x, cfg: ModelConfig, *, positions, window, cache, cache_pos):
    """One residual block. cache is {} (train/prefill) or the layer's state."""
    aux = jnp.float32(0.0)
    new_cache = {}
    has_cache = bool(cache)
    fam = cfg.family

    if fam == "ssm":
        h = rmsnorm(p["ssm_norm"], x, cfg.norm_eps)
        y, st = ssm_block(p["ssm"], h, cfg,
                          state={"ssd": cache["ssd"], "conv": cache["conv"]} if has_cache else None)
        if has_cache:
            new_cache = st
        return x + y, aux, new_cache

    h = rmsnorm(p["attn_norm"], x, cfg.norm_eps)
    kv_cache = {"k": cache["k"], "v": cache["v"]} if has_cache else None
    a, kv = attention(p["attn"], h, cfg, positions=positions, window=window,
                      cache=kv_cache, cache_pos=cache_pos)
    if fam == "hybrid":
        s, st = ssm_block(p["ssm"], h, cfg,
                          state={"ssd": cache["ssd"], "conv": cache["conv"]} if has_cache else None)
        mix = 0.5 * (a * p["beta_attn"].astype(a.dtype)
                     + s * p["beta_ssm"].astype(a.dtype))
        x = x + mix
        if has_cache:
            new_cache = dict(st)
    else:
        x = x + a
    if has_cache:
        new_cache.update(kv)

    h = rmsnorm(p["mlp_norm"], x, cfg.norm_eps)
    if fam == "moe":
        y, aux = moe(p["moe"], h, cfg)
    else:
        y = mlp(p["mlp"], h, cfg)
    return x + y, aux, new_cache


def layer_runs(cfg: ModelConfig):
    """``(kind, first layer, length)`` of each run of consecutive layers of
    one kind in ``cfg.layer_kinds``."""
    runs = []
    for i, kind in enumerate(cfg.layer_kinds):
        if runs and runs[-1][0] == kind:
            runs[-1][2] += 1
        else:
            runs.append([kind, i, 1])
    return [tuple(r) for r in runs]


def init_kind_layer(key, cfg: ModelConfig, kind: str):
    """One layer of an interleaved stack: the mixer of ``kind``, then the
    MLP, each behind its RMSNorm."""
    ks = jax.random.split(key, 2)
    if kind == "mamba":
        mixer = {"ssm_norm": init_norm(cfg.d_model, cfg),
                 "ssm": init_ssm(ks[0], cfg)}
    else:
        mixer = {"attn_norm": init_norm(cfg.d_model, cfg),
                 "attn": init_attention(ks[0], cfg)}
    return dict(mixer, mlp_norm=init_norm(cfg.d_model, cfg),
                mlp=init_mlp(ks[1], cfg))


def kind_layer_apply(p, x, cfg: ModelConfig, kind: str, *, positions, cache,
                     cache_pos):
    """``x + r * mixer(norm(x))``, then ``x + r * mlp(norm(x))`` with r the
    residual multiplier. ``cache`` is {} (train/prefill) or the layer's
    state: SSM state for a Mamba-2 layer, KV for an attention layer."""
    r = cfg.residual_multiplier
    if kind == "mamba":
        with jax.named_scope("lm.mixer"):
            h = rmsnorm(p["ssm_norm"], x, cfg.norm_eps)
            y, new_cache = ssm_block(p["ssm"], h, cfg, state=cache or None)
    else:
        with jax.named_scope("lm.attn"):
            h = rmsnorm(p["attn_norm"], x, cfg.norm_eps)
            y, new_cache = attention(p["attn"], h, cfg, positions=positions,
                                     cache=cache or None, cache_pos=cache_pos)
    x = x + y * r
    with jax.named_scope("lm.mlp"):
        y = mlp(p["mlp"], rmsnorm(p["mlp_norm"], x, cfg.norm_eps), cfg)
    return x + y * r, (new_cache if cache else {})


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------

def layer_windows(cfg: ModelConfig) -> np.ndarray:
    """Per-layer attention window (0 = full attention)."""
    w = np.full((cfg.n_layers,), cfg.sliding_window, np.int32)
    if cfg.sliding_window and cfg.attn_every:
        w[:: cfg.attn_every] = 0  # periodic global-attention layers
    return w


def init_lm(key, cfg: ModelConfig):
    ks = jax.random.split(key, 4)
    lkeys = jax.random.split(ks[0], cfg.n_layers)
    emb_key = "embed_tied" if cfg.tie_embeddings else "embed"
    if cfg.layer_kinds:
        layers = [jax.vmap(lambda k, kind=kind: init_kind_layer(k, cfg, kind))(
            lkeys[a:a + n]) for kind, a, n in layer_runs(cfg)]
    else:
        layers = jax.vmap(lambda k: init_block(k, cfg))(lkeys)
    params = {
        emb_key: init_embedding(ks[1], cfg.padded_vocab, cfg.d_model, cfg),
        "layers": layers,
        "final_norm": init_norm(cfg.d_model, cfg),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"w": init_linear(ks[2], cfg.d_model, cfg.padded_vocab, cfg, bias=False)["w"]}
    if cfg.frontend_dim:  # vlm projector (frontend itself is a stub)
        pk = jax.random.split(ks[3], 2)
        params["projector"] = {
            "fc1": init_linear(pk[0], cfg.frontend_dim, cfg.d_model, cfg, bias=True),
            "fc2": init_linear(pk[1], cfg.d_model, cfg.d_model, cfg, bias=True),
        }
    return params


def make_lm_cache(cfg: ModelConfig, batch: int, max_len: int):
    """Stacked [L, ...] decode state for scan-over-layers (an interleaved
    stack: a list, one stacked state per run of `layer_runs`)."""
    dtype = dtype_of(cfg.compute_dtype)
    if cfg.layer_kinds:
        def state(kind):
            return (make_ssm_state(cfg, batch, dtype) if kind == "mamba"
                    else make_cache(cfg, batch, max_len, dtype))

        return [jax.vmap(lambda _, kind=kind: state(kind))(jnp.arange(n))
                for kind, _, n in layer_runs(cfg)]

    def one(_):
        c = {}
        if cfg.family != "ssm":
            c.update(make_cache(cfg, batch, max_len, dtype))
        if cfg.family in ("ssm", "hybrid"):
            c.update(make_ssm_state(cfg, batch, dtype))
        return c

    return jax.vmap(one)(jnp.arange(cfg.n_layers))


def project_frontend(params, cfg: ModelConfig, feats):
    """VLM/audio stub embeddings -> d_model via 2-layer MLP projector."""
    h = jax.nn.gelu(linear(params["projector"]["fc1"], feats))
    return linear(params["projector"]["fc2"], h)


def lm_hidden(
    params,
    cfg: ModelConfig,
    tokens=None,            # [B,S] int32
    *,
    embeds=None,            # [B,S,D] pre-embedded (vlm prefix path)
    caches=None,            # stacked decode state or None
    cache_pos=None,         # scalar int32 write offset (decode)
    remat: bool = False,
):
    """The stack up to the final norm: (hidden [B,S,D], aux scalar,
    new_caches)."""
    compute_dtype = dtype_of(cfg.compute_dtype)
    emb_p = params["embed_tied"] if cfg.tie_embeddings else params["embed"]
    if embeds is None:
        x = embed(emb_p, tokens, compute_dtype)
    else:
        x = embeds.astype(compute_dtype)
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    b, s = x.shape[:2]
    if cache_pos is not None:
        positions = cache_pos + jnp.arange(s, dtype=jnp.int32)[None, :]
        positions = jnp.broadcast_to(positions, (b, s))
    else:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))
    x = logical_shard(x, "batch", "res_seq", "embed")
    unroll = cfg.n_layers if cfg.unroll_layers else 1

    if cfg.layer_kinds:
        aux, new_caches = jnp.float32(0.0), []
        for r, (kind, _, n) in enumerate(layer_runs(cfg)):
            def run_body(h, inp, kind=kind):
                lp, cache = inp
                lp = constrain_block_params(lp)
                h, new_cache = kind_layer_apply(
                    lp, h, cfg, kind, positions=positions, cache=cache,
                    cache_pos=cache_pos)
                return logical_shard(h, "batch", "res_seq", "embed"), new_cache

            if remat:
                run_body = jax.checkpoint(run_body, prevent_cse=False)
            x, new_cache = jax.lax.scan(
                run_body, x, (params["layers"][r],
                              caches[r] if caches is not None else {}),
                unroll=min(unroll, n))
            new_caches.append(new_cache)
    else:
        windows = jnp.asarray(layer_windows(cfg))

        def body(carry, inp):
            h, aux = carry
            lp, win, cache = inp
            # pinning layer params here makes their scan-accumulated
            # GRADIENTS inherit the same sharding (w_s_c transposes to the
            # cotangent)
            lp = constrain_block_params(lp)
            h, aux_i, new_cache = block_apply(
                lp, h, cfg, positions=positions, window=win,
                cache=cache, cache_pos=cache_pos)
            # Megatron-SP residual pin: saved (remat) activations shard over
            # the model axis via the sequence dim — 16x less live memory at
            # TP=16
            h = logical_shard(h, "batch", "res_seq", "embed")
            return (h, aux + aux_i), new_cache

        if remat:
            body = jax.checkpoint(body, prevent_cse=False)

        xs = (params["layers"], windows, caches if caches is not None else {})
        (x, aux), new_caches = jax.lax.scan(body, (x, jnp.float32(0.0)), xs,
                                            unroll=unroll)
    if caches is None:
        new_caches = None
    with jax.named_scope("lm.head"):
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return x, aux, new_caches


def lm_logits(params, cfg: ModelConfig, x):
    """Final-normed hidden [..., D] -> logits [..., V]: the (tied) head,
    divided by ``cfg.logits_scaling``, soft-capped, the vocabulary's padding
    masked; [B, S, V] logits pinned to the batch, sequence and vocabulary
    axes."""
    if cfg.tie_embeddings:
        logits = unembed(params["embed_tied"], x)
    else:
        logits = x @ params["lm_head"]["w"].astype(x.dtype)
    if cfg.logits_scaling != 1.0:
        logits = logits / jnp.asarray(cfg.logits_scaling, logits.dtype)
    if cfg.logit_softcap:
        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    if cfg.padded_vocab != cfg.vocab_size:  # mask the padding columns
        pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
        logits = jnp.where(pad_mask, jnp.float32(-1e30).astype(logits.dtype), logits)
    if logits.ndim == 3:
        logits = logical_shard(logits, "batch", "seq", "vocab")
    return logits


def _token_chunks(per_chunk, x, labels, mask, chunk: int, checkpoint: bool):
    """Sum of ``per_chunk(x, labels, mask)`` over ``chunk`` tokens at a
    time (0: all at once; the tokens padded with masked ones to whole
    chunks) over the sum of ``mask``. ``checkpoint``: each chunk's logits
    are recomputed in the backward pass, never stored."""
    d = x.shape[-1]
    x, labels = x.reshape(-1, d), labels.reshape(-1)
    mask = (jnp.ones(labels.shape, jnp.float32) if mask is None
            else mask.reshape(-1).astype(jnp.float32))
    c = chunk or labels.shape[0]
    pad = (-labels.shape[0]) % c
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        labels, mask = jnp.pad(labels, (0, pad)), jnp.pad(mask, (0, pad))
    fn = jax.checkpoint(per_chunk) if checkpoint else per_chunk

    def body(total, chunk):
        return total + fn(*chunk), None

    total, _ = jax.lax.scan(body, jnp.float32(0.0),
                            (x.reshape(-1, c, d), labels.reshape(-1, c),
                             mask.reshape(-1, c)))
    return total / jnp.maximum(jnp.sum(mask), 1.0)


def lm_xent(params, cfg: ModelConfig, x, labels, mask=None):
    """Token-mean cross-entropy of the logits of final-normed ``x``; with
    ``cfg.loss_chunk`` the logits exist one chunk of tokens at a time,
    else at once as [B, S, V], which keeps the batch's sharding."""
    def nll(xc, yc, mc):
        logits = lm_logits(params, cfg, xc).astype(jnp.float32)
        gold = jnp.take_along_axis(logits, yc[:, None], axis=-1)[:, 0]
        return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - gold) * mc)

    with jax.named_scope("lm.head"):
        if not cfg.loss_chunk:
            return softmax_xent(lm_logits(params, cfg, x), labels, mask)
        return _token_chunks(nll, x, labels, mask, cfg.loss_chunk,
                             checkpoint=True)


def lm_accuracy(params, cfg: ModelConfig, x, labels, mask=None):
    """Share of the (unmasked) tokens whose largest logit is the label, the
    logits ``cfg.loss_chunk`` tokens at a time."""
    def hits(xc, yc, mc):
        pred = jnp.argmax(lm_logits(params, cfg, xc), axis=-1)
        return jnp.sum((pred == yc).astype(jnp.float32) * mc)

    with jax.named_scope("lm.head"):
        return _token_chunks(hits, x, labels, mask, cfg.loss_chunk,
                             checkpoint=False)


def forward_lm(
    params,
    cfg: ModelConfig,
    tokens=None,            # [B,S] int32
    *,
    embeds=None,            # [B,S,D] pre-embedded (vlm prefix path)
    caches=None,            # stacked decode state or None
    cache_pos=None,         # scalar int32 write offset (decode)
    remat: bool = False,
):
    """Returns (logits [B,S,V], aux scalar, new_caches)."""
    x, aux, new_caches = lm_hidden(params, cfg, tokens, embeds=embeds,
                                   caches=caches, cache_pos=cache_pos,
                                   remat=remat)
    with jax.named_scope("lm.head"):
        logits = lm_logits(params, cfg, x)
    return logits, aux, new_caches
