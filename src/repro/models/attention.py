"""Grouped-query attention with RoPE, sliding-window masking and KV cache.

Used by every attention-bearing family (dense, moe, hybrid, vlm, audio).
Pure jnp by default (this is the path the multi-pod dry-run lowers); the
Pallas flash kernel in ``repro.kernels`` is an opt-in drop-in for TPU.
``cfg.use_rope`` off leaves positions out of q and k (Granite's "nope"),
``cfg.attention_multiplier`` replaces 1/sqrt(head_dim) as the softmax
scale, and ``cfg.attn_chunk`` computes a long sequence's scores that many
query rows at a time, each chunk recomputed in the backward pass.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import apply_rope, init_linear, linear
from repro.sharding.rules import axis_size, logical_shard

NEG_INF = -1e30


def init_attention(key, cfg: ModelConfig, *, n_heads=None, n_kv_heads=None):
    nh = n_heads or cfg.n_heads
    nkv = n_kv_heads or cfg.n_kv_heads
    d, hd = cfg.d_model, cfg.head_dim
    ks = jax.random.split(key, 4)
    return {
        "q": init_linear(ks[0], d, nh * hd, cfg),
        "k": init_linear(ks[1], d, nkv * hd, cfg),
        "v": init_linear(ks[2], d, nkv * hd, cfg),
        "o": init_linear(ks[3], nh * hd, d, cfg),
    }


def make_cache(cfg: ModelConfig, batch: int, max_len: int, dtype, *, n_kv_heads=None):
    nkv = n_kv_heads or cfg.n_kv_heads
    shape = (batch, max_len, nkv, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _gqa_scores(q, k, scale=0.0):
    """q [B,S,nh,hd], k [B,T,nkv,hd] -> scores [B,nkv,g,S,T] (g = nh // nkv),
    times ``scale`` (0: divided by sqrt(hd))."""
    b, s, nh, hd = q.shape
    nkv = k.shape[2]
    g = nh // nkv
    qg = q.reshape(b, s, nkv, g, hd)
    qk = jnp.einsum("bskgh,btkh->bkgst", qg, k)
    if scale:
        return qk * jnp.asarray(scale, q.dtype)
    return qk / jnp.sqrt(hd).astype(q.dtype)


def _gqa_out(probs, v):
    """probs [B,nkv,g,S,T], v [B,T,nkv,hd] -> [B,S,nh,hd]."""
    b, nkv, g, s, t = probs.shape
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v)
    return out.reshape(b, s, nkv * g, -1)


def attention(
    p,
    x,
    cfg: ModelConfig,
    *,
    positions,                      # [B, S] query positions
    causal: bool = True,
    window: int = 0,                # 0 = full
    cache: Optional[dict] = None,   # decode: KV cache dict
    cache_pos=None,                 # [] scalar — write offset into cache
    kv_x=None,                      # cross-attn: encoder output
    kv_positions=None,
    n_heads=None,
    n_kv_heads=None,
):
    """Returns (output [B,S,D], updated_cache)."""
    nh = n_heads or cfg.n_heads
    nkv = n_kv_heads or cfg.n_kv_heads
    hd = cfg.head_dim
    b, s, _ = x.shape

    q = linear(p["q"], x).reshape(b, s, nh, hd)
    src = x if kv_x is None else kv_x
    k = linear(p["k"], src).reshape(b, src.shape[1], nkv, hd)
    v = linear(p["v"], src).reshape(b, src.shape[1], nkv, hd)

    is_cross = kv_x is not None
    if not is_cross and cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        kp = positions if kv_positions is None else kv_positions
        k = apply_rope(k, kp, cfg.rope_theta)

    # Sharding scheme: head-parallel when the KV heads divide the model axis
    # (k/v/q all sharded on heads, zero attention collectives); otherwise
    # sequence-parallel on the query side (q/scores sharded over model via
    # the q-seq dim, k/v replicated over model and all-gathered per layer) —
    # this stays even for ANY head count, incl. GQA kv=8 on a 16-way axis.
    msize = axis_size("heads")
    head_parallel = msize > 1 and nkv % msize == 0
    if head_parallel:
        q = logical_shard(q, "batch", "seq", "heads", None)
        k = logical_shard(k, "batch", "seq", "kv_heads", None)
        v = logical_shard(v, "batch", "seq", "kv_heads", None)
    elif s > 1:
        q = logical_shard(q, "batch", "attn_seq", None, None)
        k = logical_shard(k, "batch", None, None, None)
        v = logical_shard(v, "batch", None, None, None)

    if cache is not None and not is_cross:
        # decode: align the new K/V with the CACHE's layout before the
        # in-place update — otherwise GSPMD reshards (re-gathers) the whole
        # multi-GB cache every step to match the unconstrained update
        kv_div = axis_size("kv_heads") > 1 and nkv % axis_size("kv_heads") == 0
        hd_div = axis_size("head_dim") > 1 and hd % axis_size("head_dim") == 0
        if s == 1 and kv_div:  # single-token decode only (prefill conflicts
            k = logical_shard(k, "batch", None, "kv_heads", None)  # with the
            v = logical_shard(v, "batch", None, "kv_heads", None)  # seq path)
        elif s == 1 and hd_div:
            k = logical_shard(k, "batch", None, None, "head_dim")
            v = logical_shard(v, "batch", None, None, "head_dim")
            # q must match, or GSPMD all-gathers the whole cache per layer
            # to run the scores contraction unsharded (137 GB/step on 104B)
            q = logical_shard(q, "batch", None, None, "head_dim")
        # append this step's K/V at cache_pos, attend over prefix
        k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), cache_pos, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), cache_pos, axis=1)
        cache = {"k": k_cache, "v": v_cache}
        k, v = k_cache.astype(x.dtype), v_cache.astype(x.dtype)
        key_positions = jnp.arange(k.shape[1])[None, :]  # [1, T]
    else:
        key_positions = (positions if kv_positions is None else kv_positions)
        if key_positions.ndim == 1:
            key_positions = key_positions[None, :]

    def attend(q, positions):
        scores = _gqa_scores(q, k, cfg.attention_multiplier)  # [B,nkv,g,S,T]
        s = q.shape[1]
        if head_parallel:
            scores = logical_shard(scores, "batch", "kv_heads", None, None,
                                   None)
        elif s > 1:
            scores = logical_shard(scores, "batch", None, None, "attn_seq",
                                   None)
        qpos = positions[:, None, None, :, None]          # [B,1,1,S,1]
        kpos = key_positions[:, None, None, None, :]      # [B,1,1,1,T]
        if causal and not is_cross:
            # `window` may be a traced per-layer scalar (scan-over-layers);
            # 0 = full
            w = jnp.asarray(window, jnp.int32)
            w_eff = jnp.where(w > 0, w, jnp.int32(2**30))
            mask = (kpos <= qpos) & (kpos > qpos - w_eff)
        else:
            mask = jnp.ones(scores.shape[-2:], bool)[None, None, None, :, :]
        scores = jnp.where(mask, scores.astype(jnp.float32), NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        return _gqa_out(probs, v)  # [B,S,nh,hd]

    c = cfg.attn_chunk
    if cache is None and c and s > c and s % c == 0:
        def rows(t):  # [B, S, ...] -> [S / c, B, c, ...]
            return jnp.moveaxis(t.reshape(b, s // c, c, *t.shape[2:]), 1, 0)

        pos = jnp.broadcast_to(positions, (b, s))
        out = jax.lax.map(lambda a: jax.checkpoint(attend)(*a),
                          (rows(q), rows(pos)))
        out = jnp.moveaxis(out, 0, 1).reshape(b, s, nh, hd)
    else:
        out = attend(q, positions)
    if head_parallel:
        out = logical_shard(out, "batch", "seq", "heads", None)
    elif s > 1:
        out = logical_shard(out, "batch", "attn_seq", None, None)
    y = linear(p["o"], out.reshape(b, s, nh * hd))
    return y, cache
