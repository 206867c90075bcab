"""Shared pure-JAX layer primitives for the model zoo.

Everything is functional: ``init_*`` builds a param sub-pytree (nested dict of
jnp arrays), the matching apply function consumes it. Params carry no framework
wrapper so the swarm merge layer (core/) can treat any model uniformly.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig


def dtype_of(name: str):
    return jnp.dtype(name)


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def dense_init(key, in_dim: int, out_dim: int, dtype, scale: Optional[float] = None):
    scale = scale if scale is not None else (1.0 / jnp.sqrt(in_dim))
    return (jax.random.normal(key, (in_dim, out_dim)) * scale).astype(dtype)


def init_linear(key, in_dim, out_dim, cfg: ModelConfig, bias: Optional[bool] = None):
    dtype = dtype_of(cfg.param_dtype)
    use_bias = cfg.use_bias if bias is None else bias
    p = {"w": dense_init(key, in_dim, out_dim, dtype)}
    if use_bias:
        p["b"] = jnp.zeros((out_dim,), dtype)
    return p


def linear(p, x):
    y = x @ p["w"].astype(x.dtype)
    if "lora_A" in p:  # LoRA adapter (injected by repro.core.lora)
        scale = p["lora_scale"].astype(x.dtype)
        y = y + ((x @ p["lora_A"].astype(x.dtype)) @ p["lora_B"].astype(x.dtype)) * scale
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


def init_norm(dim: int, cfg: ModelConfig):
    return {"scale": jnp.ones((dim,), dtype_of(cfg.param_dtype))}


def rmsnorm(p, x, eps: float = 1e-5):
    orig = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * p["scale"].astype(jnp.float32)).astype(orig)


def init_embedding(key, vocab: int, dim: int, cfg: ModelConfig):
    dtype = dtype_of(cfg.param_dtype)
    return {"table": (jax.random.normal(key, (vocab, dim)) * 0.02).astype(dtype)}


def embed(p, ids, compute_dtype):
    return p["table"].astype(compute_dtype)[ids]


def unembed(p, x):
    return x @ p["table"].astype(x.dtype).T


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponent)  # [head_dim//2]


def apply_rope(x, positions, theta: float):
    """x: [..., seq, heads, head_dim]; positions: [..., seq]."""
    head_dim = x.shape[-1]
    freqs = rope_freqs(head_dim, theta)  # [hd/2]
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..., seq, hd/2]
    cos = jnp.cos(angles)[..., :, None, :]  # [..., seq, 1, hd/2]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP variants
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None):
    d, f = cfg.d_model, (d_ff or cfg.d_ff)
    ks = jax.random.split(key, 3)
    if cfg.activation == "swiglu_fused":
        return {
            "gate_up": init_linear(ks[0], d, 2 * f, cfg),
            "down": init_linear(ks[1], f, d, cfg),
        }
    if cfg.activation == "swiglu":
        return {
            "gate": init_linear(ks[0], d, f, cfg),
            "up": init_linear(ks[1], d, f, cfg),
            "down": init_linear(ks[2], f, d, cfg),
        }
    return {
        "up": init_linear(ks[0], d, f, cfg),
        "down": init_linear(ks[1], f, d, cfg),
    }


def mlp(p, x, cfg: ModelConfig):
    if cfg.activation == "swiglu_fused":
        gate, up = jnp.split(linear(p["gate_up"], x), 2, axis=-1)
        h = jax.nn.silu(gate) * up
    elif cfg.activation == "swiglu":
        h = jax.nn.silu(linear(p["gate"], x)) * linear(p["up"], x)
    elif cfg.activation == "sq_relu":  # nemotron-4: squared ReLU
        h = jnp.square(jax.nn.relu(linear(p["up"], x)))
    else:
        h = jax.nn.gelu(linear(p["up"], x))
    return linear(p["down"], h)


# ---------------------------------------------------------------------------
# losses / misc
# ---------------------------------------------------------------------------

def softmax_xent(logits, labels, mask=None):
    """Token-mean cross entropy. logits [..., V]; labels int [...]."""
    logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(
        logits.astype(jnp.float32), labels[..., None], axis=-1
    )[..., 0]
    nll = logz - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def count_params(params) -> int:
    return int(sum(p.size for p in jax.tree_util.tree_leaves(params)))
