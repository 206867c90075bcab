"""Plain float32 reference of an interleaved Mamba-2 / attention stack
(``ModelConfig.layer_kinds``, Granite-4.0-H), for the tests.

Straight `jax.numpy`, one layer at a time, every product at HIGHEST
precision, no cache, no chunking, no kernels. It reads the program's
parameter pytree by name (``params["layers"]``: one stacked entry per run
of consecutive layers of one kind; LoRA factors beside a projection's
``w``) and shares none of its code. The layer equations follow HF's
``GraniteMoeHybrid*`` classes:

  x = E[tokens] * embedding_multiplier
  x = x + r * mixer(rmsnorm(x));  x = x + r * W_down(silu(a) * b),
      [a, b] = W_gate_up(rmsnorm(x))          (r: residual_multiplier)
  Mamba-2: [z, xBC, dt] = in_proj(h); xBC = silu(conv1d_causal(xBC) + bias);
      S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T,  y_t = C_t S_t + D x_t,
      dt = softplus(dt + dt_bias), A = -exp(A_log);
      out_proj(rmsnorm(y * silu(z)))
  attention: causal GQA, softmax(q k^T * attention_multiplier), no RoPE
  logits = rmsnorm(x) E^T / logits_scaling

The SSD is the sequential recurrence over time steps, with
`jax.checkpoint` over segments of ``SEGMENT`` steps so that its backward
pass keeps one state per segment. A sequence that is not a multiple of
the segment is padded with steps of zero ``dt``, which leave the state as
it was.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

HI = jax.lax.Precision.HIGHEST
SEGMENT = 64
f32 = jnp.float32


def _dot(a, b):
    return jnp.matmul(a, b, precision=HI)


def _linear(p, x):
    y = _dot(x, p["w"].astype(f32))
    if "lora_A" in p:
        y = y + _dot(_dot(x, p["lora_A"].astype(f32)),
                     p["lora_B"].astype(f32)) * p["lora_scale"].astype(f32)
    if "b" in p:
        y = y + p["b"].astype(f32)
    return y


def _rms(scale, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(f32)


def ssd_sequential(x, dt, a_log, b, c, segment: int = SEGMENT):
    """The Mamba-2 recurrence one step at a time. x [S, H, P], dt [S, H]
    (after softplus), a_log [H], b and c [S, G, N] -> y [S, H, P] (without
    the D skip)."""
    s, h, _ = x.shape
    g = b.shape[1]
    a = -jnp.exp(a_log.astype(f32))
    pad = (-s) % segment
    if pad:
        x, dt, b, c = (jnp.pad(t, [(0, pad)] + [(0, 0)] * (t.ndim - 1))
                       for t in (x, dt, b, c))
    group = jnp.arange(h) // (h // g)            # each head's B/C group

    def step(state, inp):                         # state [H, P, N]
        x_t, dt_t, b_t, c_t = inp
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[group][:, None, :])
        return state, jnp.einsum("hpn,hn->hp", state, c_t[group],
                                 precision=HI)

    @jax.checkpoint
    def seg(state, inp):
        return jax.lax.scan(step, state, inp)

    def rows(t):
        return t.reshape(-1, segment, *t.shape[1:])

    state0 = jnp.zeros((h, x.shape[2], b.shape[2]), f32)
    _, y = jax.lax.scan(seg, state0, tuple(map(rows, (x, dt, b, c))))
    return y.reshape(-1, h, x.shape[2])[:s]


def _mamba(p, h, cfg: ModelConfig):
    di, nh, n, g = cfg.d_inner, cfg.n_ssm_heads, cfg.ssm_state, cfg.ssm_groups
    proj = _linear(p["in_proj"], h)
    z, xbc, dt = jnp.split(proj, [di, 2 * di + 2 * g * n], axis=-1)
    w = p["conv"]["w"].astype(f32)                         # [W, C]
    width = w.shape[0]
    full = jnp.pad(xbc, ((width - 1, 0), (0, 0)))
    conv = sum(full[i:i + xbc.shape[0]] * w[i] for i in range(width))
    xbc = jax.nn.silu(conv + p["conv"]["b"].astype(f32))
    x, b, c = jnp.split(xbc, [di, di + g * n], axis=-1)
    s = x.shape[0]
    x = x.reshape(s, nh, di // nh)
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(f32))
    y = ssd_sequential(x, dt, p["A_log"], b.reshape(s, g, n),
                       c.reshape(s, g, n))
    y = (y + x * p["D"].astype(f32)[:, None]).reshape(s, di)
    y = _rms(p["norm_scale"], y * jax.nn.silu(z), cfg.norm_eps)
    return _linear(p["out_proj"], y)


def _attention(p, h, cfg: ModelConfig):
    if cfg.use_rope:
        raise ValueError("the reference has no rotary embedding")
    s, nh, nkv, hd = h.shape[0], cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = _linear(p["q"], h).reshape(s, nh, hd)
    k = _linear(p["k"], h).reshape(s, nkv, hd)
    v = _linear(p["v"], h).reshape(s, nkv, hd)
    kv = jnp.arange(nh) // (nh // nkv)                     # each head's KV
    scale = cfg.attention_multiplier or 1.0 / hd ** 0.5
    scores = jnp.einsum("qhd,khd->hqk", q, k[:, kv], precision=HI) * scale
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hqk,khd->qhd", probs, v[:, kv], precision=HI)
    return _linear(p["o"], out.reshape(s, nh * hd))


def _mlp(p, h):
    gate, up = jnp.split(_linear(p["gate_up"], h), 2, axis=-1)
    return _linear(p["down"], jax.nn.silu(gate) * up)


def layers_of(params, cfg: ModelConfig):
    """``[(kind, params of one layer)]`` in the stack's order."""
    out = []
    for run in params["layers"]:
        kind = "mamba" if "ssm" in run else "attention"
        n = jax.tree.leaves(run)[0].shape[0]
        out += [(kind, jax.tree.map(lambda a, i=i: a[i], run))
                for i in range(n)]
    if [k for k, _ in out] != list(cfg.layer_kinds):
        raise ValueError("parameters do not follow cfg.layer_kinds")
    return out


def forward(params, cfg: ModelConfig, tokens):
    """tokens [S] -> logits [S, V], float32."""
    table = params["embed_tied"]["table"].astype(f32)
    x = table[tokens] * cfg.embedding_multiplier
    r, eps = cfg.residual_multiplier, cfg.norm_eps
    for kind, p in layers_of(params, cfg):
        def layer(x, p, kind=kind):
            if kind == "mamba":
                y = _mamba(p["ssm"], _rms(p["ssm_norm"]["scale"], x, eps),
                           cfg)
            else:
                y = _attention(p["attn"],
                               _rms(p["attn_norm"]["scale"], x, eps), cfg)
            x = x + r * y
            return x + r * _mlp(p["mlp"], _rms(p["mlp_norm"]["scale"], x,
                                               eps))

        x = jax.checkpoint(layer)(x, p)
    x = _rms(params["final_norm"]["scale"], x, eps)
    logits = _dot(x, table.T) / cfg.logits_scaling
    return logits[:, :cfg.vocab_size]


def loss(params, cfg: ModelConfig, tokens, labels):
    """Mean next-token cross-entropy of one sequence."""
    logits = forward(params, cfg, tokens)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


def adapter_grad(params, cfg: ModelConfig, tokens, labels):
    """(loss, gradient of the loss with respect to every ``lora_A`` and
    ``lora_B`` leaf, the other leaves' gradients zero)."""
    def trained(path, _):
        return str(getattr(path[-1], "key", "")) in ("lora_A", "lora_B")

    mask = jax.tree_util.tree_map_with_path(trained, params)
    value, g = jax.value_and_grad(loss)(params, cfg, tokens, labels)
    return value, jax.tree.map(lambda gi, m: gi if m else jnp.zeros_like(gi),
                               g, mask)
