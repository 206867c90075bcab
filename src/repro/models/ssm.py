"""Mamba-2 (SSD — state-space duality) block, chunked, pure jnp.

Follows the SSD formulation of arXiv:2405.21060: within a chunk the recurrence
is materialized as a decay-masked quadratic form (MXU-friendly); across chunks
a short scan carries the [H, P, N] state. The Pallas ``ssd_scan`` kernel in
``repro.kernels`` runs the same chunk schedule with explicit VMEM tiling, but
takes B and C per head ([B, S, H, N]), where ``ssd_chunked`` takes them per
group: a caller with fewer groups than heads repeats them for the kernel.
This module is the path the models lower.

Decode keeps an O(1) recurrent state — this is what makes ``long_500k``
natively sub-quadratic for the ssm/hybrid families.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import dense_init, dtype_of, init_linear, linear
from repro.sharding.rules import logical_shard


def _dims(cfg: ModelConfig):
    di = cfg.d_inner
    h = cfg.n_ssm_heads
    pdim = di // h
    n = cfg.ssm_state
    g = cfg.ssm_groups
    return di, h, pdim, n, g


def init_ssm(key, cfg: ModelConfig):
    d = cfg.d_model
    di, h, pdim, n, g = _dims(cfg)
    conv_dim = di + 2 * g * n
    dtype = dtype_of(cfg.param_dtype)
    ks = jax.random.split(key, 5)
    return {
        # z (gate), x, B, C, dt in one projection
        "in_proj": init_linear(ks[0], d, 2 * di + 2 * g * n + h, cfg),
        "conv": {
            "w": (jax.random.normal(ks[1], (cfg.conv_width, conv_dim)) * 0.1).astype(dtype),
            "b": jnp.zeros((conv_dim,), dtype),
        },
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, h)).astype(jnp.float32),
        "D": jnp.ones((h,), jnp.float32),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "norm_scale": jnp.ones((di,), dtype),
        "out_proj": init_linear(ks[2], di, d, cfg),
    }


def make_ssm_state(cfg: ModelConfig, batch: int, dtype):
    di, h, pdim, n, g = _dims(cfg)
    conv_dim = di + 2 * g * n
    return {
        "ssd": jnp.zeros((batch, h, pdim, n), jnp.float32),
        "conv": jnp.zeros((batch, cfg.conv_width - 1, conv_dim), dtype),
    }


def _split_proj(cfg, zxbcdt):
    di, h, pdim, n, g = _dims(cfg)
    z, xin, bmat, cmat, dt = jnp.split(
        zxbcdt, [di, 2 * di, 2 * di + g * n, 2 * di + 2 * g * n], axis=-1
    )
    return z, xin, bmat, cmat, dt


def _causal_conv(conv_p, u, prefix=None):
    """Depthwise causal conv. u [B,S,C]; prefix [B,W-1,C] for decode."""
    w = conv_p["w"].astype(u.dtype)          # [W, C]
    width = w.shape[0]
    if prefix is None:
        pad = jnp.zeros((u.shape[0], width - 1, u.shape[2]), u.dtype)
    else:
        pad = prefix.astype(u.dtype)
    full = jnp.concatenate([pad, u], axis=1)  # [B, S+W-1, C]
    out = sum(full[:, i : i + u.shape[1]] * w[i] for i in range(width))
    out = out + conv_p["b"].astype(u.dtype)
    return jax.nn.silu(out), full[:, -(width - 1):]


def _gated_norm(scale, y, z, eps):
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
    yf = y.astype(jnp.float32)
    var = jnp.mean(yf * yf, axis=-1, keepdims=True)
    return (yf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(y.dtype)


def ssd_chunked(x, dt, a_log, bmat, cmat, chunk: int):
    """Chunked SSD scan (pure jnp oracle).

    x    [B,S,H,P]   inputs per head
    dt   [B,S,H]     softplus'd step sizes
    a_log[H]         -exp(a_log) is the decay rate
    bmat [B,S,G,N]   input->state projection
    cmat [B,S,G,N]   state->output projection
    Returns y [B,S,H,P], final_state [B,H,P,N].

    B and C are never copied per head: heads are split as [G, R] (R = H/G
    heads share a group) wherever they meet B or C, so ``C·Bᵀ`` is computed
    once per group and broadcast over its heads.
    """
    b, s, h, pdim = x.shape
    g, n = bmat.shape[2], bmat.shape[3]
    rep = h // g
    nc = s // chunk
    dtype = x.dtype
    f32 = jnp.float32

    # per-step log decay
    dA = dt * (-jnp.exp(a_log.astype(f32)))                # [B,S,H] (<0)
    xdt = x * dt[..., None].astype(dtype)                  # weight input by dt

    def ch(t):  # reshape into chunks
        return t.reshape(b, nc, chunk, *t.shape[2:])

    xc = ch(xdt).astype(f32).reshape(b, nc, chunk, g, rep, pdim)
    bc, cc = ch(bmat).astype(f32), ch(cmat).astype(f32)   # [B,nc,L,G,N]

    cum = jnp.cumsum(ch(dA), axis=2)                       # [B,nc,L,H]
    # intra-chunk: decay-masked quadratic attention
    # L_mat[i,j] = exp(cum_i - cum_j) for i >= j else 0, laid out per head
    # as [B,nc,G,R,L,L] so that the group's scores broadcast over R
    cum_t = jnp.moveaxis(cum, 2, 3).reshape(b, nc, g, rep, chunk)
    diff = cum_t[..., :, None] - cum_t[..., None, :]       # [B,nc,G,R,L,L]
    ii = jnp.arange(chunk)
    causal = ii[:, None] >= ii[None, :]
    # mask BEFORE exp: exp of masked (positive) entries overflows and poisons
    # the backward pass with inf*0 = NaN
    lmat = jnp.exp(jnp.where(causal, diff, -1e30))
    scores = jnp.einsum("bclgn,bcmgn->bcglm", cc, bc)      # [B,nc,G,L,L]
    y_diag = jnp.einsum("bcgrlm,bcmgrp->bclgrp",
                        scores[:, :, :, None] * lmat, xc)

    # chunk-final states: sum_j exp(cum_last - cum_j) * B_j ⊗ x_j
    decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum)        # [B,nc,L,H]
    states = jnp.einsum("bclgn,bclgr,bclgrp->bcgrpn", bc,
                        decay_to_end.reshape(b, nc, chunk, g, rep), xc)

    # inter-chunk recurrence
    chunk_decay = jnp.exp(cum[:, :, -1, :])                # [B,nc,H]

    def step(carry, inp):
        st_in, dec = inp                                    # [B,H,P,N], [B,H]
        new = carry * dec[:, :, None, None] + st_in
        return new, carry                                   # emit state BEFORE chunk

    init = jnp.zeros((b, h, pdim, n), f32)
    final, prev_states = jax.lax.scan(
        step, init,
        (jnp.moveaxis(states.reshape(b, nc, h, pdim, n), 1, 0),
         jnp.moveaxis(chunk_decay, 1, 0)),
    )
    prev_states = jnp.moveaxis(prev_states, 0, 1)           # [B,nc,H,P,N]

    # inter-chunk contribution: C_t · (decay to t) · S_prev; C meets the
    # state before the decay, which would otherwise spread it over R heads
    y_off = jnp.einsum("bclgn,bcgrpn->bclgrp", cc,
                       prev_states.reshape(b, nc, g, rep, pdim, n))
    y_off = y_off * jnp.exp(cum).reshape(b, nc, chunk, g, rep, 1)

    y = (y_diag + y_off).reshape(b, s, h, pdim).astype(dtype)
    return y, final


def ssm_block(p, x, cfg: ModelConfig, *, state=None):
    """Full mamba2 mixer. x [B,S,D] -> (y [B,S,D], new_state or None)."""
    b, s, d = x.shape
    di, h, pdim, n, g = _dims(cfg)
    zxbcdt = linear(p["in_proj"], x)
    z, xin, bmat, cmat, dt = _split_proj(cfg, zxbcdt)

    conv_in = jnp.concatenate([xin, bmat, cmat], axis=-1)
    decode = state is not None and s == 1
    conv_prefix = state["conv"] if decode else None
    conv_out, new_conv = _causal_conv(p["conv"], conv_in, conv_prefix)
    xin, bmat, cmat = jnp.split(conv_out, [di, di + g * n], axis=-1)

    xh = xin.reshape(b, s, h, pdim)
    xh = logical_shard(xh, "batch", "seq", "ff", None)
    bm = bmat.reshape(b, s, g, n)
    cm = cmat.reshape(b, s, g, n)
    dtv = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])   # [B,S,H]

    if decode:
        # O(1) recurrent update
        dA = jnp.exp(dtv[:, 0] * (-jnp.exp(p["A_log"])))           # [B,H]
        bm1 = jnp.repeat(bm[:, 0], h // g, axis=1)                 # [B,H,N]
        cm1 = jnp.repeat(cm[:, 0], h // g, axis=1)
        xdt = (xh[:, 0] * dtv[:, 0, :, None]).astype(jnp.float32)  # [B,H,P]
        new_ssd = state["ssd"] * dA[:, :, None, None] + \
            jnp.einsum("bhp,bhn->bhpn", xdt, bm1.astype(jnp.float32))
        y = jnp.einsum("bhpn,bhn->bhp", new_ssd, cm1.astype(jnp.float32))
        y = y[:, None].astype(x.dtype)                              # [B,1,H,P]
        new_state = {"ssd": new_ssd, "conv": new_conv}
    else:
        chunk = min(cfg.ssm_chunk, s)
        pad = (-s) % chunk
        if pad:
            padded = lambda t: jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
            xh, bm, cm, dtv = padded(xh), padded(bm), padded(cm), padded(dtv)
        with jax.named_scope("lm.ssd"):
            y, final = ssd_chunked(xh, dtv, p["A_log"], bm, cm, chunk)
        y = y[:, :s]
        new_state = {"ssd": final, "conv": new_conv} if state is not None else None

    y = y + xh[:, :s] * p["D"].astype(x.dtype)[None, None, :, None]
    y = y.reshape(b, s, di)
    y = _gated_norm(p["norm_scale"], y, z, cfg.norm_eps)
    return linear(p["out_proj"], y.astype(x.dtype)), new_state
