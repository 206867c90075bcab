"""Pallas TPU kernel: fused base + LoRA matmul.

  y = x @ W + scale · (x @ A) @ B        x:[M,K] W:[K,N] A:[K,r] B:[r,N]

The paper makes LoRA adapters the permanent exchange payload, so swarm
fine-tuning runs this everywhere. Unfused, XLA materializes xA [M,r] and
xA@B [M,N] through HBM; the kernel keeps both low-rank intermediates in VMEM
and accumulates them into the same MXU tile pass as the base matmul:

  grid (M/bm, N/bn, K/bk), K innermost (sequential). Scratch: acc [bm,bn]
  (base+total) and xa [bm,r] (low-rank running sum). On the last K step the
  r-rank correction xa @ B_tile lands on the MXU and the tile is written once.

Tile defaults are MXU-aligned (128 multiples); r stays whole (r ≤ 128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _lora_kernel(x_ref, w_ref, a_ref, b_ref, scale_ref, o_ref,
                 acc_ref, xa_ref, *, n_k: int):
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        xa_ref[...] = jnp.zeros_like(xa_ref)

    x = x_ref[...]
    acc_ref[...] += jnp.dot(x, w_ref[...],
                            preferred_element_type=jnp.float32)
    xa_ref[...] += jnp.dot(x, a_ref[...],
                           preferred_element_type=jnp.float32)

    @pl.when(k_step == n_k - 1)
    def _finish():
        scale = scale_ref[0]
        low_rank = jnp.dot(xa_ref[...].astype(x.dtype), b_ref[...],
                           preferred_element_type=jnp.float32)
        o_ref[...] = (acc_ref[...] + scale * low_rank).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret"))
def lora_matmul(x, w, a, b, scale, *, bm: int = 128, bn: int = 128,
                bk: int = 512, interpret: bool = False):
    m, k = x.shape
    _, n = w.shape
    r = a.shape[1]
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    if m % bm or n % bn or k % bk:
        raise ValueError(f"dims ({m},{n},{k}) must divide tiles ({bm},{bn},{bk})")
    grid = (m // bm, n // bn, k // bk)

    return pl.pallas_call(
        functools.partial(_lora_kernel, n_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk, r), lambda i, j, kk: (kk, 0)),
            pl.BlockSpec((r, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, r), jnp.float32),
        ],
        interpret=interpret,
    )(x, w, a, b, jnp.asarray(scale, jnp.float32).reshape(1))


def lora_apply(x, w, a, b, scale, *, interpret=None):
    """LoRA'd linear ``x @ W + scale·(x @ A) @ B`` with automatic dispatch.

    On TPU with MXU-tileable dims this is the fused Pallas kernel above
    (both low-rank intermediates stay in VMEM); elsewhere — interpret mode,
    or dims a tile doesn't divide (the model-zoo heads are small and
    arbitrary) — it is the mathematically identical unfused XLA form with
    f32 accumulation. One call site per layer, one numeric contract.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    m, k = x.shape
    n = w.shape[1]
    tiled = all(d % min(t, d) == 0
                for d, t in ((m, 128), (n, 128), (k, 512)))
    if not interpret and tiled:
        return lora_matmul(x, w, a, b, scale)
    xf = x.astype(jnp.float32)
    wf, af, bf = (t.astype(jnp.float32) for t in (w, a, b))
    y = xf @ wf + jnp.asarray(scale, jnp.float32) * ((xf @ af) @ bf)
    return y.astype(x.dtype)
