"""Pallas TPU kernel: fused swarm merge + validation gate.

The gossip commit applies  out = gate ? Σ_j w_j θ_j : θ_self  over every
parameter shard. Done naively (XLA) this materializes the weighted sum and the
select as separate HBM round-trips over the full model (multi-GB). The kernel
fuses contraction-over-nodes and gating into ONE VMEM pass: each grid step
streams an [N, BLOCK] tile from HBM, reduces over N on the VPU, applies the
gate, writes BLOCK back. Memory-bound by design — (N+1)·BLOCK bytes moved per
BLOCK produced, the roofline minimum for this op.

Two entry points:

  * ``fused_merge``      — one node's commit:   [N, D] → [D]
  * ``fused_merge_all``  — the whole swarm's commit in one launch:
                           [N, D] → [N, D] with a full mixing matrix W [N, N]
                           and per-node gate bits. Each grid step reads one
                           [N, BLOCK] tile, contracts it against the whole W
                           on the MXU and writes all N committed rows —
                           (N + N)·BLOCK bytes per column block, still the
                           roofline minimum.

Mosaic layout rules shape the operands: W and the ``[N, 1]`` int32 gate
column are whole-array blocks, tiles are ``[N, BLOCK]`` (BLOCK a multiple of
128), and scalars live in SMEM.

``fused_merge_all`` optionally takes per-element importance weights
``imp [N, D]`` (diagonal Fisher mass). The merged row then becomes the
normalized importance-weighted mean

    out[i] = gate_i ?  Σ_j W[i,j]·imp[j]⊙θ_j / Σ_j W[i,j]·imp[j]  :  θ_i

which covers fisher merging (W = 1) and gradient matching (W rows = dataset
weights; the gradmatch correction collapses algebraically to this ratio) in
the same single VMEM pass — (2N + N)·BLOCK bytes per column block instead of
the ~6N·BLOCK an unfused numerator/denominator/select chain moves.

``fused_merge_tree`` maps either entry point leaf-wise over a stacked param
pytree (2-D ``weights`` selects the all-nodes form, ``imp=`` a matching
importance pytree); the engine backend's commit runs through it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK = 16_384  # 4 nodes × 16k × 4B = 256 KiB VMEM working set

# VMEM working-set budget for auto block sizing: ~16 MB/core total, leave
# room for double buffering + compiler scratch.
VMEM_BUDGET = 4 * 1024 * 1024


def auto_block(n: int, streams: int, *, out_rows: int = 1,
               block: int = DEFAULT_BLOCK, budget: int = VMEM_BUDGET,
               align: int = 128) -> int:
    """Largest tile width whose VMEM working set fits the budget.

    A grid step holds ``streams`` input tiles of [N, block] plus ``out_rows``
    output rows of [block] — (streams·N + out_rows)·block·4 bytes. The old
    fixed DEFAULT_BLOCK ignored both N and the extra importance stream, so a
    64-node fisher commit wanted (2·64+1)·16384·4 ≈ 8.5 MB of VMEM per step.
    Returns min(requested block, budget-derived cap), multiple of ``align``
    (lane width), floored at ``align``.
    """
    rows = streams * n + out_rows
    cap = budget // (rows * 4)
    return min(block, max(align, cap // align * align))


def _mix(w, x):
    """w [M, N] · x [N, B] on the MXU at full f32 precision."""
    return jax.lax.dot(w.astype(jnp.float32), x,
                       precision=jax.lax.Precision.HIGHEST)


def _gated(g_ref, merged, local):
    """Per-row select: accepted rows take ``merged``, rejected rows keep the
    exact ``local`` values. ``g_ref`` is the [N, 1] int32 gate column."""
    g = jnp.broadcast_to(g_ref[...], local.shape) != 0
    return jnp.where(g, merged, local)


def _merge_kernel(x_ref, w_ref, idx_ref, gate_ref, o_ref):
    """x [N, B] tile; w [1, N] mixing row; idx/gate scalars (SMEM); o [1, B]."""
    x = x_ref[...].astype(jnp.float32)              # [N, B]
    merged = _mix(w_ref[...], x)                     # [1, B]
    rows = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    self_row = jnp.sum(jnp.where(rows == idx_ref[0], x, 0.0), axis=0,
                       keepdims=True)
    gate = gate_ref[0] != 0
    o_ref[...] = jnp.where(gate, merged, self_row).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def fused_merge(stacked, weights, self_idx, gate, *, block: int = DEFAULT_BLOCK,
                interpret: bool = False):
    """stacked [N, D] → merged-or-kept [D].

    weights: [N] mixing row for this node; gate: scalar bool (validation
    acceptance); self_idx: this node's row. D is padded to a block multiple.
    """
    n, d = stacked.shape
    block = min(auto_block(n, 1, block=block), max(128, d))
    pad = (-d) % block
    if pad:
        stacked = jnp.pad(stacked, ((0, 0), (0, pad)))
    dp = d + pad
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    out = pl.pallas_call(
        _merge_kernel,
        grid=(dp // block,),
        in_specs=[
            pl.BlockSpec((n, block), lambda j: (0, j)),
            pl.BlockSpec((1, n), lambda j: (0, 0)),
            smem,
            smem,
        ],
        out_specs=pl.BlockSpec((1, block), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, dp), stacked.dtype),
        interpret=interpret,
    )(stacked, jnp.asarray(weights, jnp.float32).reshape(1, n),
      jnp.asarray(self_idx, jnp.int32).reshape(1),
      jnp.asarray(gate, jnp.int32).reshape(1))
    return out[0, :d]


def _merge_all_kernel(x_ref, w_ref, g_ref, o_ref):
    """x [N, B] tile (all nodes); w [N, N]; g [N, 1] gate bits; o [N, B]."""
    x = x_ref[...].astype(jnp.float32)
    o_ref[...] = _gated(g_ref, _mix(w_ref[...], x), x).astype(o_ref.dtype)


def _merge_all_imp_kernel(x_ref, f_ref, w_ref, g_ref, o_ref):
    """Importance-weighted form: x/f [N, B] tiles; w [N, N]; g [N, 1];
    o [N, B].  merged = W·(f⊙x) / W·f  per element."""
    x = x_ref[...].astype(jnp.float32)
    f = f_ref[...].astype(jnp.float32)
    w = w_ref[...]
    merged = _mix(w, f * x) / jnp.maximum(_mix(w, f), 1e-30)
    o_ref[...] = _gated(g_ref, merged, x).astype(o_ref.dtype)


def _gate_column(gates, n: int):
    """[N] accept bits → the [N, 1] int32 gate operand every commit kernel
    reads (a whole-array block; the kernel broadcasts it along lanes)."""
    return jnp.asarray(gates).astype(jnp.int32).reshape(n, 1)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def fused_merge_all(stacked, W, gates, imp=None, *, block: int = DEFAULT_BLOCK,
                    interpret: bool = False):
    """stacked [N, D] → committed [N, D]:  out[i] = gate[i] ? Σ_j W[i,j] θ_j : θ_i.

    W: [N, N] row-stochastic mixing matrix; gates: [N] acceptance bits. Each
    grid step loads one [N, BLOCK] tile, contracts it against the whole W
    and writes every node's committed row of that column block.

    imp: optional [N, D] per-element importance weights — switches to the
    normalized weighted merge  Σ_j W[i,j]·imp[j]⊙θ_j / Σ_j W[i,j]·imp[j]
    (fisher / gradmatch commits), still one pass over the tile.

    The tile width is auto-capped so the VMEM working set — one [N, BLOCK]
    tile per input stream (two with ``imp``) plus the output tile — fits
    `VMEM_BUDGET` regardless of swarm size N (see :func:`auto_block`).
    """
    n, d = stacked.shape
    block = min(auto_block(n, 1 if imp is None else 2, out_rows=n,
                           block=block), max(128, d))
    pad = (-d) % block
    if pad:
        stacked = jnp.pad(stacked, ((0, 0), (0, pad)))
        if imp is not None:
            imp = jnp.pad(imp, ((0, 0), (0, pad)))
    dp = d + pad

    tile_spec = pl.BlockSpec((n, block), lambda j: (0, j))
    operands = [stacked]
    in_specs = [tile_spec]
    if imp is not None:  # same tiling, one extra [N, B] importance stream
        operands.append(jnp.asarray(imp, jnp.float32))
        in_specs.append(tile_spec)
    operands += [jnp.asarray(W, jnp.float32), _gate_column(gates, n)]
    in_specs += [pl.BlockSpec((n, n), lambda j: (0, 0)),
                 pl.BlockSpec((n, 1), lambda j: (0, 0))]

    out = pl.pallas_call(
        _merge_all_kernel if imp is None else _merge_all_imp_kernel,
        grid=(dp // block,),
        in_specs=in_specs,
        out_specs=tile_spec,
        out_shape=jax.ShapeDtypeStruct((n, dp), stacked.dtype),
        interpret=interpret,
    )(*operands)
    return out[:, :d]


# ---------------------------------------------------------------------------
# quantized-wire commit: quantize -> merge -> dequantize in one VMEM pass
# ---------------------------------------------------------------------------
# The per-block round-trip is `core.comms.quant_dequant_block` — the ONE
# shared implementation (kernels import core.comms; no second quantization
# body anywhere), so the fused commit can never silently diverge from the
# XLA ground truth the candidate (gate) path computes. The import is lazy:
# `repro.core.__init__` imports the engine, which imports this module, so a
# module-level import back into the package would be init-order-sensitive.

def _quant_block(v, wire_dtype: str, wire_block: int):
    from repro.core.comms import quant_dequant_block
    return quant_dequant_block(v, wire_dtype, wire_block)


def _quant_merge_kernel(x_ref, r_ref, w_ref, g_ref, o_ref, ro_ref, *,
                        wire_dtype, wire_block):
    """x (local params) / r (wire reference θ̂): [N, B] tiles; w: [N, N];
    g: [N, 1]; outputs: o committed [N, B], ro new reference [N, B].

    One VMEM pass per column block: quantize the EF delta v = x − θ̂ (per-
    wire-block int8 scales or bf16 cast), advance the reference, contract
    every node's mixing row against the dequantized payload, gate-select
    against the EXACT local row — the wire round-trip, merge, and gate never
    touch HBM between each other."""
    x = x_ref[...].astype(jnp.float32)
    r = r_ref[...].astype(jnp.float32)
    rp = r + _quant_block(x - r, wire_dtype, wire_block)
    o_ref[...] = _gated(g_ref, _mix(w_ref[...], rp), x).astype(o_ref.dtype)
    ro_ref[...] = rp


def _quant_merge_imp_kernel(x_ref, r_ref, f_ref, w_ref, g_ref, o_ref, ro_ref,
                            *, wire_dtype, wire_block):
    """Importance-weighted form: merged = W·(imp⊙θ̂') / W·imp per element
    (fisher / gradmatch / topology-restricted rows), same single pass."""
    x = x_ref[...].astype(jnp.float32)
    r = r_ref[...].astype(jnp.float32)
    rp = r + _quant_block(x - r, wire_dtype, wire_block)
    f = f_ref[...].astype(jnp.float32)                      # [N, B]
    w = w_ref[...]
    merged = _mix(w, f * rp) / jnp.maximum(_mix(w, f), 1e-30)
    o_ref[...] = _gated(g_ref, merged, x).astype(o_ref.dtype)
    ro_ref[...] = rp


@functools.partial(jax.jit, static_argnames=("wire_dtype", "wire_block",
                                             "block", "interpret"))
def fused_quant_merge_all(stacked, wire_ref, W, gates, imp=None, *,
                          wire_dtype: str = "int8", wire_block: int = 512,
                          block: int = DEFAULT_BLOCK, interpret: bool = False):
    """Quantized-wire commit: [N, D] params + [N, D] wire reference →
    (committed [N, D], new reference [N, D]).

    Fuses the error-feedback wire round-trip (quantize the delta against the
    reference copy θ̂, per-``wire_block`` scales, dequantize), the mixing-row
    (optionally importance-weighted) contraction, and the validation gate
    into one VMEM pass per column block — the wire-compressed sibling of
    :func:`fused_merge_all`. Rejected rows keep the EXACT f32 local params;
    the reference always advances (the wire traffic happened either way).

    The tile is sized by :func:`auto_block` counting every stream — params,
    reference, optional importance in; committed + reference out — then
    aligned down to a ``wire_block`` multiple so in-kernel scale blocks land
    on the same global grid as the XLA ground truth (`core.comms`).
    """
    n, d = stacked.shape
    streams = 2 if imp is None else 3
    block = auto_block(n, streams, out_rows=2 * n, block=block,
                       align=wire_block)
    block = max(wire_block, block // wire_block * wire_block)
    # don't pad small leaves (lora_scale, biases) out to the full tile —
    # cap at d rounded up to the wire-block grid
    block = min(block, -(-d // wire_block) * wire_block)
    pad = (-d) % block
    if pad:
        stacked = jnp.pad(stacked, ((0, 0), (0, pad)))
        wire_ref = jnp.pad(wire_ref, ((0, 0), (0, pad)))
        if imp is not None:
            imp = jnp.pad(imp, ((0, 0), (0, pad)))
    dp = d + pad

    tile = pl.BlockSpec((n, block), lambda j: (0, j))
    operands = [stacked, jnp.asarray(wire_ref, jnp.float32)]
    in_specs = [tile, tile]
    if imp is not None:
        operands.append(jnp.asarray(imp, jnp.float32))
        in_specs.append(tile)
    operands += [jnp.asarray(W, jnp.float32), _gate_column(gates, n)]
    in_specs += [pl.BlockSpec((n, n), lambda j: (0, 0)),
                 pl.BlockSpec((n, 1), lambda j: (0, 0))]

    kern = functools.partial(
        _quant_merge_kernel if imp is None else _quant_merge_imp_kernel,
        wire_dtype=wire_dtype, wire_block=wire_block)
    committed, new_ref = pl.pallas_call(
        kern,
        grid=(dp // block,),
        in_specs=in_specs,
        out_specs=(tile, tile),
        out_shape=(jax.ShapeDtypeStruct((n, dp), stacked.dtype),
                   jax.ShapeDtypeStruct((n, dp), jnp.float32)),
        interpret=interpret,
    )(*operands)
    return committed[:, :d], new_ref[:, :d]


def fused_quant_merge_tree(stacked_tree, wire_tree, W, gates, imp=None, **kw):
    """Leaf-wise :func:`fused_quant_merge_all` over stacked pytrees.

    Returns ``(committed_tree, new_wire_tree)``; None leaves (non-payload
    when lora_only sync is active) pass through as None in both. Flattens
    explicitly so params trees containing structural tuples can't be
    confused with the per-leaf (committed, reference) pairs."""
    nones = lambda v: v is None
    xs, treedef = jax.tree_util.tree_flatten(stacked_tree, is_leaf=nones)
    rs = jax.tree_util.tree_flatten(wire_tree, is_leaf=nones)[0]
    fs = ([None] * len(xs) if imp is None
          else jax.tree_util.tree_flatten(imp, is_leaf=nones)[0])

    committed, new_wire = [], []
    for x, r, f in zip(xs, rs, fs):
        if x is None:
            committed.append(None)
            new_wire.append(None)
            continue
        n = x.shape[0]
        c, nr = fused_quant_merge_all(
            x.reshape(n, -1), jnp.asarray(r, jnp.float32).reshape(n, -1),
            W, gates, None if f is None else jnp.asarray(f).reshape(n, -1),
            **kw)
        committed.append(c.reshape(x.shape))
        new_wire.append(nr.reshape(x.shape))
    return (jax.tree_util.tree_unflatten(treedef, committed),
            jax.tree_util.tree_unflatten(treedef, new_wire))


def fused_merge_tree(stacked_tree, weights, self_idx, gate, imp=None, **kw):
    """Apply the kernel leaf-wise over a stacked param pytree.

    weights [N] + scalar gate → one node's view ([D]-shaped leaves);
    weights [N, N] + gate [N] → the all-nodes commit (stacked leaves preserved;
    ``self_idx`` is ignored — each row is its own self). ``imp``: optional
    pytree of per-element importance weights matching ``stacked_tree``
    (fisher/gradmatch; all-nodes form only).
    """
    all_nodes = jnp.ndim(weights) == 2

    def one(x, f=None):
        if x is None:
            return None
        n = x.shape[0]
        flat = x.reshape(n, -1)
        if all_nodes:
            fflat = None if f is None else jnp.asarray(f).reshape(n, -1)
            return fused_merge_all(flat, weights, gate, fflat,
                                   **kw).reshape(x.shape)
        return fused_merge(flat, weights, self_idx, gate, **kw).reshape(x.shape[1:])

    if imp is None:
        return jax.tree.map(one, stacked_tree, is_leaf=lambda v: v is None)
    return jax.tree.map(one, stacked_tree, imp, is_leaf=lambda v: v is None)
