"""Wire-efficient sync layer: cost model, schedule picker, quantized wire.

The paper's pitch is P2P sync cheap enough for resource-constrained clinics,
yet the merge machinery alone doesn't decide what actually crosses the wire:
the same topology × merge-strategy pair can lower to an ``all_gather`` that
moves N·P values per sync or to a two-``ppermute`` ring schedule that moves
4·P — and every payload can ride the wire compressed. This module is the one
place those decisions live; every backend routes through it:

  * **Cost model** — :func:`candidate_schedules` enumerates the collective
    schedules that are *correct* for a ``SwarmConfig`` (topology × merge ×
    shard layout), each with an analytic per-device bytes/sync formula
    (:class:`SyncSchedule`). :func:`pick_schedule` argmins the model — the
    engine's gossip backend dispatches on the winner at trace time, and the
    engine backend surfaces the equivalent schedule (``simulated=True``)
    so logs and benchmarks always report predicted wire cost.
  * **Quantized error-feedback wire** (``SwarmConfig.wire_dtype``) — peers
    exchange int8/bf16-quantized parameter *deltas* against a shared
    reference copy θ̂ (what the wire has already delivered), with per-block
    scales and the residual θ−θ̂ carried across rounds in ``SwarmState.wire``
    (f32 accumulation everywhere; only the wire payload is low-precision).
    :func:`wire_effective` is the XLA ground truth; the fused Pallas
    quantize→merge→dequantize commit (`kernels.fused_merge.
    fused_quant_merge_all`) re-derives the same values in one VMEM pass.

Schedule table (values moved per device per sync, P = payload params/node,
N = swarm size; wire dtype scales the point-to-point entries, and int8 adds
4/wire_block bytes per value of scale overhead):

  topology   merge            schedule              values/sync   collective
  full       mean/fedavg      fedavg_psum           2P·(N−1)/N    psum
  full       mean/fedavg      fedavg_psum_q8        2P            reduce_scatter
  ring       mean/fedavg      ring_ppermute         2P            ppermute
  dynamic    mean/fedavg      gathered_rows         N·P           all_gather
  full       fisher/gradmatch fisher_psum           4P·(N−1)/N    psum
  full       fisher/gradmatch fisher_psum_q8        4P            reduce_scatter
  ring       fisher/gradmatch ring_topo_ppermute    4P            ppermute
  dynamic    fisher/gradmatch gathered_topo_stack   2N·P          all_gather

Ring schedules need one node per mesh shard (``per == 1``) and N ≥ 3 (an
N = 2 ring folds both neighbour edges onto one peer); otherwise the gathered
forms are the fallback. The plain psum schedules allreduce in f32 (wire
compression does not commute with the sum); an int8 wire adds the ``*_q8``
compression-aware reductions (`core.gossip`: quantized-chunk reduce-scatter
+ local dequant + quantized all_gather) whose payloads ride the wire at one
byte per value — the picker follows the bytes, not the table.

**Adapter-only (lora) payload class.** The factor formulas above are per
payload value, so they hold unchanged when only the LoRA adapters + decoder
head cross the wire (``cfg.lora_only`` carving the adapter subtree out of a
full state, or the heterogeneous ``cfg.payload = "lora"`` mode where the
stacked state IS the flat adapter payload — docs/heterogeneous.md). What
changes is P: the adapter count, orders of magnitude below the full model,
which compounds multiplicatively with the int8 wire (1 byte/value + scale
overhead vs 4). Every candidate carries its payload class in
``SyncSchedule.payload`` and CHANGES.md keeps a per-class values/sync table
that the drift gate re-derives from :func:`pick_schedule` in CI.

**Two-level (pod, node) meshes.** A swarm spanning pods has two link
classes: cheap intra-pod (ICI) links and the scarce cross-pod (DCN) hop.
On a 2-D mesh every schedule prices its traffic per class
(:meth:`SyncSchedule.bytes_by_link_class`): the flat schedules above run
over the joint ``("pod", "node")`` axis, so their collectives span pods and
the whole payload is classed *cross*; the hierarchical schedules
(``hier_fedavg_ring_q8`` / ``hier_fisher_ring_q8``, K pods × ``per_pod``
nodes, ring topology, int8 wire) keep the f32 bulk intra-pod — a weighted
intra-pod psum reduce, then each device carries a 1/per_pod chunk of its
pod's average onto a cross-pod int8 error-feedback ring (one delegate
chunk per device; k = 1 hop at K = 2 since the pair ring folds, else 2),
then an intra-pod all_gather broadcast. :func:`pick_schedule` argmins
Σ bytes(class) · ``cfg.{intra,cross}_pod_cost`` — with neutral costs the
flat forms win (they move fewer total bytes); once the DCN hop costs ≳5.4×
the ICI link, the hierarchical forms win. On 1-D meshes everything rides
one class and the picker reduces exactly to the PR 4/5 bytes argmin.

Error-feedback contract: v_t = θ_t − θ̂_{t−1} is quantized per block of
``wire_block`` elements (scale = max|v|/127, round-half-even — fully
deterministic), θ̂_t = θ̂_{t−1} + dequant(v_t), so the residual θ_t − θ̂_t is
exactly the quantization error and telescopes: on constant inputs
‖residual‖ contracts by ≥ 127× per round toward zero.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import List, Optional

import jax
import jax.numpy as jnp

WIRE_BYTES = {"f32": 4, "bf16": 2, "int8": 1}

#: nominal payload used to rank schedules when the real count isn't known yet
_NOMINAL_P = 1 << 20


def validate_wire_dtype(wire_dtype: str) -> str:
    wd = wire_dtype or "f32"
    if wd not in WIRE_BYTES:
        raise ValueError(f"unknown wire_dtype {wire_dtype!r} "
                         f"(choose from {sorted(WIRE_BYTES)})")
    return wd


def validate_wire_block(wire_block: int) -> int:
    if wire_block <= 0 or wire_block % 128:
        raise ValueError(f"wire_block must be a positive multiple of 128 "
                         f"(lane width), got {wire_block}")
    return wire_block


PAYLOAD_MODES = ("full", "lora")


def payload_mode(cfg) -> str:
    """``cfg.payload`` with validation — what the stacked state covers.

    ``"full"`` (default): SwarmState.params is every node's full pytree and
    ``cfg.lora_only`` selects the adapter subtree at sync time. ``"lora"``:
    the heterogeneous-swarm mode — the state IS the wire payload (one flat
    path-keyed adapter dict per node, `core.lora.flatten_payload`) and each
    node's frozen backbone lives inside its closures (docs/heterogeneous.md).
    """
    mode = getattr(cfg, "payload", "full") or "full"
    if mode not in PAYLOAD_MODES:
        raise ValueError(f"unknown payload mode {mode!r} "
                         f"(choose from {PAYLOAD_MODES})")
    return mode


def split_payload_at_sync(cfg) -> bool:
    """True when sync must carve the adapter subtree out of a full state.

    In ``payload="lora"`` mode there is nothing to carve — the state already
    is the payload — so ``lora_only`` is satisfied structurally and the
    engine/host split-at-sync paths turn off."""
    if not getattr(cfg, "lora_only", False):
        return False
    return payload_mode(cfg) != "lora"


# ---------------------------------------------------------------------------
# cost model + schedule picker
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class SyncSchedule:
    """One collective schedule with its analytic wire cost.

    ``payload_factor`` is the number of values moved per device per sync in
    units of P (the per-node payload param count) — the HLO result-shape
    convention `launch.hlo_stats` measures (all_gather counts its gathered
    result, psum its ring-allreduce traffic, ppermute each permuted payload).
    """

    name: str
    collective: str          # "psum" | "ppermute" | "all_gather" | "none"
    payload_factor: float
    wire_dtype: str = "f32"
    wire_block: int = 512
    simulated: bool = False  # engine backend: the SPMD-equivalent cost
    # Two-level (pod, node) split of payload_factor. cross_factor is None on
    # a flat mesh (one bandwidth domain — everything counts as intra). On a
    # 2-D mesh: cross_factor·P values cross pods at wire_dtype and
    # intra_factor·P values stay on intra-pod links at intra_dtype; flat
    # schedules built for a 2-D mesh set cross_factor = payload_factor
    # (their global collectives span the pod axis on every hop).
    cross_factor: Optional[float] = None
    intra_factor: float = 0.0
    intra_dtype: str = "f32"
    # payload class: "full" = whole param pytree crosses the wire; "lora" =
    # only the adapter subtree / adapter-only state does (P is then the
    # adapter count — orders of magnitude smaller, and it compounds with the
    # int8 wire). Purely descriptive for the factor formulas (identical per
    # class) but load-bearing for the CHANGES.md drift gate, which re-derives
    # the lora rows per class from pick_schedule.
    payload: str = "full"

    def _leg_bytes(self, vals: float, dtype: str) -> float:
        out = vals * WIRE_BYTES[dtype]
        if dtype == "int8":  # one f32 scale per wire block
            out += vals / self.wire_block * 4.0
        return out

    def bytes_by_link_class(self, payload_params: int) -> dict:
        """Predicted per-device wire bytes per link class for one sync.

        ``{"intra": ..., "cross": ...}`` — on a flat mesh everything is
        intra (there is no second class to cross)."""
        p = float(payload_params)
        if self.cross_factor is None:
            return {"intra": self._leg_bytes(self.payload_factor * p,
                                             self.wire_dtype),
                    "cross": 0.0}
        return {"intra": self._leg_bytes(self.intra_factor * p,
                                         self.intra_dtype),
                "cross": self._leg_bytes(self.cross_factor * p,
                                         self.wire_dtype)}

    def bytes_per_sync(self, payload_params: int) -> float:
        """Predicted per-device wire bytes for one sync of P payload values."""
        b = self.bytes_by_link_class(payload_params)
        return b["intra"] + b["cross"]

    def cost_per_sync(self, payload_params: int, intra_cost: float = 1.0,
                      cross_cost: float = 1.0) -> float:
        """Σ bytes(class) · cost(class): what :func:`pick_schedule` argmins.

        With equal per-byte costs this is plain bytes_per_sync, so flat-mesh
        picks are unchanged from the PR 4/5 bytes argmin."""
        b = self.bytes_by_link_class(payload_params)
        return b["intra"] * intra_cost + b["cross"] * cross_cost

    def describe(self, payload_params: Optional[int] = None) -> str:
        p = _NOMINAL_P if payload_params is None else payload_params
        tag = " (simulated)" if self.simulated else ""
        if self.payload != "full":
            tag = f"/{self.payload}{tag}"
        out = (f"{self.name}[{self.collective}/{self.wire_dtype}]{tag}: "
               f"{self.payload_factor:g}·P values, "
               f"{self.bytes_per_sync(p) / 1e6:.3f} MB/sync at P={p}")
        if self.cross_factor is not None:
            b = self.bytes_by_link_class(p)
            out += (f" [intra {b['intra'] / 1e6:.3f} MB + "
                    f"cross {b['cross'] / 1e6:.3f} MB]")
        return out


def candidate_schedules(cfg, *, per: int = 1, model_sharded: bool = False,
                        mesh_shape=None) -> List[SyncSchedule]:
    """Every schedule that is CORRECT for this config's sync semantics.

    ``per`` = stacked nodes per mesh shard (N // mesh axis size); ppermute
    schedules map one node to one shard, so they need ``per == 1``.
    ``model_sharded`` = payload leaves carry non-trivial inner (model-axis)
    PartitionSpecs; the q8 psum reductions chunk the globally-flattened
    payload and don't support that layout, so they drop out of the
    candidate set (the ring/gathered q8 forms handle inner specs).
    ``mesh_shape`` = (n_pods, per_pod) on a two-level ("pod", "node") mesh;
    flat candidates are then priced 100% cross-pod (their collectives span
    the pod axis) and the hierarchical pod-delegate candidates join the set.
    """
    n = cfg.n_nodes
    wd = validate_wire_dtype(getattr(cfg, "wire_dtype", "f32"))
    wb = validate_wire_block(getattr(cfg, "wire_block", 512))
    weighted = cfg.merge in ("fisher", "gradmatch")
    ring_ok = cfg.topology == "ring" and per == 1 and n >= 3
    psum_q8_ok = wd == "int8" and not model_sharded
    two_level = mesh_shape is not None
    # flat schedules on a 2-D mesh run over the joint axis: every hop of the
    # collective may cross pods, so the whole payload prices as cross-pod
    flat_kw = lambda factor: (
        {"cross_factor": factor, "intra_factor": 0.0} if two_level else {})
    pcls = ("lora" if (payload_mode(cfg) == "lora"
                       or getattr(cfg, "lora_only", False)) else "full")
    mk = lambda name, coll, factor, wdt: SyncSchedule(
        name, coll, factor, wire_dtype=wdt, wire_block=wb,
        payload=pcls, **flat_kw(factor))

    out: List[SyncSchedule] = []
    if weighted:
        if cfg.topology == "full":
            # psums reduce in f32: compression doesn't commute with the sum
            out.append(mk("fisher_psum", "psum", 4.0 * (n - 1) / n, "f32"))
            if psum_q8_ok:
                # compression-aware reduction: int8 reduce-scatter chunks
                # (all_to_all, P values/stream) + int8 all_gather of the
                # reduced chunks (P values/stream), two (num ⊕ mass) streams
                out.append(mk("fisher_psum_q8", "reduce_scatter", 4.0, wd))
        out.append(mk("gathered_topo_stack", "all_gather", 2.0 * n, wd))
        if ring_ok:
            out.append(mk("ring_topo_ppermute", "ppermute", 4.0, wd))
    else:
        if cfg.topology == "full":
            out.append(mk("fedavg_psum", "psum", 2.0 * (n - 1) / n, "f32"))
            if psum_q8_ok:
                out.append(mk("fedavg_psum_q8", "reduce_scatter", 2.0, wd))
        out.append(mk("gathered_rows", "all_gather", 1.0 * n, wd))
        if ring_ok:
            out.append(mk("ring_ppermute", "ppermute", 2.0, wd))

    if two_level:
        k_pods, per_pod = mesh_shape
        # hierarchical pod-delegate forms: intra-pod f32 psum reduce (
        # 2(per−1)/per values of ring-allreduce traffic) + cross-pod int8 EF
        # ring over per_pod-sharded delegate chunks (k·P/per_pod values,
        # k = 1 at K = 2 since the pair ring folds both edges onto one peer)
        # + intra-pod f32 all_gather broadcast (P values). Ring topology +
        # int8 wire + one node per device only — same constraints as the
        # flat ring q8 forms, minus the N ≥ 3 floor (the pod ring handles
        # K = 2 as a single chunk swap).
        hier_ok = (k_pods >= 2 and per_pod >= 2 and per == 1
                   and n == k_pods * per_pod and wd == "int8"
                   and not model_sharded and cfg.topology == "ring")
        if hier_ok:
            k_hops = 1.0 if k_pods == 2 else 2.0
            cross = k_hops / per_pod
            intra = 2.0 * (per_pod - 1) / per_pod + 1.0
            if weighted:
                out.append(SyncSchedule(
                    "hier_fisher_ring_q8", "hier_ring",
                    2.0 * (cross + intra), wire_dtype=wd, wire_block=wb,
                    cross_factor=2.0 * cross, intra_factor=2.0 * intra,
                    payload=pcls))
            else:
                out.append(SyncSchedule(
                    "hier_fedavg_ring_q8", "hier_ring", cross + intra,
                    wire_dtype=wd, wire_block=wb,
                    cross_factor=cross, intra_factor=intra, payload=pcls))
    return out


def has_inner_sharding(param_specs) -> bool:
    """True when a param-specs pytree names any non-trivial inner (model)
    axis — the layout the q8 psum reductions can't chunk."""
    if param_specs is None:
        return False
    from jax.sharding import PartitionSpec as PSpec
    leaves = jax.tree.leaves(param_specs,
                             is_leaf=lambda x: isinstance(x, PSpec))
    return any(any(d is not None for d in tuple(s))
               for s in leaves if isinstance(s, PSpec))


def pick_schedule(cfg, *, per: int = 1, payload_params: Optional[int] = None,
                  simulated: bool = False, model_sharded: bool = False,
                  mesh_shape=None) -> SyncSchedule:
    """Cheapest correct schedule under the cost model (trace-time static:
    everything it consumes — topology, merge, wire dtype, N, shard layout,
    mesh shape, link costs — is config/mesh data, so the choice never
    retraces a compiled round). On a two-level mesh the objective is
    Σ bytes(link class) · per-byte cost (``cfg.intra_pod_cost`` /
    ``cfg.cross_pod_cost``); on a flat mesh it reduces to the bytes argmin."""
    p = _NOMINAL_P if payload_params is None else payload_params
    cands = candidate_schedules(cfg, per=per, model_sharded=model_sharded,
                                mesh_shape=mesh_shape)
    intra_cost = float(getattr(cfg, "intra_pod_cost", 1.0))
    cross_cost = float(getattr(cfg, "cross_pod_cost", 1.0))
    best = min(cands, key=lambda s: s.cost_per_sync(p, intra_cost, cross_cost))
    if simulated:
        best = dataclasses.replace(best, simulated=True)
    return best


def payload_param_count(stacked, lora_only: bool, n_nodes: int) -> int:
    """Per-node payload values P for a stacked params pytree."""
    tree = stacked
    if lora_only:
        from repro.core.lora import split_adapters
        tree = split_adapters(stacked)[0]
    total = sum(x.size for x in jax.tree.leaves(tree) if x is not None)
    return int(total // max(n_nodes, 1))


# ---------------------------------------------------------------------------
# shared quantization core: THE per-block int8/bf16 round-trip implementation
# ---------------------------------------------------------------------------
# Every path that quantizes — the stateless XLA wire (`_leaf_quant_dequant`),
# the fused Pallas commit kernel (`kernels.fused_merge`), and the mesh gossip
# q8 schedules (`core.gossip`) — goes through these three functions, so the
# EF contract (scale = max|block|/127, round-half-even, clip ±127) has exactly
# one home and can never silently diverge between the gate candidate and the
# committed params.

def _block_quantize(v):
    """[..., n_blocks, wire_block] f32 → (q f32 int-valued, scale f32).

    scale = max|block|/127 (zero blocks keep scale 0 and quantize to 0);
    q = clip(round(v / scale), ±127) — deterministic round-half-even."""
    scale = jnp.max(jnp.abs(v), axis=-1, keepdims=True) / 127.0
    q = jnp.clip(jnp.round(v / jnp.where(scale > 0, scale, 1.0)),
                 -127.0, 127.0)
    return q, scale


def quant_dequant_block(v, wire_dtype: str, wire_block: int):
    """The single int8/bf16 round-trip over a [..., B] array (B a multiple of
    ``wire_block``; f32 out). Safe inside a Pallas kernel body (pure jnp on
    the tile) and equal bit-for-bit to ``quant_decode(*quant_encode(v))``."""
    vf = jnp.asarray(v, jnp.float32)
    if wire_dtype == "f32":
        return vf
    if wire_dtype == "bf16":
        return vf.astype(jnp.bfloat16).astype(jnp.float32)
    shape = vf.shape
    blocks = vf.reshape(shape[:-1] + (shape[-1] // wire_block, wire_block))
    q, scale = _block_quantize(blocks)
    return (q * scale).reshape(shape)


def quant_encode(v, wire_block: int):
    """[..., B] f32 (B a multiple of ``wire_block``) → the int8 wire payload
    ``(q int8 [..., B], scales f32 [..., B // wire_block])`` — what actually
    crosses a mesh collective on the q8 schedules."""
    vf = jnp.asarray(v, jnp.float32)
    shape = vf.shape
    blocks = vf.reshape(shape[:-1] + (shape[-1] // wire_block, wire_block))
    q, scale = _block_quantize(blocks)
    return q.astype(jnp.int8).reshape(shape), scale[..., 0]


def quant_decode(q, scales, wire_block: int):
    """Inverse of :func:`quant_encode`: (int8 payload, per-block scales) →
    the dequantized f32 values (== the sender's round-trip, bit-exact)."""
    qf = q.astype(jnp.float32)
    shape = qf.shape
    blocks = qf.reshape(shape[:-1] + (shape[-1] // wire_block, wire_block))
    return (blocks * scales[..., None]).reshape(shape)


# ---------------------------------------------------------------------------
# quantized wire: stateless per-block quant→dequant + error-feedback advance
# ---------------------------------------------------------------------------

def _leaf_quant_dequant(x, wire_dtype: str, wire_block: int):
    """Per-leaf quantize→dequantize of a stacked [N, ...] leaf (f32 out).

    Pads the flattened per-node payload to the ``wire_block`` grid and runs
    the shared :func:`quant_dequant_block` core — the exact arithmetic the
    fused Pallas commit kernel applies in its VMEM pass (same block grid
    from 0)."""
    xf = jnp.asarray(x, jnp.float32)
    if wire_dtype == "f32":
        return xf
    if wire_dtype == "bf16":
        return xf.astype(jnp.bfloat16).astype(jnp.float32)
    n = xf.shape[0]
    flat = xf.reshape(n, -1)
    d = flat.shape[1]
    pad = (-d) % wire_block
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    deq = quant_dequant_block(flat, wire_dtype, wire_block)
    return deq[:, :d].reshape(xf.shape)


def quant_dequant_tree(tree, wire_dtype: str, wire_block: int = 512):
    """Stateless wire round-trip of a stacked pytree (None leaves pass)."""
    wire_dtype = validate_wire_dtype(wire_dtype)
    if wire_dtype == "f32":
        return jax.tree.map(
            lambda x: None if x is None else jnp.asarray(x, jnp.float32),
            tree, is_leaf=lambda v: v is None)
    wire_block = validate_wire_block(wire_block)
    return jax.tree.map(
        lambda x: (None if x is None
                   else _leaf_quant_dequant(x, wire_dtype, wire_block)),
        tree, is_leaf=lambda v: v is None)


def init_wire(payload):
    """Zero wire reference θ̂ matching a stacked payload pytree (f32)."""
    return jax.tree.map(
        lambda x: None if x is None else jnp.zeros(x.shape, jnp.float32),
        payload, is_leaf=lambda v: v is None)


def wire_effective(payload, wire, wire_dtype: str, wire_block: int = 512):
    """Error-feedback wire advance: θ̂' = θ̂ + dequant(quant(θ − θ̂)).

    Returns the NEW reference θ̂' — simultaneously the effective params every
    peer reconstructs this round and the state to carry into the next one
    (the residual θ − θ̂' is exactly this round's quantization error, so
    untransmitted mass is never dropped, only delayed)."""
    wire_dtype = validate_wire_dtype(wire_dtype)
    wire_block = validate_wire_block(wire_block)

    def one(p, w):
        if p is None:
            return None
        v = jnp.asarray(p, jnp.float32) - w
        return w + _leaf_quant_dequant(v, wire_dtype, wire_block)

    return jax.tree.map(one, payload, wire, is_leaf=lambda v: v is None)


def wire_residual(payload, wire):
    """θ − θ̂: the untransmitted (error-feedback) mass per leaf."""
    return jax.tree.map(
        lambda p, w: None if p is None else jnp.asarray(p, jnp.float32) - w,
        payload, wire, is_leaf=lambda v: v is None)


def _mix32(v):
    """murmur3 fmix32: bijective avalanche on uint32 (each input bit flips
    ~half the output bits)."""
    v = v ^ (v >> jnp.uint32(16))
    v = v * jnp.uint32(0x85EBCA6B)
    v = v ^ (v >> jnp.uint32(13))
    v = v * jnp.uint32(0xC2B2AE35)
    v = v ^ (v >> jnp.uint32(16))
    return v


def payload_checksum(payload):
    """Per-node uint32 checksum of a stacked payload pytree ([N] uint32).

    Bit-level: every leaf row is bitcast to uint32, each element is
    position-salted (a Weyl sequence keyed on the flattened index and the
    leaf's position in the pytree) and avalanche-mixed before the mod-2³²
    per-node sum. The mixing matters: a plain sum lets symmetric multi-bit
    corruption cancel — k elements with the SAME bit toggled shift the sum
    by (#zeros−#ones)·2^bit, which is zero whenever the toggles balance
    (≈1/√k odds for random data). After mixing, every single-bit flip
    perturbs its element's contribution pseudorandomly, so collisions need
    a ~2⁻³² coincidence. Computed by sender and receiver of the quantized
    wire; a mismatch quarantines the sender for the round
    (reject-and-keep-local — see `SwarmEngine.sync` and docs/faults.md).
    Traceable and cheap: elementwise bitcast + mix + a per-node reduction.
    """
    leaves = [x for x in jax.tree.leaves(payload,
                                         is_leaf=lambda v: v is None)
              if x is not None]
    if not leaves:
        raise ValueError("payload_checksum: empty payload pytree")
    n = leaves[0].shape[0]
    total = jnp.zeros((n,), jnp.uint32)
    for i, x in enumerate(leaves):
        u = jax.lax.bitcast_convert_type(jnp.asarray(x, jnp.float32),
                                         jnp.uint32).reshape(n, -1)
        salt = (jnp.arange(u.shape[1], dtype=jnp.uint32)
                + jnp.uint32(i)) * jnp.uint32(0x9E3779B9)
        total = total + jnp.sum(_mix32(u ^ salt[None, :]), axis=1,
                                dtype=jnp.uint32)
    return total
