"""SPMD gossip: the paper's P2P exchange realized as TPU mesh collectives.

Two schedules, both operating on stacked pytrees whose leading node axis is
sharded over a mesh axis (the swarm axis — `node` single-pod, `pod` multi-pod):

  * ``fedavg_gossip``   — dense merge: one weighted ``psum`` over the swarm
    axis (every node ends with the same weighted average). Collective bytes
    per sync: ~2·P per link direction (reduce-scatter + all-gather lowering).
  * ``ring_gossip``     — sparse P2P merge: two ``ppermute`` shifts; each node
    mixes with its ring neighbours only. Collective bytes per sync: 2·P
    point-to-point, no global reduction — the TPU-native analogue of the
    paper's pairwise peer exchange, and the beyond-paper §Perf winner.
  * ``matrix_gossip``   — arbitrary (possibly dynamic-membership) mixing
    matrix via all_gather + local contraction; the faithful general form.
  * ``ring_rows_gossip`` / ``ring_topo_fisher_gossip`` — ring-native
    schedules: two ``ppermute`` shifts honouring (possibly traced) ring
    mixing rows, 2·P / 4·P point-to-point values per sync instead of the
    gathered forms' N·P / 2·N·P. ``topo_fisher_gossip`` is the general-rows
    fallback — ONE all_gather of the fused ``(F⊙θ ⊕ F)`` stack.

Which schedule a given config lowers to is decided by the `core.comms` cost
model (`comms.pick_schedule`); ``wire_dtype`` compresses point-to-point
payloads: bf16 is a stateless cast, and int8 rides the **mesh error-feedback
wire** — the ``*_q8`` schedule forms below carry a sharded EF reference
(per-shard residual pytree in the SPMD gossip state) so the collectives move
int8 payloads + per-block f32 scales instead of f32/bf16 values:

  * ``ring_rows_gossip_q8`` / ``ring_topo_fisher_gossip_q8`` — the ppermute
    schedules with int8 deltas against per-node references; each device also
    tracks its two ring neighbours' references (updated from the same delta
    stream the senders apply, so replicas never diverge).
  * ``matrix_gossip_q8`` / ``topo_fisher_gossip_q8`` — the gathered forms
    with ONE int8 all_gather of every node's delta; every device carries the
    full reconstruction table (replicated — all devices receive the same
    deltas, so the table stays bit-identical across the mesh).
  * ``fedavg_psum_q8`` / ``fisher_psum_q8`` — the psum family rebuilt as a
    compression-aware reduction: quantized-chunk reduce-scatter (all_to_all
    of int8 chunks + local dequant-and-sum at the chunk owner, with a
    second-stage EF residual per chunk) followed by a quantized all_gather
    of the reduced chunks into a replicated consensus accumulator.
  * ``hier_fedavg_ring_q8`` / ``hier_fisher_ring_q8`` — two-level
    ``("pod", "node")`` meshes: the flat schedules above also run over the
    joint axis tuple unchanged, but these keep the f32 bulk on intra-pod
    links — a weighted intra-pod psum reduce, then each device delegates a
    1/per_pod chunk of its pod's reduction onto a cross-pod int8 EF ring
    (per-pod residual + neighbour-pod replicas riding ``SwarmState.wire``),
    then an intra-pod all_gather broadcast. Cross-pod (DCN) traffic drops
    to k·P/per_pod int8 values per device (k = 1 at two pods, else 2).

All quantization goes through the shared `core.comms` quant core, so the
mesh wire can never diverge from the engine-backend EF contract. Every EF
residual telescopes: on settling inputs the reconstructions converge to the
exact f32 payloads and the merges to their uncompressed oracles.

All schedules return a stacked pytree of the same structure. `None` leaves
(the non-payload part when lora_only sync is active) pass through untouched.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import comms

def shard_map(f, mesh, in_specs, out_specs, check_vma=True):
    """``jax.shard_map`` over the Auto-typed view of ``mesh`` (see
    :func:`auto_mesh`). check_vma=False: the q8 schedules return replicated
    state (the reconstruction table / consensus accumulator) that IS
    identical on every device — each applies the same all_gathered deltas —
    but the static replication checker can't see through the axis_index
    arithmetic."""
    return jax.shard_map(f, mesh=auto_mesh(mesh), in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def auto_mesh(mesh):
    """``mesh`` with every axis of type Auto.

    The swarm round leaves the layout of everything between its collectives
    to the compiler's sharding propagation. On a mesh with Explicit axes
    (what ``jax.make_mesh`` builds by default) the node sharding of every
    shard_map output becomes part of its type, and the vmapped train and
    eval steps then refuse inputs that mix node-sharded and unplaced
    arrays."""
    from jax.sharding import AxisType, Mesh
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def spans_mesh(mesh, axis) -> bool:
    """Whether the swarm ``axis`` (a name or a tuple of names) covers every
    axis of ``mesh``: each device then holds whole sites, with no data or
    model axis splitting a site across devices."""
    names = axis if isinstance(axis, tuple) else (axis,)
    return set(mesh.axis_names) <= set(names)


def per_shard(fn, mesh, axis, replicated=()):
    """``fn`` (a vmapped per-node step or eval) run shard-locally: every
    device applies it to its own rows of the node axis, so a site's step
    runs whole on the device that holds the site, with no collective inside
    it. Arguments at the positions in ``replicated`` (e.g. the step counter)
    are whole on every device. Only for a swarm axis that spans the mesh
    (:func:`spans_mesh`): with data or model axes inside a site, the step
    is left to the partitioner.

    Left to the partitioner on such a mesh, too, a vmapped 1x1 convolution
    over a node-sharded batch computes wrong values on the CPU backend of
    JAX 0.9.0 (the histo CNN's transition layers), where the tests and
    rehearsals run; the TPU backend computes it correctly."""
    def run(*args):
        specs = tuple(P() if i in replicated else P(axis)
                      for i in range(len(args)))
        return shard_map(fn, mesh, in_specs=specs, out_specs=P(axis),
                         check_vma=False)(*args)

    return run


def axis_size(mesh, axis) -> int:
    """Total shard count along the swarm axis — a single mesh axis name or
    a tuple of names (two-level meshes gossip over the joint axis)."""
    if isinstance(axis, tuple):
        size = 1
        for a in axis:
            size *= mesh.shape[a]
        return size
    return mesh.shape[axis]


def _pod_axes(axis):
    """The (pod, node) axis names of a two-level swarm axis."""
    if not (isinstance(axis, tuple) and len(axis) == 2):
        raise ValueError("hierarchical schedules need a two-level swarm axis "
                         f"(pod, node); got {axis!r}")
    return axis[0], axis[1]


def _mapped(fn, mesh, axis, stacked, *extra, inner_specs=None):
    """shard_map fn over the swarm axis, skipping None leaves.

    inner_specs: optional pytree of PartitionSpecs for the NON-node dims of
    each leaf. Without it the shard_map boundary implies replication on the
    other mesh axes, which forces a full all-gather of (data, model)-sharded
    params before every gossip round (measured: 12.6 GB/device of spurious
    all-gather on minicpm-2b). With it, gossip exchanges only local shards.
    """
    nones = lambda x: x is None

    def leaf_fn(x, spec):
        if x is None:
            return None
        in_spec = P(axis, *(tuple(spec) if spec is not None else ()))
        out = shard_map(fn, mesh,
                        in_specs=(in_spec,) + tuple(P() for _ in extra),
                        out_specs=in_spec)(x, *extra)
        return out

    if inner_specs is None:
        inner_specs = jax.tree.map(lambda x: None, stacked, is_leaf=nones)
    return jax.tree.map(leaf_fn, stacked, inner_specs, is_leaf=nones)


def _wire_cast(z, wire_dtype):
    """STATELESS cast of a payload for the wire (point-to-point collectives
    only). bf16 halves link bytes; accumulation stays f32 after decode.
    int8 is refused here because a stateless int8 wire would silently drop
    mass — it rides the ``*_q8`` error-feedback schedule forms below, which
    carry the sharded mesh EF state instead.
    """
    if wire_dtype in (None, "f32"):
        return z
    if wire_dtype == "bf16":
        return z.astype(jnp.bfloat16)
    raise ValueError(f"wire_dtype {wire_dtype!r} has no stateless mesh cast "
                     "(int8 needs error-feedback state — the *_q8 schedule "
                     "forms carry it)")


def fedavg_gossip(stacked, weights, mesh, axis: str, inner_specs=None):
    """Weighted global merge: θ_i ← Σ_j w_j θ_j for every node i."""
    n = axis_size(mesh, axis)

    def f(x, w):  # x: [N/n_shards, ...] local shard; w: [N]
        idx = jax.lax.axis_index(axis)
        per = x.shape[0]
        wl = jax.lax.dynamic_slice_in_dim(w, idx * per, per, 0)
        contrib = x.astype(jnp.float32) * wl.reshape((per,) + (1,) * (x.ndim - 1))
        merged = jax.lax.psum(contrib.sum(0), axis)
        return jnp.broadcast_to(merged, x.shape).astype(x.dtype)

    w = jnp.asarray(weights, jnp.float32)
    assert w.shape == (n,) or w.size % n == 0
    return _mapped(f, mesh, axis, stacked, w, inner_specs=inner_specs)


def ring_gossip(stacked, mesh, axis: str, self_weight: float = 0.5,
                inner_specs=None):
    """Sparse P2P: θ_i ← s·θ_i + (1-s)/2·(θ_{i-1} + θ_{i+1})."""
    n = axis_size(mesh, axis)
    fwd = [(i, (i + 1) % n) for i in range(n)]
    bwd = [(i, (i - 1) % n) for i in range(n)]

    def f(x):
        # wire dtype = param dtype (bf16): halves link bytes vs f32;
        # the mixing arithmetic still accumulates in f32
        left = jax.lax.ppermute(x, axis, fwd).astype(jnp.float32)
        right = jax.lax.ppermute(x, axis, bwd).astype(jnp.float32)
        side = (1.0 - self_weight) / 2.0
        return (self_weight * x.astype(jnp.float32)
                + side * (left + right)).astype(x.dtype)

    return _mapped(f, mesh, axis, stacked, inner_specs=inner_specs)


def fisher_gossip(stacked, fishers, mesh, axis: str, inner_specs=None,
                  eps: float = 1e-8):
    """Diagonal-Fisher-weighted merge over the swarm axis:
    θ* = Σ_i F_i⊙θ_i / Σ_i F_i  (two psums), broadcast to every node.

    The SPMD realization of `merge_impl.fisher_merge` — the principled
    aggregation the paper cites ([6]) but never builds.
    """
    def f(x, fsh):
        xf = x.astype(jnp.float32)
        ff = fsh.astype(jnp.float32) + eps
        num = jax.lax.psum((ff * xf).sum(0), axis)
        den = jax.lax.psum(ff.sum(0), axis)
        return jnp.broadcast_to(num / den, x.shape).astype(x.dtype)

    nones = lambda v: v is None

    def leaf_fn(x, fsh, spec):
        if x is None:
            return None
        in_spec = P(axis, *(tuple(spec) if spec is not None else ()))
        return shard_map(f, mesh, in_specs=(in_spec, in_spec),
                         out_specs=in_spec)(x, fsh)

    if inner_specs is None:
        inner_specs = jax.tree.map(lambda v: None, stacked, is_leaf=nones)
    return jax.tree.map(leaf_fn, stacked, fishers, inner_specs, is_leaf=nones)


def _fisher_pair_map(fn, mesh, axis, stacked, fishers, extra, inner_specs):
    """shard_map fn(x, fisher, *extra) leaf-wise over (params, mass) pairs;
    extras are replicated (P()); None leaves pass through."""
    nones = lambda v: v is None

    def leaf_fn(x, fsh, spec):
        if x is None:
            return None
        in_spec = P(axis, *(tuple(spec) if spec is not None else ()))
        return shard_map(fn, mesh,
                         in_specs=(in_spec, in_spec)
                         + tuple(P() for _ in extra),
                         out_specs=in_spec)(x, fsh, *extra)

    if inner_specs is None:
        inner_specs = jax.tree.map(lambda v: None, stacked, is_leaf=nones)
    return jax.tree.map(leaf_fn, stacked, fishers, inner_specs, is_leaf=nones)


def topo_fisher_gossip(stacked, fishers, rows, mesh, axis: str,
                       inner_specs=None, eps: float = 1e-8, wire_dtype=None):
    """Topology-restricted importance-weighted merge over the swarm axis:

        θ*_i = Σ_j rows[i,j]·(F_j+eps)⊙θ_j / Σ_j rows[i,j]·(F_j+eps)

    The SPMD realization of `merge_impl.topo_weighted_merge` — ring/dynamic
    swarms merge only graph-neighbour contributions. Lowering: the
    importance-weighted numerator and the mass are stacked into ONE
    ``(num ⊕ mass)`` payload and moved by a SINGLE ``all_gather`` per leaf
    (2·N·P values at the wire dtype), then contracted locally per row —
    the general-rows form; ring rows take the 4·P two-``ppermute`` schedule
    (:func:`ring_topo_fisher_gossip`) instead."""
    n = axis_size(mesh, axis)

    def f(x, fsh, Wm):  # x/fsh: [per, ...] local shard; Wm: [N, N]
        idx = jax.lax.axis_index(axis)
        per = x.shape[0]
        xf = x.astype(jnp.float32)
        ff = fsh.astype(jnp.float32) + eps
        z = jnp.concatenate([ff * xf, ff], axis=0)          # [2·per, ...]
        allz = jax.lax.all_gather(_wire_cast(z, wire_dtype), axis,
                                  tiled=True).astype(jnp.float32)
        pair = allz.reshape(n, 2, per, -1)                   # shard-major
        num_all = pair[:, 0].reshape(n * per, -1)            # [N, D]
        den_all = pair[:, 1].reshape(n * per, -1)
        r = jax.lax.dynamic_slice_in_dim(Wm, idx * per, per, 0)  # [per, N]
        num = r @ num_all
        den = r @ den_all
        out = num / jnp.maximum(den, 1e-30)
        return out.reshape((per,) + x.shape[1:]).astype(x.dtype)

    Wj = jnp.asarray(rows, jnp.float32)
    return _fisher_pair_map(f, mesh, axis, stacked, fishers, (Wj,),
                            inner_specs)


def _ring_perms(n: int):
    """(receive-from-left, receive-from-right) ppermute pairs."""
    fwd = [(i, (i + 1) % n) for i in range(n)]   # data flows i -> i+1
    bwd = [(i, (i - 1) % n) for i in range(n)]   # data flows i -> i-1
    return fwd, bwd


def _check_one_node_per_shard(stacked, mesh, axis, what: str):
    n = axis_size(mesh, axis)
    lead = jax.tree.leaves(stacked)[0].shape[0]
    if lead != n:
        raise ValueError(
            f"{what} needs one node per mesh shard (leading axis {lead} vs "
            f"mesh axis {axis}={n}); use the gathered fallback for per>1")
    if n < 3:
        raise ValueError(f"{what} needs N >= 3 (an N=2 ring folds both "
                         f"neighbour edges onto one peer); got N={n}")


def ring_rows_gossip(stacked, W, mesh, axis: str, inner_specs=None,
                     wire_dtype=None):
    """Ring-native mixing-row gossip (mean/fedavg on a ring):

        θ*_i = W[i,i]·θ_i + W[i,i−1]·θ_{i−1} + W[i,i+1]·θ_{i+1}

    Two ``ppermute`` shifts move 2·P point-to-point values per device — no
    global collective — while honouring a (possibly traced, membership-
    masked) ring mixing matrix, unlike :func:`ring_gossip`'s fixed
    self-weight. Only neighbour payloads are wire-cast; the self term stays
    exact local precision. Requires one node per shard and N ≥ 3."""
    _check_one_node_per_shard(stacked, mesh, axis, "ring_rows_gossip")
    n = axis_size(mesh, axis)
    fwd, bwd = _ring_perms(n)

    def f(x, Wm):  # x: [1, ...] this node's shard; Wm: [N, N]
        idx = jax.lax.axis_index(axis)
        z = _wire_cast(x, wire_dtype)
        left = jax.lax.ppermute(z, axis, fwd).astype(jnp.float32)
        right = jax.lax.ppermute(z, axis, bwd).astype(jnp.float32)
        w_self = Wm[idx, idx]
        w_left = Wm[idx, (idx - 1) % n]
        w_right = Wm[idx, (idx + 1) % n]
        out = (w_self * x.astype(jnp.float32) + w_left * left
               + w_right * right)
        return out.astype(x.dtype)

    return _mapped(f, mesh, axis, stacked, jnp.asarray(W, jnp.float32),
                   inner_specs=inner_specs)


def ring_topo_fisher_gossip(stacked, fishers, rows, mesh, axis: str,
                            inner_specs=None, eps: float = 1e-8,
                            wire_dtype=None):
    """Ring-native topology-restricted weighted merge — the wire-optimal
    form of :func:`topo_fisher_gossip` for ring mixing rows:

        θ*_i = Σ_{j∈{i−1,i,i+1}} rows[i,j]·(F_j+eps)⊙θ_j
             / Σ_{j∈{i−1,i,i+1}} rows[i,j]·(F_j+eps)

    Each node fuses its importance-weighted numerator and mass into one
    ``(F⊙θ ⊕ F)`` side-channel payload and ppermutes it to both ring
    neighbours: ~4·P point-to-point values per sync instead of the gathered
    form's 2·N·P. Self contributions never touch the wire (exact f32).
    Requires one node per shard and N ≥ 3 (ring rows only have the three
    per-row entries this schedule exchanges)."""
    _check_one_node_per_shard(stacked, mesh, axis, "ring_topo_fisher_gossip")
    n = axis_size(mesh, axis)
    fwd, bwd = _ring_perms(n)

    def f(x, fsh, Wm):  # x/fsh: [1, ...]; Wm: [N, N] ring-structured rows
        idx = jax.lax.axis_index(axis)
        xf = x.astype(jnp.float32)
        ff = fsh.astype(jnp.float32) + eps
        y = ff * xf                                   # numerator payload
        z = _wire_cast(jnp.concatenate([y, ff], axis=0), wire_dtype)  # [2,...]
        left = jax.lax.ppermute(z, axis, fwd).astype(jnp.float32)
        right = jax.lax.ppermute(z, axis, bwd).astype(jnp.float32)
        r_self = Wm[idx, idx]
        r_left = Wm[idx, (idx - 1) % n]
        r_right = Wm[idx, (idx + 1) % n]
        num = r_self * y + r_left * left[0:1] + r_right * right[0:1]
        den = r_self * ff + r_left * left[1:2] + r_right * right[1:2]
        return (num / jnp.maximum(den, 1e-30)).astype(x.dtype)

    Wj = jnp.asarray(rows, jnp.float32)
    return _fisher_pair_map(f, mesh, axis, stacked, fishers, (Wj,),
                            inner_specs)


# ---------------------------------------------------------------------------
# mesh int8 error-feedback wire: the *_q8 schedule forms
# ---------------------------------------------------------------------------
# Per-leaf EF codec (runs INSIDE shard_map, on local shards). The payload is
# flattened per row, zero-padded to the wire-block grid, and delta-encoded
# against a same-shaped reference through the shared `core.comms` quant core;
# the padded tail stays exactly zero on both sides, so references can be
# stored in payload shape and re-padded every round without drift.

def _ef_encode(z, ref, wire_block: int, pad_to: int = 0):
    """(z, ref) local [rows, ...] → (q int8 [rows, Dp], scales f32
    [rows, Dp/wb], ref' [rows, ...]) with ref' = ref + dequant(q·s)."""
    rows = z.shape[0]
    flat = z.astype(jnp.float32).reshape(rows, -1)
    rflat = ref.astype(jnp.float32).reshape(rows, -1)
    d = flat.shape[1]
    grid = max(wire_block, pad_to)
    pad = (-d) % grid
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
        rflat = jnp.pad(rflat, ((0, 0), (0, pad)))
    q, s = comms.quant_encode(flat - rflat, wire_block)
    ref2 = rflat + comms.quant_decode(q, s, wire_block)
    return q, s, ref2[:, :d].reshape(ref.shape)


def _ef_apply(ref, q, s, wire_block: int):
    """Receiver side: advance a reference replica with a received (q, s)
    payload — bit-identical to the sender's own `_ef_encode` advance."""
    rows = ref.shape[0]
    rflat = ref.astype(jnp.float32).reshape(rows, -1)
    d = rflat.shape[1]
    deq = comms.quant_decode(q, s, wire_block)[:, :d]
    return (rflat + deq).reshape(ref.shape)


def _leafwise(fn, trees, n_out: int):
    """Apply ``fn(*leaves) -> n_out-tuple`` leaf-wise over parallel pytrees
    (explicit flatten, so structural tuples in params can't be confused with
    the output tuples); None payload leaves map to None in every output."""
    nones = lambda v: v is None
    flats = [jax.tree_util.tree_flatten(t, is_leaf=nones)[0] for t in trees]
    treedef = jax.tree_util.tree_flatten(trees[0], is_leaf=nones)[1]
    outs = [[] for _ in range(n_out)]
    for leaves in zip(*flats):
        res = (None,) * n_out if leaves[0] is None else fn(*leaves)
        for acc, r in zip(outs, res):
            acc.append(r)
    return tuple(jax.tree_util.tree_unflatten(treedef, acc) for acc in outs)


def _inner_spec_tree(stacked, inner_specs):
    if inner_specs is None:
        return jax.tree.map(lambda x: None, stacked,
                            is_leaf=lambda v: v is None)
    return inner_specs


def _padded_chunk(shape, n: int, wire_block: int) -> int:
    """Per-shard chunk length of a leaf row flattened and padded to the
    n·wire_block grid (the psum-q8 reduce-scatter layout)."""
    d = 1
    for s in shape[1:]:
        d *= s
    grid = n * wire_block
    return (-(-d // grid) * grid) // n


def init_mesh_wire(schedule: str, payload, *, n_shards: int,
                   wire_block: int = 512, mesh_shape=None):
    """Zero EF wire state for a ``*_q8`` mesh schedule over a stacked payload
    pytree ([N, ...] leaves; None leaves mirror as None). The returned pytree
    rides ``SwarmState.wire`` next to the params:

      ring:      {"ref", "left", "right"} — own + neighbour-replica
                 references, payload-shaped, sharded by node
                 (weighted forms: each a {"num", "mass"} pair of trees)
      gathered:  {"table"} — the full reconstruction table, replicated
      psum q8:   {"ref"} per-shard contribution reference (one row/shard),
                 {"cons"} replicated consensus row, {"cres"} second-stage
                 chunk residual (one chunk per shard)
      hier q8:   {"ref", "left"[, "right"]} — per-device delegate-chunk
                 references ([N, chunk] rows, sharded over the joint
                 ("pod", "node") axis) for own pod + neighbour pods; needs
                 ``mesh_shape=(n_pods, per_pod)``, and "right" exists only
                 for n_pods > 2 (a two-pod ring folds onto one peer)
    """
    nones = lambda v: v is None
    zlike = lambda x: (None if x is None
                       else jnp.zeros(x.shape, jnp.float32))
    zrow = lambda x: (None if x is None
                      else jnp.zeros((1,) + x.shape[1:], jnp.float32))
    zshard = lambda x: (None if x is None
                        else jnp.zeros((n_shards,) + x.shape[1:], jnp.float32))
    zchunk = lambda x: (None if x is None else jnp.zeros(
        (n_shards, _padded_chunk(x.shape, n_shards, wire_block)), jnp.float32))
    tmap = lambda f: jax.tree.map(f, payload, is_leaf=nones)
    pair = lambda f: {"num": tmap(f), "mass": tmap(f)}
    if schedule == "ring_ppermute":
        return {"ref": tmap(zlike), "left": tmap(zlike), "right": tmap(zlike)}
    if schedule == "ring_topo_ppermute":
        return {"ref": pair(zlike), "left": pair(zlike), "right": pair(zlike)}
    if schedule == "gathered_rows":
        return {"table": tmap(zlike)}
    if schedule == "gathered_topo_stack":
        return {"table": pair(zlike)}
    if schedule == "fedavg_psum_q8":
        return {"ref": tmap(zshard), "cons": tmap(zrow), "cres": tmap(zchunk)}
    if schedule == "fisher_psum_q8":
        return {"ref": pair(zshard), "cons": pair(zrow), "cres": pair(zchunk)}
    if schedule in ("hier_fedavg_ring_q8", "hier_fisher_ring_q8"):
        if mesh_shape is None:
            raise ValueError(f"{schedule} needs mesh_shape=(n_pods, per_pod)")
        k_pods, per_pod = mesh_shape
        zhier = lambda x: (None if x is None else jnp.zeros(
            (k_pods * per_pod, _padded_chunk(x.shape, per_pod, wire_block)),
            jnp.float32))
        leaf = tmap if schedule == "hier_fedavg_ring_q8" else pair
        out = {"ref": leaf(zhier), "left": leaf(zhier)}
        if k_pods > 2:
            out["right"] = leaf(zhier)
        return out
    raise ValueError(f"no mesh wire state for schedule {schedule!r}")


def mesh_wire_shardings(wire, mesh, axis):
    """Where each leaf of an :func:`init_mesh_wire` pytree lives, as the q8
    shard_maps read and write it: the gathered reconstruction ``table`` and
    the psum consensus row ``cons`` are replicated on every device; every
    other leaf is one row (or chunk) per shard along the swarm axis."""
    from jax.sharding import NamedSharding

    def placed(key, sub):
        spec = P() if key in ("table", "cons") else P(axis)
        return jax.tree.map(lambda _: NamedSharding(mesh, spec), sub)

    return {k: placed(k, v) for k, v in wire.items()}


def reset_mesh_wire(wire):
    """Quarantine the WHOLE mesh EF wire state (crash→rejoin recovery).

    Per-node row surgery is unsafe here: the q8 ring/hier schedules carry
    neighbour replicas ("left"/"right") that must track the sender's "ref"
    bit-exactly — zeroing one node's reference without zeroing every
    replica of it (sharded on other devices) would desynchronize the
    telescoping residual and the divergence would be committed as if it
    were quantization error. A full reset keeps every replica trivially
    consistent: the next sync retransmits full quantized payloads
    everywhere and EF re-settles within a few rounds (see docs/faults.md).

    ``x * 0`` (not ``zeros_like``) so shardings and replication of the
    schedule-shaped pytree are preserved leaf-by-leaf.
    """
    return jax.tree.map(lambda x: None if x is None else x * 0,
                        wire, is_leaf=lambda v: v is None)


def ring_rows_gossip_q8(stacked, W, wire, mesh, axis: str, inner_specs=None,
                        wire_block: int = 512):
    """int8-EF form of :func:`ring_rows_gossip`: the two ppermutes move int8
    deltas + per-block scales (~2·P bytes + 8·P/wire_block per sync instead
    of 8·P f32 bytes). Each device advances its own reference and its two
    neighbour replicas from the identical delta stream, so reconstructions
    match the senders bit-for-bit; the self term stays exact local f32.
    Returns ``(merged, new_wire)``."""
    _check_one_node_per_shard(stacked, mesh, axis, "ring_rows_gossip_q8")
    n = axis_size(mesh, axis)
    fwd, bwd = _ring_perms(n)
    Wj = jnp.asarray(W, jnp.float32)

    def f(x, ref, lft, rgt, Wm):
        idx = jax.lax.axis_index(axis)
        q, s, ref2 = _ef_encode(x, ref, wire_block)
        ql = jax.lax.ppermute(q, axis, fwd)
        sl = jax.lax.ppermute(s, axis, fwd)
        qr = jax.lax.ppermute(q, axis, bwd)
        sr = jax.lax.ppermute(s, axis, bwd)
        lft2 = _ef_apply(lft, ql, sl, wire_block)
        rgt2 = _ef_apply(rgt, qr, sr, wire_block)
        w_self = Wm[idx, idx]
        w_left = Wm[idx, (idx - 1) % n]
        w_right = Wm[idx, (idx + 1) % n]
        out = (w_self * x.astype(jnp.float32) + w_left * lft2
               + w_right * rgt2)
        return out.astype(x.dtype), ref2, lft2, rgt2

    def leaf(x, ref, lft, rgt, spec):
        in_spec = P(axis, *(tuple(spec) if spec is not None else ()))
        sm = shard_map(f, mesh, in_specs=(in_spec,) * 4 + (P(),),
                       out_specs=(in_spec,) * 4)
        return sm(x, ref, lft, rgt, Wj)

    specs = _inner_spec_tree(stacked, inner_specs)
    merged, ref2, lft2, rgt2 = _leafwise(
        leaf, (stacked, wire["ref"], wire["left"], wire["right"], specs), 4)
    return merged, {"ref": ref2, "left": lft2, "right": rgt2}


def ring_topo_fisher_gossip_q8(stacked, fishers, rows, wire, mesh, axis: str,
                               inner_specs=None, eps: float = 1e-8,
                               wire_block: int = 512):
    """int8-EF form of :func:`ring_topo_fisher_gossip`: the fused
    ``(F⊙θ ⊕ F)`` side-channel rides the wire as two delta-encoded streams
    (numerator and mass, each int8 + scales) against per-node references
    with neighbour replicas — ~4·P wire bytes per sync instead of 16·P.
    Self contributions never touch the wire (exact f32).
    Returns ``(merged, new_wire)``."""
    _check_one_node_per_shard(stacked, mesh, axis,
                              "ring_topo_fisher_gossip_q8")
    n = axis_size(mesh, axis)
    fwd, bwd = _ring_perms(n)
    Wj = jnp.asarray(rows, jnp.float32)

    def f(x, fsh, rn, rm, ln, lm, rgn, rgm, Wm):
        idx = jax.lax.axis_index(axis)
        xf = x.astype(jnp.float32)
        ff = fsh.astype(jnp.float32) + eps
        y = ff * xf
        # the num and mass streams ride as ONE stacked (F⊙θ ⊕ F) payload —
        # per-row quantization blocks are unchanged, but each direction
        # launches one int8 ppermute + one scale ppermute instead of four
        z = jnp.concatenate([y, ff], axis=0)              # [2, ...]
        refs = jnp.concatenate([rn, rm], axis=0)
        q, s, ref2 = _ef_encode(z, refs, wire_block)
        ql = jax.lax.ppermute(q, axis, fwd)
        sl = jax.lax.ppermute(s, axis, fwd)
        qr = jax.lax.ppermute(q, axis, bwd)
        sr = jax.lax.ppermute(s, axis, bwd)
        lft2 = _ef_apply(jnp.concatenate([ln, lm], axis=0), ql, sl,
                         wire_block)
        rgt2 = _ef_apply(jnp.concatenate([rgn, rgm], axis=0), qr, sr,
                         wire_block)
        r_self = Wm[idx, idx]
        r_left = Wm[idx, (idx - 1) % n]
        r_right = Wm[idx, (idx + 1) % n]
        num = r_self * y + r_left * lft2[0:1] + r_right * rgt2[0:1]
        den = r_self * ff + r_left * lft2[1:2] + r_right * rgt2[1:2]
        return ((num / jnp.maximum(den, 1e-30)).astype(x.dtype),
                ref2[0:1], ref2[1:2], lft2[0:1], lft2[1:2],
                rgt2[0:1], rgt2[1:2])

    def leaf(x, fsh, rn, rm, ln, lm, rgn, rgm, spec):
        in_spec = P(axis, *(tuple(spec) if spec is not None else ()))
        sm = shard_map(f, mesh, in_specs=(in_spec,) * 8 + (P(),),
                       out_specs=(in_spec,) * 7)
        return sm(x, fsh, rn, rm, ln, lm, rgn, rgm, Wj)

    specs = _inner_spec_tree(stacked, inner_specs)
    ref, lft, rgt = wire["ref"], wire["left"], wire["right"]
    merged, rn2, rm2, ln2, lm2, rgn2, rgm2 = _leafwise(
        leaf, (stacked, fishers, ref["num"], ref["mass"], lft["num"],
               lft["mass"], rgt["num"], rgt["mass"], specs), 7)
    return merged, {"ref": {"num": rn2, "mass": rm2},
                    "left": {"num": ln2, "mass": lm2},
                    "right": {"num": rgn2, "mass": rgm2}}


def matrix_gossip_q8(stacked, W, wire, mesh, axis: str, inner_specs=None,
                     wire_block: int = 512):
    """int8-EF form of :func:`matrix_gossip` (the ``gathered_rows`` q8
    schedule): ONE int8 all_gather of every node's delta + scales; each
    device advances the full replicated reconstruction table (all devices
    see the same deltas, so the table stays bit-identical across the mesh)
    and contracts its mixing rows against the reconstructions.
    Returns ``(merged, new_wire)``."""
    n = axis_size(mesh, axis)
    Wj = jnp.asarray(W, jnp.float32)

    def f(x, table, Wm):  # x: [per, ...] local; table: [N, ...] replicated
        idx = jax.lax.axis_index(axis)
        per = x.shape[0]
        myref = jax.lax.dynamic_slice_in_dim(table, idx * per, per, 0)
        q, s, _ = _ef_encode(x.astype(jnp.float32), myref, wire_block)
        allq = jax.lax.all_gather(q, axis, tiled=True)    # [N, Dp] int8
        alls = jax.lax.all_gather(s, axis, tiled=True)    # [N, Dp/wb] f32
        table2 = _ef_apply(table, allq, alls, wire_block)
        rows = jax.lax.dynamic_slice_in_dim(Wm, idx * per, per, 0)  # [per, N]
        out = rows @ table2.reshape(table2.shape[0], -1)
        return out.reshape(x.shape).astype(x.dtype), table2

    def leaf(x, table, spec):
        inner = tuple(spec) if spec is not None else ()
        in_spec = P(axis, *inner)
        tab_spec = P(None, *inner)
        sm = shard_map(f, mesh, in_specs=(in_spec, tab_spec, P()),
                       out_specs=(in_spec, tab_spec), check_vma=False)
        return sm(x, table, Wj)

    specs = _inner_spec_tree(stacked, inner_specs)
    merged, table2 = _leafwise(leaf, (stacked, wire["table"], specs), 2)
    return merged, {"table": table2}


def topo_fisher_gossip_q8(stacked, fishers, rows, wire, mesh, axis: str,
                          inner_specs=None, eps: float = 1e-8,
                          wire_block: int = 512):
    """int8-EF form of :func:`topo_fisher_gossip` (the
    ``gathered_topo_stack`` q8 schedule): the importance-weighted numerator
    and mass streams are delta-encoded against a replicated reconstruction
    table and moved by ONE stacked int8 all_gather plus one scale gather
    (PR 4's fused-gather invariant, kept at the q8 byte cost), then
    contracted per mixing row. Returns ``(merged, new_wire)``."""
    n = axis_size(mesh, axis)
    Wj = jnp.asarray(rows, jnp.float32)

    def f(x, fsh, tn, tm, Wm):
        idx = jax.lax.axis_index(axis)
        per = x.shape[0]
        xf = x.astype(jnp.float32)
        ff = fsh.astype(jnp.float32) + eps
        y = ff * xf
        refn = jax.lax.dynamic_slice_in_dim(tn, idx * per, per, 0)
        refm = jax.lax.dynamic_slice_in_dim(tm, idx * per, per, 0)
        z = jnp.concatenate([y, ff], axis=0)              # [2·per, ...]
        refs = jnp.concatenate([refn, refm], axis=0)
        q, s, _ = _ef_encode(z, refs, wire_block)
        allq = jax.lax.all_gather(q, axis, tiled=True)    # [N·2·per, Dp]
        alls = jax.lax.all_gather(s, axis, tiled=True)
        gq = allq.reshape(n, 2, per, allq.shape[-1])      # shard-major
        gs = alls.reshape(n, 2, per, alls.shape[-1])
        tn2 = _ef_apply(tn, gq[:, 0].reshape(n * per, -1),
                        gs[:, 0].reshape(n * per, -1), wire_block)
        tm2 = _ef_apply(tm, gq[:, 1].reshape(n * per, -1),
                        gs[:, 1].reshape(n * per, -1), wire_block)
        r = jax.lax.dynamic_slice_in_dim(Wm, idx * per, per, 0)   # [per, N]
        num = r @ tn2.reshape(tn2.shape[0], -1)
        den = r @ tm2.reshape(tm2.shape[0], -1)
        out = num / jnp.maximum(den, 1e-30)
        return out.reshape(x.shape).astype(x.dtype), tn2, tm2

    def leaf(x, fsh, tn, tm, spec):
        inner = tuple(spec) if spec is not None else ()
        in_spec = P(axis, *inner)
        tab_spec = P(None, *inner)
        sm = shard_map(f, mesh,
                       in_specs=(in_spec, in_spec, tab_spec, tab_spec, P()),
                       out_specs=(in_spec, tab_spec, tab_spec),
                       check_vma=False)
        return sm(x, fsh, tn, tm, Wj)

    specs = _inner_spec_tree(stacked, inner_specs)
    tab = wire["table"]
    merged, tn2, tm2 = _leafwise(
        leaf, (stacked, fishers, tab["num"], tab["mass"], specs), 3)
    return merged, {"table": {"num": tn2, "mass": tm2}}


def _psum_q8_stream(z, ref, cons, cres, axis, n: int, wire_block: int):
    """One delta-consensus EF stream of the compression-aware psum:

      1. delta-encode the local contribution z against its per-shard
         reference (int8 + scales; reference advances locally),
      2. quantized-chunk reduce-scatter: all_to_all of int8 chunks, local
         dequant + sum at each chunk owner (f32 accumulation),
      3. second-stage EF: the owner re-quantizes its reduced chunk against
         a per-chunk residual, and the int8 chunks are all_gathered into
         the replicated consensus accumulator.

    Returns ``(consensus_row', ref', cons', cres')`` — all errors live in
    EF residuals, so the consensus telescopes to Σ_j z_j exactly as inputs
    settle. Runs INSIDE shard_map: z/ref/cons [1, ...row], cres [1, chunk].
    """
    q, s, ref2 = _ef_encode(z, ref, wire_block, pad_to=n * wire_block)
    dp = q.shape[1]
    chunk = dp // n
    qc = q.reshape(n, chunk)
    sc = s.reshape(n, chunk // wire_block)
    qx = jax.lax.all_to_all(qc, axis, split_axis=0, concat_axis=0)
    sx = jax.lax.all_to_all(sc, axis, split_axis=0, concat_axis=0)
    deq = comms.quant_decode(qx, sx, wire_block)          # [n, chunk] f32
    u = deq.sum(0, keepdims=True) + cres                  # [1, chunk]
    q2, s2 = comms.quant_encode(u, wire_block)
    cres2 = u - comms.quant_decode(q2, s2, wire_block)
    aq = jax.lax.all_gather(q2, axis, tiled=True)         # [n, chunk] int8
    as_ = jax.lax.all_gather(s2, axis, tiled=True)
    dhat = comms.quant_decode(aq, as_, wire_block).reshape(1, dp)
    cflat = cons.astype(jnp.float32).reshape(1, -1)
    d = cflat.shape[1]
    cons2 = (cflat + dhat[:, :d]).reshape(cons.shape)
    return cons2, ref2, cons2, cres2


def fedavg_psum_q8(stacked, weights, wire, mesh, axis: str, inner_specs=None,
                   wire_block: int = 512):
    """Compression-aware weighted global merge (the ``fedavg_psum_q8``
    schedule): every node ends with the replicated consensus reconstruction
    of Σ_j w_j θ_j, built from int8 wire traffic only (see
    :func:`_psum_q8_stream`). Weights may be traced (runtime membership).
    Returns ``(merged, new_wire)``."""
    n = axis_size(mesh, axis)
    if inner_specs is not None and any(
            s is not None for s in jax.tree.leaves(inner_specs)):
        raise ValueError("fedavg_psum_q8 does not support model-sharded "
                         "payloads (inner_specs); use a ring/gathered "
                         "schedule or wire_dtype='bf16'")
    w = jnp.asarray(weights, jnp.float32)

    def f(x, ref, cons, cres, wv):
        idx = jax.lax.axis_index(axis)
        per = x.shape[0]
        wl = jax.lax.dynamic_slice_in_dim(wv, idx * per, per, 0)
        z = (x.astype(jnp.float32)
             * wl.reshape((per,) + (1,) * (x.ndim - 1))).sum(0, keepdims=True)
        cons_row, ref2, cons2, cres2 = _psum_q8_stream(
            z, ref, cons, cres, axis, n, wire_block)
        merged = jnp.broadcast_to(cons_row, x.shape).astype(x.dtype)
        return merged, ref2, cons2, cres2

    def leaf(x, ref, cons, cres, spec):
        in_spec = P(axis)
        sm = shard_map(f, mesh,
                       in_specs=(in_spec, in_spec, P(), in_spec, P()),
                       out_specs=(in_spec, in_spec, P(), in_spec),
                       check_vma=False)
        return sm(x, ref, cons, cres, w)

    specs = _inner_spec_tree(stacked, inner_specs)
    merged, ref2, cons2, cres2 = _leafwise(
        leaf, (stacked, wire["ref"], wire["cons"], wire["cres"], specs), 4)
    return merged, {"ref": ref2, "cons": cons2, "cres": cres2}


def fisher_psum_q8(stacked, fishers, wire, mesh, axis: str, inner_specs=None,
                   eps: float = 1e-8, wire_block: int = 512):
    """Compression-aware importance-weighted global merge (the
    ``fisher_psum_q8`` schedule): numerator Σ (F+eps)⊙θ and mass Σ (F+eps)
    each ride one delta-consensus EF stream (int8 reduce-scatter +
    all_gather); the merge is the ratio of the two replicated consensus
    reconstructions. Any weight folding (gradmatch) happens in the mass
    before the call, exactly like :func:`fisher_gossip`.
    Returns ``(merged, new_wire)``."""
    n = axis_size(mesh, axis)
    if inner_specs is not None and any(
            s is not None for s in jax.tree.leaves(inner_specs)):
        raise ValueError("fisher_psum_q8 does not support model-sharded "
                         "payloads (inner_specs); use a ring/gathered "
                         "schedule or wire_dtype='bf16'")

    def f(x, fsh, rn, rm, cn, cm, qn_res, qm_res):
        xf = x.astype(jnp.float32)
        ff = fsh.astype(jnp.float32) + eps
        zn = (ff * xf).sum(0, keepdims=True)
        zm = ff.sum(0, keepdims=True)
        num_row, rn2, cn2, qn2 = _psum_q8_stream(
            zn, rn, cn, qn_res, axis, n, wire_block)
        den_row, rm2, cm2, qm2 = _psum_q8_stream(
            zm, rm, cm, qm_res, axis, n, wire_block)
        merged = num_row / jnp.maximum(den_row, 1e-30)
        return (jnp.broadcast_to(merged, x.shape).astype(x.dtype),
                rn2, rm2, cn2, cm2, qn2, qm2)

    def leaf(x, fsh, rn, rm, cn, cm, qn_res, qm_res, spec):
        in_spec = P(axis)
        sm = shard_map(
            f, mesh,
            in_specs=(in_spec, in_spec, in_spec, in_spec, P(), P(),
                      in_spec, in_spec),
            out_specs=(in_spec, in_spec, in_spec, P(), P(), in_spec,
                       in_spec),
            check_vma=False)
        return sm(x, fsh, rn, rm, cn, cm, qn_res, qm_res)

    specs = _inner_spec_tree(stacked, inner_specs)
    ref, cons, cres = wire["ref"], wire["cons"], wire["cres"]
    merged, rn2, rm2, cn2, cm2, qn2, qm2 = _leafwise(
        leaf, (stacked, fishers, ref["num"], ref["mass"], cons["num"],
               cons["mass"], cres["num"], cres["mass"], specs), 7)
    return merged, {"ref": {"num": rn2, "mass": rm2},
                    "cons": {"num": cn2, "mass": cm2},
                    "cres": {"num": qn2, "mass": qm2}}


# ---------------------------------------------------------------------------
# hierarchical two-level schedules: intra-pod reduce → pod-delegate int8 EF
# ring → intra-pod broadcast
# ---------------------------------------------------------------------------

def _hier_shapes(mesh, axis, stacked):
    """Validate a hierarchical call and return (pod_ax, node_ax, K, per)."""
    pod_ax, node_ax = _pod_axes(axis)
    k_pods = mesh.shape[pod_ax]
    per_pod = mesh.shape[node_ax]
    lead = jax.tree.leaves(stacked)[0].shape[0]
    if lead != k_pods * per_pod:
        raise ValueError(
            f"hierarchical schedules need one node per device (leading axis "
            f"{lead} vs mesh {pod_ax}×{node_ax}={k_pods}×{per_pod})")
    if k_pods < 2 or per_pod < 2:
        raise ValueError(f"hierarchical schedules need ≥2 pods and ≥2 nodes "
                         f"per pod; got {k_pods}×{per_pod}")
    return pod_ax, node_ax, k_pods, per_pod


def _refuse_inner_sharding(inner_specs, what: str):
    if inner_specs is not None and any(
            s is not None for s in jax.tree.leaves(inner_specs)):
        raise ValueError(f"{what} does not support model-sharded payloads "
                         "(inner_specs): delegate chunks slice the "
                         "globally-flattened payload")


def hier_fedavg_ring_q8(stacked, weights, pod_rows, wire, mesh, axis,
                        inner_specs=None, wire_block: int = 512):
    """Hierarchical weighted merge on a two-level ``("pod", "node")`` mesh
    (the ``hier_fedavg_ring_q8`` schedule):

      1. **intra-pod reduce** — a weighted f32 psum over the node axis gives
         every device its pod's average  ā_q = Σ_{i∈q} w_i θ_i / Σ_{i∈q} w_i
         (2·(per−1)/per values of intra-pod ring-allreduce traffic);
      2. **pod-delegate int8 EF ring** — each device owns the 1/per_pod
         chunk of the flattened ā_q matching its node index and ppermutes it
         across pods as an int8 delta + per-block scales against a per-pod
         EF residual (neighbour-pod replicas advance from the identical
         stream). Only this leg crosses the DCN: k·P/per_pod int8 values
         per device, k = 1 at two pods (the pair ring folds both edges onto
         one peer and "right" drops out of the wire), else 2;
      3. **intra-pod broadcast** — a node-axis all_gather reassembles the
         pod-row-mixed chunks (P f32 values, intra-pod).

    The self-pod term mixes at exact f32; neighbour pods telescope through
    the EF wire, so on settling inputs every node converges to the pod-ring
    mix  Σ_q pod_rows[pod(i), q] · ā_q . Weights may be traced (runtime
    membership) but every pod needs ≥1 active node for its average to be
    meaningful. Returns ``(merged, new_wire)``."""
    pod_ax, node_ax, k_pods, per_pod = _hier_shapes(mesh, axis, stacked)
    _refuse_inner_sharding(inner_specs, "hier_fedavg_ring_q8")
    fwd, bwd = _ring_perms(k_pods)
    two_sided = k_pods > 2
    Wp = jnp.asarray(pod_rows, jnp.float32)
    w = jnp.asarray(weights, jnp.float32)

    def f(x, ref, lft, rgt, wv, Wpm):  # x/ref/lft/rgt: [1, ...] per device
        p = jax.lax.axis_index(pod_ax)
        j = jax.lax.axis_index(node_ax)
        wl = jax.lax.dynamic_slice_in_dim(wv, p * per_pod + j, 1, 0)  # [1]
        xf = x.astype(jnp.float32)
        ones = (1,) + (1,) * (xf.ndim - 1)
        num = jax.lax.psum(xf * wl.reshape(ones), node_ax)
        mass = jax.lax.psum(wl, node_ax)
        avg = num / jnp.maximum(mass, 1e-30).reshape(ones)
        flat = avg.reshape(1, -1)
        d = flat.shape[1]
        pad = (-d) % (per_pod * wire_block)
        if pad:
            flat = jnp.pad(flat, ((0, 0), (0, pad)))
        clen = flat.shape[1] // per_pod
        chunk = jax.lax.dynamic_slice_in_dim(flat, j * clen, clen, 1)
        q, s, ref2 = _ef_encode(chunk, ref, wire_block)
        ql = jax.lax.ppermute(q, pod_ax, fwd)
        sl = jax.lax.ppermute(s, pod_ax, fwd)
        lft2 = _ef_apply(lft, ql, sl, wire_block)
        mixed = Wpm[p, p] * chunk + Wpm[p, (p - 1) % k_pods] * lft2
        if two_sided:
            qr = jax.lax.ppermute(q, pod_ax, bwd)
            sr = jax.lax.ppermute(s, pod_ax, bwd)
            rgt2 = _ef_apply(rgt, qr, sr, wire_block)
            mixed = mixed + Wpm[p, (p + 1) % k_pods] * rgt2
        full = jax.lax.all_gather(mixed, node_ax, tiled=True)  # [per, clen]
        out = full.reshape(1, per_pod * clen)[:, :d].reshape(xf.shape)
        if two_sided:
            return out.astype(x.dtype), ref2, lft2, rgt2
        return out.astype(x.dtype), ref2, lft2

    n_out = 4 if two_sided else 3

    def leaf(x, ref, lft, rgt, spec):
        in_spec = P(axis)
        sm = shard_map(f, mesh, in_specs=(in_spec,) * 4 + (P(), P()),
                       out_specs=(in_spec,) * n_out, check_vma=False)
        return sm(x, ref, lft, rgt, w, Wp)

    specs = _inner_spec_tree(stacked, inner_specs)
    rgt_in = wire["right"] if two_sided else wire["left"]  # dummy at K=2
    outs = _leafwise(leaf, (stacked, wire["ref"], wire["left"], rgt_in,
                            specs), n_out)
    if two_sided:
        merged, ref2, lft2, rgt2 = outs
        return merged, {"ref": ref2, "left": lft2, "right": rgt2}
    merged, ref2, lft2 = outs
    return merged, {"ref": ref2, "left": lft2}


def hier_fisher_ring_q8(stacked, fishers, pod_rows, wire, mesh, axis,
                        inner_specs=None, eps: float = 1e-8,
                        wire_block: int = 512):
    """Hierarchical importance-weighted merge on a two-level mesh (the
    ``hier_fisher_ring_q8`` schedule) — :func:`hier_fedavg_ring_q8` with the
    fused ``(F⊙θ ⊕ F)`` side channel of the ring fisher forms: the intra-pod
    psums reduce the pod numerator Σ (F+eps)⊙θ and mass Σ (F+eps), both ride
    the cross-pod delegate ring as ONE stacked two-stream EF payload
    (2·k·P/per_pod int8 values per device), and the merge is the ratio of
    the pod-row-mixed streams. Any weight folding (gradmatch) happens in the
    mass before the call, exactly like :func:`fisher_psum_q8`.
    Returns ``(merged, new_wire)``."""
    pod_ax, node_ax, k_pods, per_pod = _hier_shapes(mesh, axis, stacked)
    _refuse_inner_sharding(inner_specs, "hier_fisher_ring_q8")
    fwd, bwd = _ring_perms(k_pods)
    two_sided = k_pods > 2
    Wp = jnp.asarray(pod_rows, jnp.float32)

    def f(x, fsh, rn, rm, ln, lm, rgn, rgm, Wpm):
        p = jax.lax.axis_index(pod_ax)
        j = jax.lax.axis_index(node_ax)
        xf = x.astype(jnp.float32)
        ff = fsh.astype(jnp.float32) + eps
        num = jax.lax.psum(ff * xf, node_ax)          # [1, ...] pod Σ F⊙θ
        den = jax.lax.psum(ff, node_ax)               # [1, ...] pod Σ F
        zn = num.reshape(1, -1)
        zm = den.reshape(1, -1)
        d = zn.shape[1]
        pad = (-d) % (per_pod * wire_block)
        if pad:
            zn = jnp.pad(zn, ((0, 0), (0, pad)))
            zm = jnp.pad(zm, ((0, 0), (0, pad)))
        clen = zn.shape[1] // per_pod
        z = jnp.concatenate([zn, zm], axis=0)         # [2, Dp]
        chunk = jax.lax.dynamic_slice_in_dim(z, j * clen, clen, 1)  # [2, ·]
        refs = jnp.concatenate([rn, rm], axis=0)
        q, s, ref2 = _ef_encode(chunk, refs, wire_block)
        ql = jax.lax.ppermute(q, pod_ax, fwd)
        sl = jax.lax.ppermute(s, pod_ax, fwd)
        lft2 = _ef_apply(jnp.concatenate([ln, lm], axis=0), ql, sl,
                         wire_block)
        r_self = Wpm[p, p]
        r_left = Wpm[p, (p - 1) % k_pods]
        num_mix = r_self * chunk[0:1] + r_left * lft2[0:1]
        den_mix = r_self * chunk[1:2] + r_left * lft2[1:2]
        if two_sided:
            qr = jax.lax.ppermute(q, pod_ax, bwd)
            sr = jax.lax.ppermute(s, pod_ax, bwd)
            rgt2 = _ef_apply(jnp.concatenate([rgn, rgm], axis=0), qr, sr,
                             wire_block)
            r_right = Wpm[p, (p + 1) % k_pods]
            num_mix = num_mix + r_right * rgt2[0:1]
            den_mix = den_mix + r_right * rgt2[1:2]
        mixed = num_mix / jnp.maximum(den_mix, 1e-30)  # [1, clen]
        full = jax.lax.all_gather(mixed, node_ax, tiled=True)
        out = full.reshape(1, per_pod * clen)[:, :d].reshape(xf.shape)
        if two_sided:
            return (out.astype(x.dtype), ref2[0:1], ref2[1:2],
                    lft2[0:1], lft2[1:2], rgt2[0:1], rgt2[1:2])
        return (out.astype(x.dtype), ref2[0:1], ref2[1:2],
                lft2[0:1], lft2[1:2])

    n_out = 7 if two_sided else 5

    def leaf(x, fsh, rn, rm, ln, lm, rgn, rgm, spec):
        in_spec = P(axis)
        sm = shard_map(f, mesh, in_specs=(in_spec,) * 8 + (P(),),
                       out_specs=(in_spec,) * n_out, check_vma=False)
        return sm(x, fsh, rn, rm, ln, lm, rgn, rgm, Wp)

    specs = _inner_spec_tree(stacked, inner_specs)
    ref, lft = wire["ref"], wire["left"]
    rgt = wire["right"] if two_sided else wire["left"]  # dummy at K=2
    outs = _leafwise(
        leaf, (stacked, fishers, ref["num"], ref["mass"], lft["num"],
               lft["mass"], rgt["num"], rgt["mass"], specs), n_out)
    if two_sided:
        merged, rn2, rm2, ln2, lm2, rgn2, rgm2 = outs
        return merged, {"ref": {"num": rn2, "mass": rm2},
                        "left": {"num": ln2, "mass": lm2},
                        "right": {"num": rgn2, "mass": rgm2}}
    merged, rn2, rm2, ln2, lm2 = outs
    return merged, {"ref": {"num": rn2, "mass": rm2},
                    "left": {"num": ln2, "mass": lm2}}


def matrix_gossip(stacked, W, mesh, axis: str, inner_specs=None,
                  wire_dtype=None):
    """General mixing matrix (dynamic membership): all_gather + local row mix."""
    n = axis_size(mesh, axis)

    def f(x, Wm):  # x: [per, ...]; Wm: [N, N]
        idx = jax.lax.axis_index(axis)
        per = x.shape[0]
        allx = jax.lax.all_gather(
            _wire_cast(x.astype(jnp.float32), wire_dtype), axis,
            tiled=True).astype(jnp.float32)                             # [N, ...]
        rows = jax.lax.dynamic_slice_in_dim(Wm, idx * per, per, 0)          # [per, N]
        flat = allx.reshape(allx.shape[0], -1)
        out = rows @ flat
        return out.reshape((per,) + x.shape[1:]).astype(x.dtype)

    Wj = jnp.asarray(W, jnp.float32)
    assert Wj.shape[0] == Wj.shape[1]
    return _mapped(f, mesh, axis, stacked, Wj, inner_specs=inner_specs)
