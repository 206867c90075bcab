"""Jitted stacked swarm engine: the whole P2P-SL round as ONE compiled program.

The paper's loop (§3.1) — `sync_every` local steps, peer exchange, 80 %-
validation gated commit — runs here end-to-end over **stacked pytrees**
(leading node axis N):

  local steps   ``jax.vmap`` of the user train step over the node axis, or
                the stacked form the step offers where one TPU holds
                several sites (``train_step_fn.stacked``; the histo step
                folds the sites into the DenseNet's lanes),
                ``jax.lax.scan`` over the ``sync_every`` time axis; the
                configured `merge_impl.MergeStrategy` accumulates per-node
                importance statistics (Fisher mass) in the same scan;
  propose       strategy-driven: mixing-matrix contraction or Fisher-
                weighted merge (engine backend) / mesh collectives (gossip
                backend, `core.gossip`) — every merge method in-graph;
  gate          in-graph validation metrics for local AND merged params
                (``jax.vmap`` of a traceable ``eval_fn``, or its stacked
                form as for the step) → per-node accept
                bits — no host scalar sync anywhere in the round;
  commit        `kernels.fused_merge.fused_merge_tree`: the Pallas kernel
                fuses contraction-over-nodes (W rows, optionally importance-
                weighted for fisher/gradmatch) and gating into one VMEM pass
                per leaf (interpret-mode on CPU).

API
---
**The public entry point is `repro.core.session.SwarmSession`**, which wraps
this engine behind a single `SwarmState` pytree (params, opt state, strategy
stats, runtime active mask, rng, counters) on both backends, and adds the
lifecycle layer: ``join``/``leave`` as pure state updates (zero retraces —
the mixing matrix is built in-graph by `topology.mixing_matrix_traced` from
the runtime mask) and ``save``/``restore`` checkpointing.

``SwarmEngine(cfg, train_step_fn, eval_fn, *, data_sizes, backend, ...)``

  * ``engine.round(params, opt_state, batches, val, active, step0, stats)``
      one jitted round: ``[T, N, ...]`` batches → T vmapped local steps +
      propose + gate + fused commit. ``(params, opt_state, stats)`` are
      donated, so the round updates buffers in place. ``out["stats"]``
      carries the updated importance accumulators for weighted merges.
  * ``engine.run_rounds(params, opt_state, batches, val, active, step0)``
      ``jax.lax.scan`` driver over ``[R, T, N, ...]`` batches: R full rounds
      with zero host round-trips between them (fisher/gradmatch statistics
      live inside the scan carry). Returns per-round train metrics and sync
      logs (gates / metric_local / metric_merged, ``[R, N]``). With
      ``cfg.overlap_sync`` the commit of round k is produced as a *side
      value* and folded in after round k+1's local steps (stale-by-one,
      double-buffered params) so the collective/merge overlaps compute.
  * ``engine.run_local(params, opt_state, batches, step0, stats)``
      sync-free local training over ``[S, N, ...]`` batches (isolated
      baselines, remainder steps) → ``(params, opt_state, metrics, stats)``;
      stats stays None unless accumulators are threaded in.
  * ``engine.propose(stacked, active, fishers)`` / ``engine.sync(...)``
      the pure pieces, reused by `launch.train.make_swarm_sync_step`.

``train_step_fn(params, opt_state, batch, step) -> (params, opt_state,
metrics)`` — or the opt-in true-Fisher 4-tuple form that additionally
returns per-step ``grads`` (consumed as exact squared gradients by
fisher/gradmatch accumulation) — and ``eval_fn(params, val) -> scalar in
[0, 1]`` must be jax-traceable.

Roofline
--------
The fused commit is memory-bound. For P stacked parameters the mean/fedavg
kernel moves 2N·P·4 bytes (read the [N, BLOCK] tile once per column block,
write N rows) — on TPU v5e (819 GB/s) that is ~9.8 µs per 10⁶ f32 params at
N = 4. The weighted (fisher/gradmatch) commit streams a second [N, BLOCK]
importance tile alongside the params, so it moves 3N·P·4 bytes — ~14.7 µs
per 10⁶ params at N = 4 — and fuses the numerator contraction, denominator
reduction, normalization, and gate select into that single pass; the unfused
XLA chain materializes numerator, denominator, candidate, and select as
separate HBM round-trips (~6N·P moved). Note the gate forces the candidate
to be materialized anyway (its validation metric is part of the round), so
the fused commit re-contracts W·θ (or ΣFθ/ΣF) rather than re-reading
candidate+local. Everything else in the round (vmapped train steps; the
squared-delta Fisher accumulation is one extra elementwise FMA per step) is
compute-bound, so a round's wall time approaches T × (single-node step time)
on hardware with N-way parallelism along the node axis. In
``overlap_sync`` mode the commit additionally leaves the critical path:
round k+1's local steps depend only on round k's *local* params, and the
merge/collective output is consumed one round late — on hardware with async
collectives the sync cost hides entirely behind the next T local steps.
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import SwarmConfig
import repro.core.topology as topo
from repro.core import comms
from repro.core import merge_impl as merge_lib
from repro.core.lora import combine, split_adapters
from repro.faults.signals import flip_payload_bits
from repro.kernels.fused_merge import (DEFAULT_BLOCK, fused_merge_tree,
                                       fused_quant_merge_tree)
from repro import tracing

# device names of the round's stages (`tracing.ROUND_SCOPES`): metadata
# only, they change no arithmetic and no fusion
LOCAL_STEPS, PROPOSE, GATE, COMMIT = tracing.ROUND_SCOPES


def default_interpret() -> bool:
    """Pallas interpret mode when no TPU is attached (validation mode)."""
    return jax.default_backend() != "tpu"


def lanes_tiled() -> bool:
    """Whether arrays sit in 128-lane tiles (the TPU), so that stacked
    forms that fold several sites into the lanes (`histo`'s) save the
    padding of each site's narrow channel axis; elsewhere their
    block-diagonal weights only multiply the work."""
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# model-zoo dispatch: per-node closures over a shared stacked payload
# ---------------------------------------------------------------------------
# In the heterogeneous payload="lora" mode every node's frozen backbone lives
# inside its own train/eval closure and only the shared adapter payload is
# stacked. A single vmap can't dispatch to N different programs, so zoo
# closure lists lower to an unrolled per-node call whose outputs restack —
# same (stacked in, stacked out) contract as the vmapped homogeneous path.
# Engine backend only: on gossip the node axis is sharded, and per-node
# indexing would lower to cross-shard gathers.

def _index_node(tree, i: int):
    """Row ``i`` of every stacked leaf (None subtrees pass through)."""
    return jax.tree.map(lambda x: x[i], tree)


def _stack_nodes(trees):
    """Inverse of :func:`_index_node` over a list of per-node pytrees."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def zoo_vstep(step_fns: Sequence[Callable]) -> Callable:
    """Stacked train-step dispatcher over per-node closures.

    Each ``step_fns[i]`` sees node i's (payload, opt_state, batch) rows and
    must return the same 3-tuple ``(params, opt_state, metrics)`` — or the
    true-Fisher 4-tuple — with structurally identical payload/metrics
    pytrees across nodes (the stacked-state contract; backbones may differ
    arbitrarily inside the closures)."""
    step_fns = list(step_fns)
    n = len(step_fns)

    def vstep(p, o, b, s):
        outs = [step_fns[i](_index_node(p, i), _index_node(o, i),
                            _index_node(b, i), s) for i in range(n)]
        k = len(outs[0])
        if any(len(out) != k for out in outs):
            raise ValueError("zoo train steps must agree on the 3-tuple vs "
                             "true-Fisher 4-tuple return form")
        return tuple(_stack_nodes([out[j] for out in outs])
                     for j in range(k))

    return vstep


def zoo_veval(eval_fns: Sequence[Callable]) -> Callable:
    """Stacked eval dispatcher: node i's closure scores its own payload row
    on its own validation rows → ``[N]`` metric vector."""
    eval_fns = list(eval_fns)

    def veval(p, val):
        return jnp.stack([fn(_index_node(p, i), _index_node(val, i))
                          for i, fn in enumerate(eval_fns)])

    return veval


def site_vmap(fn: Callable, in_axes: tuple) -> Callable:
    """``jax.vmap(fn, in_axes)`` over the sites; arguments beyond
    ``in_axes`` (the frozen base that every site shares, `SwarmSession`'s
    ``shared``) enter every site's call unmapped."""
    def vfn(*args):
        axes = in_axes + (None,) * (len(args) - len(in_axes))
        return jax.vmap(fn, in_axes=axes)(*args)

    return vfn


# ---------------------------------------------------------------------------
# pure building blocks (shared by the engine and the SPMD paths)
# ---------------------------------------------------------------------------

def mixing_matrix(cfg: SwarmConfig, data_sizes: Sequence[float],
                  active: Optional[Sequence[bool]] = None) -> np.ndarray:
    """Host-side (numpy) mixing matrix for the configured topology."""
    weights = topo.fedavg_weights(data_sizes) if cfg.merge == "fedavg" else None
    return topo.build_matrix(cfg.topology, cfg.n_nodes,
                             weights=weights, self_weight=cfg.self_weight,
                             active=active)


def active_weights(data_sizes, active=None) -> np.ndarray:
    """FedAvg weights zeroed + renormalized over the active membership.

    Departed nodes must not leak into fisher/gradmatch merges with full
    dataset weight — their mass is redistributed over the survivors.
    """
    w = np.asarray(data_sizes, np.float64)
    if active is not None:
        w = w * np.asarray(active, np.float64)
    s = w.sum()
    if s <= 0:  # nobody active: uniform (downstream gates reject everything)
        return np.full(len(w), 1.0 / len(w))
    return w / s


def active_weights_traced(data_sizes, active) -> jnp.ndarray:
    """In-graph version of :func:`active_weights` (active may be traced)."""
    w = jnp.asarray(data_sizes, jnp.float32) * active.astype(jnp.float32)
    s = w.sum()
    n = w.shape[0]
    return jnp.where(s > 0, w / jnp.where(s > 0, s, 1.0), jnp.full((n,), 1.0 / n))


# the mask-departed-nodes invariant lives in merge_impl; re-exported here for
# existing importers
mask_fishers = merge_lib.mask_fishers


# in-graph topology construction now lives in `core.topology`; re-exported
# here for existing importers
dynamic_matrix_traced = topo.dynamic_matrix_traced


def strategy_propose(stacked, cfg: SwarmConfig, W, *, fishers=None,
                     weights=None, strategy=None, rows=None):
    """Merge candidate for every node via the configured `MergeStrategy`.

    Honors lora_only payload selection. Returns ``(candidate, W_commit,
    imp)``: the candidate pytree plus the row-weight matrix / optional
    importance pytree (payload subtree when lora_only) that `host_commit`
    re-contracts through the fused Pallas kernel. ``rows`` (optional [N, N])
    switches fisher/gradmatch to the topology-restricted per-row merge —
    only graph-neighbour contributions enter each node's candidate.
    """
    strategy = strategy or merge_lib.get_strategy(cfg)
    if comms.split_payload_at_sync(cfg):
        adapters, base = split_adapters(stacked)
        f_payload = (split_adapters(fishers)[0] if fishers is not None
                     else None)
        cand, W_eff, imp = strategy.propose(adapters, W, weights=weights,
                                            fishers=f_payload, rows=rows)
        return combine(cand, base), W_eff, imp
    return strategy.propose(stacked, W, weights=weights, fishers=fishers,
                            rows=rows)


def propose_merge(stacked, cfg: SwarmConfig, W, *, fishers=None, weights=None):
    """Merge candidate for every node (candidate-only view of
    :func:`strategy_propose`, kept for existing callers)."""
    return strategy_propose(stacked, cfg, W, fishers=fishers,
                            weights=weights)[0]


def gate_decisions(metric_merged, metric_local, threshold: float,
                   mode: str = "relative"):
    """Per-node accept bits. `relative`: merged ≥ thr × local (robust default);
    `absolute`: merged ≥ thr (the paper's literal 80% reading)."""
    m, l = jnp.asarray(metric_merged), jnp.asarray(metric_local)
    if mode == "relative":
        return m >= threshold * l
    return m >= threshold


def gated_commit(candidate, local, gates):
    """θ_i ← gate_i ? merged_i : local_i (leading node axis) — the unfused
    where-select, used when the candidate is not a W-row mix (fisher/gradmatch)."""
    g = jnp.asarray(gates)

    def one(c, l):
        if c is None or l is None:
            return c if l is None else l
        gb = g.reshape((g.shape[0],) + (1,) * (c.ndim - 1))
        return jnp.where(gb, c, l)

    return jax.tree.map(one, candidate, local, is_leaf=lambda x: x is None)


def host_commit(stacked, candidate, W, gates, cfg: SwarmConfig, *, imp=None,
                block: int = DEFAULT_BLOCK, interpret: bool = False):
    """Commit via the fused Pallas kernel: mean/fedavg re-contract the W rows;
    fisher/gradmatch pass their per-leaf importance weights (``imp``) so the
    normalized weighted merge also runs in the single VMEM pass. Only a
    candidate with no kernel form (gossip backend) falls back to where-select.

    lora_only: only adapter leaves are re-merged; base leaves pass through
    local params bit-exactly (candidate base == local base by construction).
    """
    if cfg.merge in ("mean", "fedavg") or imp is not None:
        kw = dict(block=block, interpret=interpret)
        if comms.split_payload_at_sync(cfg):
            adapters, base = split_adapters(stacked)
            merged = fused_merge_tree(adapters, W, None, gates, imp=imp, **kw)
            return combine(merged, base)
        return fused_merge_tree(stacked, W, None, gates, imp=imp, **kw)
    return gated_commit(candidate, stacked, gates)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

class SwarmEngine:
    """Compiled stacked swarm: vmapped local steps + in-graph gated sync.

    backend="engine"  merge via mixing-matrix contraction, commit via the
                      fused Pallas kernel (N param copies on one device —
                      the paper-repro and benchmark path).
    backend="gossip"  merge via `core.gossip` mesh collectives (leading node
                      axis sharded over ``axis``); commit stays the in-graph
                      where-select, since the merged payload already lives on
                      each node's shard.
    """

    def __init__(self, cfg: SwarmConfig, train_step_fn: Optional[Callable],
                 eval_fn: Optional[Callable], *,
                 data_sizes: Optional[Sequence[float]] = None,
                 backend: str = "engine", mesh=None,
                 axis: Optional[str] = None,
                 param_specs=None, block: int = DEFAULT_BLOCK,
                 interpret: Optional[bool] = None,
                 strategy: Optional[merge_lib.MergeStrategy] = None):
        if backend not in ("engine", "gossip"):
            raise ValueError(f"unknown backend {backend!r}; choose "
                             '"engine" or "gossip"')
        if backend == "gossip" and (mesh is None or axis is None):
            raise ValueError("gossip backend needs mesh and axis")
        self.cfg = cfg
        self.backend = backend
        if backend == "gossip":
            from repro.core import gossip
            mesh = gossip.auto_mesh(mesh)
        self.mesh, self.axis, self.param_specs = mesh, axis, param_specs
        self.block = block
        self.interpret = default_interpret() if interpret is None else interpret
        self.data_sizes = (np.ones(cfg.n_nodes) if data_sizes is None
                           else np.asarray(data_sizes, np.float64))
        self.strategy = strategy or merge_lib.get_strategy(cfg)
        self.wire_dtype = comms.validate_wire_dtype(
            getattr(cfg, "wire_dtype", "f32"))
        self.wire_block = comms.validate_wire_block(
            getattr(cfg, "wire_block", 512))
        # static degradation policy (resolved here, not inside the traced
        # sync): minimum active membership for any commit — docs/faults.md
        self.quorum = int(getattr(cfg, "quorum", 0) or 0)
        if self.quorum > cfg.n_nodes:
            raise ValueError(f"quorum={self.quorum} can never be met with "
                             f"n_nodes={cfg.n_nodes}")
        # what the stacked state covers (full pytree vs adapter-only flat
        # payload) and whether sync still needs to carve the adapter subtree
        # out of it — docs/heterogeneous.md
        self.payload_mode = comms.payload_mode(cfg)
        self._split_lora = comms.split_payload_at_sync(cfg)
        # per-site fairness floor, ANDed into the commit gate like quorum
        self.fairness_floor = float(getattr(cfg, "fairness_floor", 0.0) or 0.0)
        if not 0.0 <= self.fairness_floor <= 1.0:
            raise ValueError("fairness_floor must be a gate-metric value in "
                             f"[0, 1], got {self.fairness_floor}")
        # the comms cost model picks the sync schedule at trace time: for
        # the gossip backend this decides which collectives propose lowers
        # to; for engine it reports the SPMD-equivalent wire cost
        # (simulated).
        # model-sharded payloads (inner param specs) drop the q8 psum
        # reductions from the candidate set — they chunk the globally-
        # flattened payload, which a model axis would scramble.
        # The swarm axis may be a 2-tuple of mesh axis names — a two-level
        # ("pod", "node") mesh: flat schedules then run over the joint axis
        # and the per-link-class cost model decides whether the hierarchical
        # pod-delegate forms win (cfg.intra_pod_cost / cfg.cross_pod_cost).
        self._axis_size = None
        self.mesh_shape = None
        if backend == "gossip":
            if isinstance(axis, tuple):
                size = 1
                for a in axis:
                    size *= mesh.shape[a]
                self._axis_size = size
                if len(axis) == 2:
                    self.mesh_shape = (mesh.shape[axis[0]],
                                       mesh.shape[axis[1]])
            else:
                self._axis_size = mesh.shape[axis]
        per = 1 if backend != "gossip" else max(
            1, cfg.n_nodes // self._axis_size)
        self.sync_schedule = comms.pick_schedule(
            cfg, per=per, simulated=(backend != "gossip"),
            model_sharded=(backend == "gossip"
                           and comms.has_inner_sharding(param_specs)),
            mesh_shape=self.mesh_shape)
        # per-node closure lists ("model zoo", heterogeneous backbones)
        # dispatch through the unrolled zoo_vstep/zoo_veval instead of vmap
        zoo = (isinstance(train_step_fn, (list, tuple))
               or isinstance(eval_fn, (list, tuple)))
        if zoo and backend == "gossip":
            raise ValueError(
                "per-node closure lists (model zoo) are engine-backend only: "
                "the gossip backend shards the node axis and per-node "
                "dispatch would lower to cross-shard gathers")

        def _fn_list(fn, what):
            fns = list(fn)
            if len(fns) != cfg.n_nodes:
                raise ValueError(f"{what} zoo must list one closure per node "
                                 f"(got {len(fns)}, n_nodes={cfg.n_nodes})")
            return fns

        # A step or eval may offer a stacked form as its ``stacked``
        # attribute: every site in one call, stacked in and out, returning
        # what its vmap returns (`histo._make_model_fns` folds the sites
        # into the DenseNet's lanes). It is taken where one TPU holds
        # several sites; zoo lists and the gossip backend keep their paths.
        fold = backend == "engine" and cfg.n_nodes > 1 and lanes_tiled()

        def _form(fn):
            if fn is None:
                return None
            if isinstance(fn, (list, tuple)):
                return "zoo"
            return "stacked" if fold and hasattr(fn, "stacked") else "vmap"

        self.forms = {LOCAL_STEPS: _form(train_step_fn),
                      GATE: _form(eval_fn)}
        form = self.forms[LOCAL_STEPS]
        if form == "zoo":
            self._vstep = zoo_vstep(_fn_list(train_step_fn, "train_step_fn"))
        elif form == "stacked":
            self._vstep = train_step_fn.stacked
        else:
            self._vstep = (None if train_step_fn is None
                           else site_vmap(train_step_fn, (0, 0, 0, None)))
        form = self.forms[GATE]
        if form == "zoo":
            self._veval = zoo_veval(_fn_list(eval_fn, "eval_fn"))
        elif form == "stacked":
            self._veval = eval_fn.stacked
        else:
            self._veval = (None if eval_fn is None
                           else site_vmap(eval_fn, (0, 0)))
        tracing.annotate(forms=self.forms, folded_sites=(
            cfg.n_nodes if "stacked" in self.forms.values() else 0))
        from repro.core import gossip
        if backend == "gossip" and gossip.spans_mesh(mesh, axis):
            # each device steps and scores its own sites (gossip.per_shard);
            # with data/model axes inside a site (param_specs), the
            # partitioner lays the step out from the sync's shardings
            if self._vstep is not None:
                self._vstep = gossip.per_shard(self._vstep, mesh, axis,
                                               replicated=(3,))
            if self._veval is not None:
                self._veval = gossip.per_shard(self._veval, mesh, axis)
        self._base_W = mixing_matrix(cfg, self.data_sizes)
        self.spectral_gap = topo.spectral_gap(self._base_W)

        # jitted entry points; (params, opt_state, stats) buffers are donated
        # so a round updates in place — callers must not reuse the inputs.
        self.round = jax.jit(self._round, donate_argnums=(0, 1, 6))
        self.run_rounds = jax.jit(self._run_rounds, donate_argnums=(0, 1))
        self.run_local = jax.jit(self._run_local, donate_argnums=(0, 1, 4))

    def init_stats(self, stacked):
        """Strategy importance accumulators (None for mean/fedavg)."""
        return (self.strategy.init_stats(stacked)
                if self.strategy.uses_stats else None)

    # -- local training ------------------------------------------------------

    def local_steps(self, params, opt_state, batches, step0, stats=None,
                    shared=None):
        """scan over the leading [T] time axis of vmapped local steps; the
        strategy's importance accumulation rides in the same scan.
        ``shared``: the frozen base every site's step takes as its last
        argument, unmapped (None: the step takes none).

        ``train_step_fn`` may opt into the true-Fisher hook by returning a
        4-tuple ``(params, opt_state, metrics, grads)``: the per-step grads
        feed ``strategy.accumulate_grads`` (exact squared gradients) instead
        of the Δθ² proxy.
        """
        def body(carry, batch):
            p, o, st, s = carry
            out = self._vstep(p, o, batch, s, *extra)
            if len(out) == 4:
                p2, o2, m, grads = out
                if st is not None:
                    st = self.strategy.accumulate_grads(st, grads, s)
            else:
                p2, o2, m = out
                if st is not None:
                    st = self.strategy.accumulate(st, p, p2, s)
            return (p2, o2, st, s + 1), m

        extra = () if shared is None else (shared,)
        init = (params, opt_state, stats, jnp.asarray(step0, jnp.int32))
        with jax.named_scope(LOCAL_STEPS):
            (p, o, st, _), metrics = jax.lax.scan(body, init, batches)
        return p, o, st, metrics

    # -- propose -------------------------------------------------------------

    def propose(self, stacked, active=None, fishers=None, stats=None):
        """Merge candidate for every node.

        Returns ``(candidate, W_commit, imp)`` — ``W_commit``/``imp`` are
        None on the gossip backend (commit is the in-graph where-select).
        """
        if fishers is None and stats is not None:
            fishers = stats
        if self.backend == "gossip":
            return self._propose_gossip(stacked, active, fishers)[0], None, None
        n = self.cfg.n_nodes
        a = (jnp.ones((n,), bool) if active is None
             else jnp.asarray(active).astype(bool))
        W = self._traced_W(a)
        w = active_weights_traced(self.data_sizes, a)
        if self.strategy.uses_stats and fishers is None:
            # no evidence for any node -> zero mass everywhere, which the
            # eps floor turns into a uniform mean
            fishers = jax.tree.map(jnp.zeros_like, stacked)
        fishers = self.strategy.finalize_mass(fishers, a)
        rows = None
        if self.strategy.uses_stats and self.cfg.topology in ("ring",
                                                              "dynamic"):
            # topology-restricted weighted merge: only graph-neighbour
            # contributions enter each node's fisher/gradmatch candidate
            rows = self.strategy.topo_rows(W, w)
        return strategy_propose(stacked, self.cfg, W, fishers=fishers,
                                weights=w, strategy=self.strategy, rows=rows)

    def _pod_rows(self):
        """Pod-level ring mixing matrix for the hierarchical schedules
        ([K, K], K = number of pods). `topo.ring_matrix` folds both
        neighbour edges onto the single peer at K = 2, so the pair mesh
        mixes s·ā_self + (1−s)·ā_peer."""
        return jnp.asarray(
            topo.ring_matrix(self.mesh_shape[0], self.cfg.self_weight),
            jnp.float32)

    def _traced_W(self, active):
        """The round's mixing matrix, built in-graph from the runtime active
        mask (join/leave/failure never retraces the compiled round)."""
        weights = self.data_sizes if self.cfg.merge == "fedavg" else None
        return topo.mixing_matrix_traced(self.cfg.topology, active,
                                         weights=weights,
                                         self_weight=self.cfg.self_weight)

    def _propose_gossip(self, stacked, active, fishers, wire=None):
        """Merge on the mesh, lowered to the collective schedule the comms
        cost model picked at construction (`self.sync_schedule`):

          fedavg_psum / fisher_psum       — global weighted psum(s), f32
          *_psum_q8                       — compression-aware reduction:
                                            int8 reduce-scatter + all_gather
          ring_ppermute / ring_topo_...   — two point-to-point ppermutes
          gathered_rows / gathered_topo_… — one all_gather + row contraction
          hier_*_ring_q8                  — two-level ("pod", "node") mesh:
                                            intra-pod psum reduce → cross-pod
                                            delegate int8 EF ring → intra-pod
                                            all_gather broadcast

        Point-to-point schedules wire-cast their payloads per
        ``cfg.wire_dtype``; with ``wire_dtype="int8"`` every schedule runs
        its error-feedback q8 form against the sharded mesh wire state
        (``wire``; auto-initialized to zero when not threaded).

        Returns ``(merged, new_wire)`` — ``new_wire`` is None unless the
        int8 mesh wire is active."""
        from repro.core import gossip
        from jax.sharding import PartitionSpec as P

        cfg, specs = self.cfg, self.param_specs
        sched = self.sync_schedule.name
        q8 = self.wire_dtype == "int8"
        wire_cast = None if self.wire_dtype == "f32" or q8 else self.wire_dtype
        if q8 and wire is None:
            wire = self._auto_wire(stacked, None)
        # merge="mean" averages uniformly (its W is uniform); only fedavg
        # folds dataset sizes into the psum weights
        sizes = (jnp.asarray(self.data_sizes, jnp.float32)
                 if cfg.merge == "fedavg"
                 else jnp.ones(cfg.n_nodes, jnp.float32))
        weights = sizes / sizes.sum()
        if self._split_lora:
            payload, base = split_adapters(stacked)
            if specs is not None:
                specs = split_adapters(
                    specs, is_leaf=lambda x: isinstance(x, P))[0]
            if fishers is not None:
                fishers = split_adapters(fishers)[0]
        else:
            payload, base = stacked, None

        new_wire = None
        qkw = dict(wire_block=self.wire_block)
        if cfg.merge in ("fisher", "gradmatch"):
            if fishers is None:
                if not self.strategy.uses_stats:
                    raise ValueError(f"{cfg.merge} merge needs fisher "
                                     "estimates or strategy stats")
                fishers = jax.tree.map(jnp.zeros_like, payload)
            a = (jnp.ones((cfg.n_nodes,), bool) if active is None
                 else jnp.asarray(active).astype(bool))
            fishers = self.strategy.finalize_mass(fishers, a)
            w = active_weights_traced(self.data_sizes, a)
            if sched == "hier_fisher_ring_q8":
                # two-level mesh: intra-pod psums reduce the (num ⊕ mass)
                # side channel, the pod-ring mixing matrix plays the role of
                # the flat forms' topo rows (membership within a pod rides
                # the finalized mass; a fully-absent pod is out of scope)
                fishers = self.strategy.gossip_mass(fishers, w)
                merged, new_wire = gossip.hier_fisher_ring_q8(
                    payload, fishers, self._pod_rows(), wire, self.mesh,
                    self.axis, inner_specs=specs, eps=self.strategy.eps,
                    **qkw)
            elif sched in ("fisher_psum", "fisher_psum_q8"):
                # the strategy owns any weight-folding identity (gradmatch ≡
                # w-weighted fisher ratio) — the two psums / the two EF
                # delta-consensus streams do the rest
                fishers = self.strategy.gossip_mass(fishers, w)
                if sched == "fisher_psum_q8":
                    merged, new_wire = gossip.fisher_psum_q8(
                        payload, fishers, wire, self.mesh, self.axis,
                        inner_specs=specs, eps=self.strategy.eps, **qkw)
                else:
                    merged = gossip.fisher_gossip(payload, fishers, self.mesh,
                                                  self.axis, inner_specs=specs)
            else:
                # topology-restricted weighted merge on the mesh: per-row
                # ratio over graph-neighbour contributions only, matching
                # the engine backend's `topo_weighted_merge` rows
                rows = self.strategy.topo_rows(self._traced_W(a), w)
                if q8:
                    fn = (gossip.ring_topo_fisher_gossip_q8
                          if sched == "ring_topo_ppermute"
                          else gossip.topo_fisher_gossip_q8)
                    merged, new_wire = fn(payload, fishers, rows, wire,
                                          self.mesh, self.axis,
                                          inner_specs=specs,
                                          eps=self.strategy.eps, **qkw)
                else:
                    fn = (gossip.ring_topo_fisher_gossip
                          if sched == "ring_topo_ppermute"
                          else gossip.topo_fisher_gossip)
                    merged = fn(payload, fishers, rows, self.mesh, self.axis,
                                inner_specs=specs, eps=self.strategy.eps,
                                wire_dtype=wire_cast)
        elif sched in ("fedavg_psum", "fedavg_psum_q8",
                       "hier_fedavg_ring_q8"):
            a = (None if active is None
                 else jnp.asarray(active).astype(bool))
            # runtime membership stays on the psum schedule: weights are
            # active-masked + renormalized in-graph, and absent nodes keep
            # their own params in the candidate (same semantics as the
            # masked mixing rows, at psum instead of gather cost)
            w_eff = (jnp.asarray(weights, jnp.float32) if a is None
                     else active_weights_traced(sizes, a))
            if sched == "hier_fedavg_ring_q8":
                # intra-pod weighted reduce normalizes per pod (the pod
                # average is invariant to the global renormalization), then
                # pod averages mix over the pod ring
                merged, new_wire = gossip.hier_fedavg_ring_q8(
                    payload, w_eff, self._pod_rows(), wire, self.mesh,
                    self.axis, inner_specs=specs, **qkw)
            elif sched == "fedavg_psum_q8":
                merged, new_wire = gossip.fedavg_psum_q8(
                    payload, w_eff, wire, self.mesh, self.axis,
                    inner_specs=specs, **qkw)
            else:
                merged = gossip.fedavg_gossip(payload, w_eff, self.mesh,
                                              self.axis, inner_specs=specs)
            if a is not None:
                def keep_absent(m, x):
                    if m is None:
                        return None
                    ab = a.reshape((a.shape[0],) + (1,) * (m.ndim - 1))
                    return jnp.where(ab, m, x)

                merged = jax.tree.map(keep_absent, merged, payload,
                                      is_leaf=lambda v: v is None)
        else:
            # in-graph masking so a traced active mask works under jit too
            a = (jnp.ones((cfg.n_nodes,), bool) if active is None
                 else jnp.asarray(active).astype(bool))
            W = self._traced_W(a)
            if sched == "ring_ppermute":
                if q8:
                    merged, new_wire = gossip.ring_rows_gossip_q8(
                        payload, W, wire, self.mesh, self.axis,
                        inner_specs=specs, **qkw)
                else:
                    merged = gossip.ring_rows_gossip(payload, W, self.mesh,
                                                     self.axis,
                                                     inner_specs=specs,
                                                     wire_dtype=wire_cast)
            elif q8:
                merged, new_wire = gossip.matrix_gossip_q8(
                    payload, W, wire, self.mesh, self.axis,
                    inner_specs=specs, **qkw)
            else:
                merged = gossip.matrix_gossip(payload, W, self.mesh,
                                              self.axis, inner_specs=specs,
                                              wire_dtype=wire_cast)

        return (combine(merged, base) if self._split_lora else merged), new_wire

    # -- gated sync ----------------------------------------------------------

    def _auto_wire(self, params, wire):
        """Default EF wire reference when ``cfg.wire_dtype`` enables
        compression but the caller didn't thread state (the direct engine
        tuple API): a zero reference per call — stateless quantization, so
        the knob is honoured (never a silent f32 no-op) even without the
        session's carried ``SwarmState.wire``. On the gossip backend the
        int8 wire state is the schedule-specific sharded mesh EF pytree
        (`gossip.init_mesh_wire`); bf16 stays a stateless cast (no state)."""
        if wire is not None or self.wire_dtype == "f32":
            return wire
        payload = (split_adapters(params)[0] if self._split_lora
                   else params)
        if self.backend == "engine":
            return comms.init_wire(payload)
        if self.wire_dtype != "int8":
            return None
        from repro.core import gossip
        return gossip.init_mesh_wire(self.sync_schedule.name, payload,
                                     n_shards=self._axis_size,
                                     wire_block=self.wire_block,
                                     mesh_shape=self.mesh_shape)

    def sync(self, params, val, active=None, stats=None, wire=None,
             faults=None, shared=None):
        """propose → in-graph validate → gate → fused commit. Pure/traceable.

        ``wire``: the error-feedback wire state from `core.comms` /
        `core.gossip` — peers merge the int8/bf16 wire reconstruction θ̂'
        instead of the exact params and rejected nodes keep exact f32
        locals. On the engine backend the commit runs through the fused Pallas
        quantize→merge→dequantize kernel; on the gossip backend the q8
        collective schedules advance the sharded mesh EF state in-graph.
        The advanced state is returned in the log under ``"wire"``.

        ``faults``: optional `repro.faults.signals.FaultSignals` — in-graph
        corrupt-wire injection. Flagged nodes' effective payloads arrive
        bit-flipped; the per-payload checksum (`comms.payload_checksum`)
        detects the damage and the sender is quarantined for the round
        (reject-and-keep-local: excluded from the merge AND gated off, so
        nobody — including the sender — commits corrupted bytes). Only the
        wire-carrying engine backend supports injection; pass drops
        (membership masking) elsewhere. Both ``faults`` fields are runtime
        data, so arming/disarming never retraces. ``shared``: the frozen
        base the gate's eval takes as its last argument (see
        `local_steps`).
        """
        n = self.cfg.n_nodes
        a = (jnp.ones((n,), bool) if active is None
             else jnp.asarray(active).astype(bool))
        wire = self._auto_wire(params, wire)
        use_wire = wire is not None and self.backend == "engine"
        use_mesh_wire = wire is not None and self.backend == "gossip"
        if faults is not None and not use_wire:
            raise ValueError(
                "in-graph corrupt-wire injection (faults=) requires the "
                "engine backend with a quantized/EF wire (SwarmState.wire); "
                "lower corrupt events to drops instead "
                "(FaultPlan.lower(corrupt_in_graph=False))")
        log = {}
        with jax.named_scope(PROPOSE):
            if use_wire:
                if self._split_lora:
                    payload, base = split_adapters(params)
                else:
                    payload, base = params, None
                # θ̂' — what every peer reconstructs from this round's
                # wire traffic; also next round's reference (EF: the
                # residual θ−θ̂' is exactly this round's quantization error)
                eff_payload = comms.wire_effective(
                    payload, wire, self.wire_dtype, self.wire_block)
                if faults is not None:
                    # sender-side checksum of the honest reconstruction,
                    # then the (deterministic, seeded) wire damage, then the
                    # receiver-side checksum: a mismatch quarantines the
                    # sender for this round exactly like an absence.
                    sent = comms.payload_checksum(eff_payload)
                    eff_payload = flip_payload_bits(
                        eff_payload, faults.corrupt, faults.key)
                    wire_ok = jnp.equal(sent,
                                        comms.payload_checksum(eff_payload))
                    a = a & wire_ok
                    log["wire_ok"] = wire_ok
                eff = (combine(eff_payload, base) if base is not None
                       else eff_payload)
                fishers = None
                if self.strategy.uses_stats:
                    f = (stats if stats is not None
                         else jax.tree.map(jnp.zeros_like, params))
                    f = self.strategy.finalize_mass(f, a)
                    if self._split_lora:
                        # only the payload's mass crosses the wire — don't
                        # burn a full-model quantize pass on base leaves
                        # propose will immediately discard
                        f = split_adapters(f)[0]
                    # importance mass crosses the wire too (stateless
                    # round-trip: mass errors cancel in the merge ratio, no
                    # EF state needed; propose re-finalizes, which only
                    # rescales — the merge ratio is scale-free)
                    fishers = comms.quant_dequant_tree(f, self.wire_dtype,
                                                       self.wire_block)
                candidate, W, imp = self.propose(eff, a, fishers=fishers,
                                                 stats=None)
            elif use_mesh_wire:
                # sharded mesh EF wire: the q8 collective schedule quantizes,
                # exchanges, and reconstructs in-graph; stats are the raw
                # importance accumulators (finalized inside _propose_gossip)
                candidate, new_mesh_wire = self._propose_gossip(
                    params, active, stats, wire)
                W = imp = None
                log["wire"] = new_mesh_wire
            else:
                candidate, W, imp = self.propose(params, active, stats=stats)
        extra = () if shared is None else (shared,)
        with jax.named_scope(GATE):
            if self.forms[GATE] == "stacked":
                # both scorings through one loop body: the device holds one
                # copy of the stacked forward's code, which is larger than
                # the vmapped one's
                pair = jax.tree.map(lambda *x: jnp.stack(x), params,
                                    candidate)
                local, merged = jax.lax.map(
                    lambda p: self._veval(p, val, *extra), pair)
            else:
                local = self._veval(params, val, *extra)
                merged = self._veval(candidate, val, *extra)
            metric_local = jnp.where(a, local, 1.0)
            metric_merged = jnp.where(a, merged, 0.0)
            gates = gate_decisions(metric_merged, metric_local,
                                   self.cfg.val_threshold) & a
            q = self.quorum
            if q > 0:
                # degradation policy: below quorum the whole round holds
                # locals — every gate closes and the sync is a no-op commit.
                # In-graph on the runtime mask, so membership swings never
                # retrace.
                quorum_ok = jnp.sum(a.astype(jnp.int32)) >= q
                gates = gates & quorum_ok
                log["quorum_ok"] = quorum_ok
            if self.fairness_floor > 0.0:
                # per-site fairness floor (docs/heterogeneous.md): the merged
                # candidate must clear cfg.gate_metric at EVERY active site or
                # the whole swarm holds its locals — a commit that helps the
                # average while degrading the worst site never lands. Inactive
                # sites read as 1.0 so they never drag the min; in-graph on the
                # traced metrics, so metric/membership swings never retrace.
                worst = jnp.min(jnp.where(a, metric_merged, 1.0))
                fair_ok = worst >= self.fairness_floor
                gates = gates & fair_ok
                log["fairness_ok"] = fair_ok
                log["worst_site"] = worst
        with jax.named_scope(COMMIT):
            if use_wire:
                committed_payload, new_wire = fused_quant_merge_tree(
                    payload, wire, W, gates, imp=imp,
                    wire_dtype=self.wire_dtype, wire_block=self.wire_block,
                    block=self.block, interpret=self.interpret)
                committed = (combine(committed_payload, base)
                             if base is not None else committed_payload)
                log["wire"] = new_wire
            elif self.backend == "engine":
                committed = host_commit(params, candidate, W, gates, self.cfg,
                                        imp=imp, block=self.block,
                                        interpret=self.interpret)
            else:
                committed = gated_commit(candidate, params, gates)
        return committed, dict(log, gates=gates, metric_local=metric_local,
                               metric_merged=metric_merged)

    # -- jitted drivers ------------------------------------------------------

    def _round(self, params, opt_state, batches, val, active=None, step0=0,
               stats=None, wire=None, faults=None, shared=None):
        """T local steps + one gated sync — a single compiled program."""
        if stats is None:
            stats = self.init_stats(params)
        params, opt_state, stats, train_metrics = self.local_steps(
            params, opt_state, batches, step0, stats, shared)
        params, log = self.sync(params, val, active, stats=stats, wire=wire,
                                faults=faults, shared=shared)
        out = dict(log, train=train_metrics)
        if stats is not None:
            out["stats"] = stats
        return params, opt_state, out

    def _run_rounds(self, params, opt_state, batches, val, active=None,
                    step0=0, stats=None, wire=None, shared=None):
        """scan over R rounds of [R, T, N, ...] batches; no host round-trips.

        Fisher/gradmatch importance accumulators live inside the scan carry,
        so weighted merges run across all R rounds without ever leaving the
        device. ``cfg.overlap_sync`` switches to the double-buffered
        stale-by-one schedule: round k's commit delta is a side value folded
        in after round k+1's local steps, taking the merge (collective on the
        gossip backend) off the critical path at the cost of one round of
        staleness in the consensus signal.
        """
        t = jax.tree.leaves(batches)[0].shape[1]
        if stats is None:
            stats = self.init_stats(params)
        # init the wire ref OUTSIDE the scan so the carry structure is
        # round-invariant (and EF state actually accumulates across rounds)
        wire = self._auto_wire(params, wire)
        step0 = jnp.asarray(step0, jnp.int32)

        if not self.cfg.overlap_sync:
            def body(carry, round_batches):
                p, o, st, wr, s = carry
                p, o, st, tm = self.local_steps(p, o, round_batches, s, st,
                                                shared)
                p, log = self.sync(p, val, active, stats=st, wire=wr,
                                   shared=shared)
                wr = log.pop("wire", wr)   # wire ref rides the carry, not
                return (p, o, st, wr, s + t), (tm, log)  # the stacked logs

            init = (params, opt_state, stats, wire, step0)
            (p, o, st, wr, _), (train_metrics, logs) = jax.lax.scan(
                body, init, batches)
            if st is not None:   # final accumulators, for chunked callers
                logs = dict(logs, stats=st)
            if wr is not None:
                logs = dict(logs, wire=wr)
            return p, o, train_metrics, logs

        def body(carry, round_batches):
            p, o, st, wr, s, pending = carry
            # local steps depend on the previous round's LOCAL params (plus
            # the already-available stale delta) — never on the in-flight
            # merge, so the sync below can overlap them on hardware.
            p_loc, o, st, tm = self.local_steps(p, o, round_batches, s, st,
                                                shared)
            committed, log = self.sync(p_loc, val, active, stats=st, wire=wr,
                                       shared=shared)
            wr = log.pop("wire", wr)
            delta = jax.tree.map(lambda c, l: c - l, committed, p_loc)
            p_next = jax.tree.map(lambda l, d: l + d, p_loc, pending)
            return (p_next, o, st, wr, s + t, delta), (tm, log)

        zeros = jax.tree.map(jnp.zeros_like, params)
        init = (params, opt_state, stats, wire, step0, zeros)
        (p, o, st, wr, _, pending), (train_metrics, logs) = jax.lax.scan(
            body, init, batches)
        # fold in the last round's commit so no accepted merge is dropped
        p = jax.tree.map(lambda l, d: l + d, p, pending)
        if st is not None:       # final accumulators, for chunked callers
            logs = dict(logs, stats=st)
        if wr is not None:
            logs = dict(logs, wire=wr)
        return p, o, train_metrics, logs

    def _run_local(self, params, opt_state, batches, step0=0, stats=None,
                   shared=None):
        """Sync-free local training over [S, N, ...] batches. Returns
        ``(params, opt_state, metrics, stats)`` — stats is None unless
        importance accumulators were passed in (accumulation only runs when
        the caller threads them)."""
        p, o, st, metrics = self.local_steps(params, opt_state, batches,
                                             step0, stats, shared)
        return p, o, metrics, st
