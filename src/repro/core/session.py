"""SwarmSession: ONE backend-agnostic entry point for P2P swarm learning.

`SwarmSession` drives the compiled `SwarmEngine` round on one device or on
a mesh behind a single API driven by one pytree, :class:`SwarmState`:

    session = SwarmSession(cfg, train_step, eval_fn, params=params,
                           data_sizes=sizes)          # backend="engine"
    log = session.round(batches, val)                 # T steps + gated sync
    session.leave(3); session.round(batches, val)     # zero retraces
    session.join(3)
    session.save("ckpt.msgpack")
    session = SwarmSession.restore("ckpt.msgpack", cfg, train_step, eval_fn,
                                   params=params, data_sizes=sizes)

Backends (construction-time choice; the API and its contract are the
same on both — stacked ``[..., N, ...]`` arrays in, logs of device arrays
out):

  * ``"engine"``  — the compiled stacked round (N param copies on one
    device): vmapped local steps, in-graph gate, fused Pallas commit.
  * ``"gossip"``  — the same round with the merge realized as mesh
    collectives (leading node axis sharded over ``axis``).

Dynamic membership is **runtime state**: ``session.join(i)`` / ``leave(i)``
flip one element of ``SwarmState.active`` — a device array consumed by the
traced topology builder (`topology.mixing_matrix_traced`), so a join→leave→
rejoin schedule mid-``run_rounds`` reuses the same compiled round with zero
retraces. Checkpoints round-trip the FULL state — params, opt state, merge-
strategy importance accumulators, membership mask, rng, and round/step
counters — through `checkpointing.io`.
"""
from __future__ import annotations

import dataclasses
import logging
from dataclasses import dataclass
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import tracing
from repro.checkpointing import load_metadata, load_pytree, save_pytree
from repro.configs.base import SwarmConfig
from repro.core import comms, gossip
from repro.core import merge_impl as merge_lib
from repro.core.engine import SwarmEngine
from repro.kernels.fused_merge import DEFAULT_BLOCK

logger = logging.getLogger(__name__)


@dataclass
class SwarmState:
    """The whole swarm as one pytree (both backends consume and return it).

    params / opt_state / stats are **stacked** pytrees (leading node axis N);
    ``stats`` carries the merge strategy's importance accumulators (None for
    mean/fedavg). ``wire`` is the quantized-sync error-feedback state: the
    θ̂ reference on the engine backend (`core.comms`), the schedule-specific
    sharded mesh EF pytree on the gossip backend (`core.gossip`), and None
    unless ``cfg.wire_dtype`` enables stateful wire compression.
    ``active`` is the runtime membership mask, ``rng`` a (legacy uint32)
    PRNG key folded once per round, ``round``/``step`` the global counters.
    All fields are data — membership changes, resumed counters, and reseeded
    rngs never trigger a recompile.
    """

    params: Any
    opt_state: Any = None
    stats: Any = None
    wire: Any = None
    active: Any = None
    rng: Any = None
    round: Any = 0
    step: Any = 0


jax.tree_util.register_dataclass(
    SwarmState,
    data_fields=["params", "opt_state", "stats", "wire", "active", "rng",
                 "round", "step"],
    meta_fields=[])


def _stack_per_node(value, n: int):
    """list/tuple of N per-node pytrees -> stacked; single pytree -> tiled.

    A TOP-LEVEL list/tuple is always read as "one entry per node". Params
    whose own pytree root is a list/tuple (e.g. a plain list of per-layer
    arrays) must therefore be wrapped — ``params=[p] * cfg.n_nodes`` — or
    passed pre-stacked via ``stacked=True``; they cannot be disambiguated
    from a per-node list by inspection.
    """
    if value is None:
        return None
    if isinstance(value, (list, tuple)):
        if len(value) != n:
            raise ValueError(
                f"expected {n} per-node pytrees, got a length-{len(value)} "
                "list/tuple. A top-level list/tuple is interpreted as one "
                "entry per node — wrap a list-rooted params pytree as "
                "[params] * n_nodes, or pass it pre-stacked (stacked=True)")
        return merge_lib.stack_params(list(value))
    return merge_lib.stack_params([value] * n)


class SwarmSession:
    """Backend-agnostic swarm driver over a single :class:`SwarmState`.

    Parameters
    ----------
    cfg : SwarmConfig
    train_step_fn : ``(params, opt_state, batch, step) -> (params, opt_state,
        metrics)`` — or the opt-in true-Fisher 4-tuple form that additionally
        returns per-step grads. Must be jax-traceable.
    eval_fn : ``(params, val) -> scalar in [0, 1]``, jax-traceable.
        Both fns may instead be a LIST of ``n_nodes`` per-node closures
        (model zoo: heterogeneous frozen backbones captured per closure,
        shared adapter payload as the state — ``cfg.payload="lora"``,
        engine backend only; see docs/heterogeneous.md).
    params / opt_state : a single per-node pytree (replicated N times), a
        list of N pytrees, or — with ``stacked=True`` — an already-stacked
        pytree with leading node axis.
    data_sizes : per-node dataset sizes (fedavg / weighted-merge weights).
    backend : ``"engine"`` (default) | ``"gossip"``.
    mesh / axis / param_specs : gossip backend placement; ``axis`` is a mesh
        axis name, or a 2-tuple ``("pod", "node")`` on a two-level mesh —
        gossip then runs over the joint axis and the per-link-class cost
        model may pick the hierarchical pod-delegate schedules.
    seed : session rng seed (defaults to ``cfg.seed``).
    shared : a frozen pytree every site uses alike (one backbone under
        per-site LoRA adapters), engine backend only. It is held once and
        enters each compiled round as an argument that the sites' vmap does
        not map over, never as a constant of the program; the train step
        and the eval take it as their last argument, ``train_step_fn(params,
        opt_state, batch, step, shared)`` and ``eval_fn(params, val,
        shared)``, and the state's params are what each site trains.
    """

    def __init__(self, cfg: SwarmConfig, train_step_fn: Optional[Callable],
                 eval_fn: Optional[Callable], *, params=None, opt_state=None,
                 data_sizes: Optional[Sequence[float]] = None,
                 backend: str = "engine", mesh=None, axis: Optional[str] = None,
                 param_specs=None, block: int = DEFAULT_BLOCK,
                 interpret: Optional[bool] = None, strategy=None,
                 seed: Optional[int] = None, stacked: bool = False,
                 shared=None):
        if backend not in ("engine", "gossip"):
            raise ValueError(f"unknown backend {backend!r}; choose "
                             '"engine" or "gossip"')
        zoo = isinstance(train_step_fn, (list, tuple)) or isinstance(
            eval_fn, (list, tuple))
        if shared is not None and (backend != "engine" or zoo):
            raise ValueError("a shared frozen base needs the engine backend "
                             "and one train step and eval for every site: "
                             "the gossip backend shards sites over devices "
                             "and zoo closures take no base")
        self.cfg = cfg
        self.backend = backend
        self.train_step_fn = train_step_fn
        self.eval_fn = eval_fn
        self.shared = shared
        # rounds this object has dispatched, counted on the host: the ``id``
        # of its `tracing` spans (the device's ``state.round`` is never read)
        self._dispatched = 0
        n = cfg.n_nodes
        if stacked:
            stacked_params, stacked_opt = params, opt_state
        else:
            stacked_params = _stack_per_node(params, n)
            stacked_opt = _stack_per_node(opt_state, n)
        if stacked_params is None:
            raise ValueError("SwarmSession needs initial params")
        rng = jax.random.PRNGKey(cfg.seed if seed is None else seed)
        self.engine = SwarmEngine(
            cfg, train_step_fn, eval_fn, data_sizes=data_sizes,
            backend=backend, mesh=mesh, axis=axis, param_specs=param_specs,
            block=block, interpret=interpret, strategy=strategy)
        # error-feedback wire state for the quantized sync — the engine
        # backend carries the θ̂ reference (shaped like the sync payload,
        # adapters only under lora_only); the gossip backend carries the
        # schedule-specific sharded mesh EF pytree; bf16-on-mesh is a
        # stateless cast (no state)
        wire = self.engine._auto_wire(stacked_params, None)
        self.mesh, self.axis = self.engine.mesh, axis
        self._placed = backend == "gossip" and gossip.spans_mesh(self.mesh,
                                                                 axis)
        self._state = self._place_state(SwarmState(
            params=stacked_params, opt_state=stacked_opt,
            stats=self.engine.init_stats(stacked_params), wire=wire,
            active=jnp.ones((n,), bool), rng=rng,
            round=jnp.asarray(0, jnp.int32), step=jnp.asarray(0, jnp.int32)))
        # cost-model-driven schedule choice, surfaced for logs/benchmarks;
        # predicted_link_bytes splits the prediction per link class on a
        # two-level ("pod", "node") mesh ({"intra": ..., "cross": ...})
        self.sync_schedule = self.engine.sync_schedule
        self.payload_params = comms.payload_param_count(
            stacked_params, comms.split_payload_at_sync(cfg), n)
        self.predicted_sync_bytes = self.sync_schedule.bytes_per_sync(
            self.payload_params)
        self.predicted_link_bytes = self.sync_schedule.bytes_by_link_class(
            self.payload_params)
        logger.info("sync schedule: %s",
                    self.sync_schedule.describe(self.payload_params))
        # the three compiled drivers; the state buffer is donated, so every
        # call consumes self._state and replaces it with the result
        self._round_jit = jax.jit(self._round_impl, donate_argnums=(0,))
        self._rounds_jit = jax.jit(self._rounds_impl, donate_argnums=(0,))
        self._local_jit = jax.jit(self._local_impl, donate_argnums=(0,))

    # -- mesh placement (gossip backend) -------------------------------------
    # On a mesh the swarm axis spans (one or more whole sites per device),
    # each site's params, optimizer state, stats, wire rows and data live on
    # the device that owns the site. The state, each round's batches and
    # ``val`` are placed there once, exactly as the compiled round returns
    # them, so the second round reuses the first one's program. With data or
    # model axes inside a site (param_specs), and on the engine backend,
    # placement is left to jit.

    def _on_mesh(self, tree, lead: int = 0):
        """Place ``tree`` with dim ``lead`` of every leaf (its node axis)
        sharded over the swarm axis. Identity unless ``self._placed``."""
        if not self._placed or tree is None:
            return tree

        def put(x):   # host arrays go straight to their shards
            sharded = jnp.ndim(x) > lead
            spec = P(*(None,) * lead, self.axis) if sharded else P()
            return jax.device_put(x, NamedSharding(self.mesh, spec))

        return jax.tree.map(put, tree)

    def _place_state(self, state: SwarmState) -> SwarmState:
        if not self._placed:
            return state
        wire = state.wire
        if wire is not None:
            wire = jax.device_put(
                wire, gossip.mesh_wire_shardings(wire, self.mesh, self.axis))
        return dataclasses.replace(
            state, params=self._on_mesh(state.params),
            opt_state=self._on_mesh(state.opt_state),
            stats=self._on_mesh(state.stats), wire=wire,
            active=self._replicated(state.active),
            rng=self._replicated(state.rng),
            round=self._replicated(state.round),
            step=self._replicated(state.step))

    def _replicated(self, x):
        """Whole on every device: the counters, the rng and the membership
        mask (it feeds the mixing-matrix build, whose [N, N] rows and
        columns both index nodes)."""
        if not self._placed:
            return x
        return jax.device_put(x, NamedSharding(self.mesh, P()))

    # -- state ---------------------------------------------------------------

    @property
    def state(self) -> SwarmState:
        return self._state

    def load_state(self, state: SwarmState) -> None:
        """Replace the session's state."""
        self._state = self._place_state(state)

    @property
    def node_params(self):
        """Per-node (unstacked) parameter pytrees."""
        return merge_lib.unstack_params(self._state.params, self.cfg.n_nodes)

    @property
    def active(self) -> np.ndarray:
        return np.asarray(self._state.active)

    # -- dynamic membership (runtime data; never recompiles) -----------------

    def join(self, node: int) -> None:
        """Node (re-)joins the swarm: flips one element of the active mask."""
        self._set_active_index(node, True)

    def leave(self, node: int) -> None:
        """Node leaves the swarm: excluded from every merge (its params and
        importance mass enter nobody's candidate, its own params pass through
        commits untouched). Local training is governed by DATA, not
        membership — a departed node keeps training on whatever batches the
        caller still supplies; feed it padding it can ignore to pause it."""
        self._set_active_index(node, False)

    def set_active(self, mask) -> None:
        self._state = dataclasses.replace(
            self._state,
            active=self._replicated(jnp.asarray(mask).astype(bool)))

    def _set_active_index(self, node: int, value: bool) -> None:
        self._state = dataclasses.replace(
            self._state,
            active=self._replicated(self._state.active.at[node].set(value)))

    def quarantine_wire(self, node: Optional[int] = None) -> None:
        """Reset the error-feedback wire state for a crash→rejoin.

        A node that left and came back holds a θ̂ reference the survivors
        kept advancing without it — telescoping against the stale reference
        would commit the divergence as if it were quantization error, so
        the rejoiner's EF state must be quarantined before its first sync.

        engine backend: zero ONE node's rows of the θ̂ reference (the next
        sync retransmits that node's full payload; everyone else's EF
        residual is untouched). gossip backend: the mesh EF pytree is a
        schedule-shaped sharded structure whose neighbour replicas must
        track the reference bit-exactly, so per-node surgery is unsafe —
        the whole mesh wire resets (`gossip.reset_mesh_wire`) and EF
        re-settles for everyone. No-op without wire state. Pure data
        update: never retraces.
        """
        if self._state.wire is None:
            return
        wire = self._state.wire
        if self.backend == "engine" and node is not None:
            new_wire = jax.tree.map(
                lambda x: None if x is None else x.at[node].set(0),
                wire, is_leaf=lambda v: v is None)
        else:
            new_wire = gossip.reset_mesh_wire(wire)
        self._state = dataclasses.replace(self._state, wire=new_wire)

    # -- compiled round bodies -----------------------------------------------
    # Thin SwarmState adapters over the engine's round implementations — the
    # serial and stale-by-one overlap scan bodies have exactly one home
    # (`SwarmEngine._round` / `_run_rounds` / `_run_local`).

    def _round_impl(self, state: SwarmState, batches, val, faults=None,
                    shared=None):
        t = jax.tree.leaves(batches)[0].shape[0]
        p, o, out = self.engine._round(state.params, state.opt_state, batches,
                                       val, state.active, state.step,
                                       state.stats, state.wire, faults,
                                       shared)
        st = out.pop("stats", None)
        wr = out.pop("wire", state.wire)
        new = SwarmState(
            params=p, opt_state=o, stats=st, wire=wr, active=state.active,
            rng=jax.random.fold_in(state.rng, state.round),
            round=state.round + 1, step=state.step + t)
        return new, out

    def _rounds_impl(self, state: SwarmState, batches, val, shared=None):
        shape = jax.tree.leaves(batches)[0].shape
        r, t = shape[0], shape[1]
        p, o, tm, logs = self.engine._run_rounds(
            state.params, state.opt_state, batches, val, state.active,
            state.step, state.stats, state.wire, shared)
        st = logs.pop("stats", None)
        wr = logs.pop("wire", state.wire)
        rng = state.rng
        for i in range(r):  # same per-round folds as r successive round()s
            rng = jax.random.fold_in(rng, state.round + i)
        new = SwarmState(
            params=p, opt_state=o, stats=st, wire=wr, active=state.active,
            rng=rng, round=state.round + r, step=state.step + r * t)
        return new, tm, logs

    def _local_impl(self, state: SwarmState, batches, shared=None):
        s_count = jax.tree.leaves(batches)[0].shape[0]
        p, o, tm, st = self.engine._run_local(
            state.params, state.opt_state, batches, state.step, state.stats,
            shared)
        new = dataclasses.replace(state, params=p, opt_state=o, stats=st,
                                  step=state.step + s_count)
        return new, tm

    # -- drivers -------------------------------------------------------------

    def round(self, batches, val, faults=None):
        """One full round: ``sync_every`` local steps + gated sync, as one
        compiled call.

        ``batches`` is a stacked ``[T, N, ...]`` pytree (a site that should
        not train gets padding its step ignores) and ``val`` a stacked
        ``[N, ...]`` pytree. Returns the round's log of device arrays:
        ``gates`` / ``metric_local`` / ``metric_merged`` (each [N]) and
        ``train`` ([T, N] per-step metrics), plus ``quorum_ok`` (with
        ``cfg.quorum``), ``fairness_ok``/``worst_site`` (with
        ``cfg.fairness_floor``) and ``wire_ok`` (with ``faults``).

        ``faults``: optional `repro.faults.signals.FaultSignals` for
        in-graph corrupt-wire injection (engine backend with a quantized
        wire only — see `SwarmEngine.sync`). Thread a signal (possibly
        `faults.idle_signals`) every round to keep one compiled trace.
        """
        with tracing.span("round", id=self._dispatched):
            self._dispatched += 1
            self._state, out = self._round_jit(
                self._state, self._on_mesh(batches, 1), self._on_mesh(val),
                faults, self.shared)
            return out

    def run_rounds(self, batches, val):
        """R rounds over stacked ``[R, T, N, ...]`` batches, scanned
        on-device in one compiled call. Returns the logs of :meth:`round`
        stacked over rounds: ``gates`` etc. ``[R, N]``, ``train``
        ``[R, T, N]``."""
        with tracing.span("round", id=self._dispatched):
            self._dispatched += jax.tree.leaves(batches)[0].shape[0]
            self._state, tm, logs = self._rounds_jit(
                self._state, self._on_mesh(batches, 2), self._on_mesh(val),
                self.shared)
            return dict(logs, train=tm)

    def run_local(self, batches):
        """Sync-free local training over stacked ``[S, N, ...]`` batches in
        one compiled call. Returns the ``[S, N]`` per-step train metrics."""
        with tracing.span("round", id=self._dispatched):
            self._state, tm = self._local_jit(
                self._state, self._on_mesh(batches, 1), self.shared)
            return tm

    # -- checkpoint / resume -------------------------------------------------

    def save(self, path: str) -> None:
        """Checkpoint the FULL session state (params, opt state, strategy
        stats, active mask, rng, counters) as one msgpack pytree."""
        state = self.state
        meta = {"cfg": dataclasses.asdict(self.cfg), "backend": self.backend,
                "round": int(state.round), "step": int(state.step),
                "format": 1}
        save_pytree(path, state, metadata=meta)

    def load(self, path: str) -> "SwarmSession":
        """Restore a checkpoint into this session (same cfg/param shapes)."""
        meta = load_metadata(path)
        saved_cfg = meta.get("cfg", {})
        for key in ("n_nodes", "merge", "topology", "lora_only",
                    "payload", "wire_dtype"):
            if key in saved_cfg and saved_cfg[key] != getattr(self.cfg, key):
                raise ValueError(
                    f"checkpoint cfg mismatch: {key}={saved_cfg[key]!r} "
                    f"saved vs {getattr(self.cfg, key)!r} in session")
        self.load_state(load_pytree(path, self.state))
        return self

    @classmethod
    def restore(cls, path: str, cfg: SwarmConfig, train_step_fn, eval_fn,
                **kwargs) -> "SwarmSession":
        """Build a session (constructor kwargs supply the param template)
        and restore the checkpointed state into it."""
        return cls(cfg, train_step_fn, eval_fn, **kwargs).load(path)


def load_checkpoint_params(path: str, params_template, *,
                           expect_nodes: Optional[int] = None):
    """Serving-plane ingest surface: read ONLY the stacked per-node params
    out of a full :meth:`SwarmSession.save` checkpoint.

    ``params_template`` is a stacked params pytree (leading node axis N)
    with the target shapes/dtypes/shardings — normally the serving
    ensemble's current live params. ``load_pytree`` restores by flattened
    key, so a params-only ``SwarmState`` template skips the checkpoint's
    opt state, merge stats, wire state and counters without materializing
    them. ``expect_nodes`` cross-checks the checkpoint cfg's ``n_nodes``
    so a serving ensemble can't silently ingest a differently-sized swarm.
    """
    meta = load_metadata(path)
    saved_cfg = meta.get("cfg", {})
    if (expect_nodes is not None and "n_nodes" in saved_cfg
            and saved_cfg["n_nodes"] != expect_nodes):
        raise ValueError(
            f"checkpoint has n_nodes={saved_cfg['n_nodes']}, the serving "
            f"ensemble expects {expect_nodes}")
    template = SwarmState(params=params_template, opt_state=None, stats=None,
                          wire=None, active=None, rng=None, round=None,
                          step=None)
    return load_pytree(path, template).params
