"""Model-merging algorithms for swarm aggregation.

All merges operate on **stacked pytrees**: every leaf carries a leading node
axis N. This single representation serves both execution modes:

  * the engine backend (paper repro, N param copies on one device),
  * SPMD swarm (leading axis sharded over the mesh's `node`/`pod` axis, where
    the einsum against the mixing matrix lowers to the gossip collectives).

Implemented merges (paper §2 taxonomy):
  mean / fedavg — arithmetic & dataset-size-weighted averaging (the paper's
                  own mechanism; weighting is folded into the mixing matrix)
  fisher        — diagonal-Fisher-weighted averaging (Matena & Raffel style;
                  cited by the paper as the principled upgrade)
  gradmatch     — uncertainty-based gradient matching (Daheim et al. [6]):
                  Fisher-preconditioned delta correction around a reference

`MergeStrategy` wraps each method as a traceable first-class object with
``init_stats / accumulate / propose`` hooks, so the compiled swarm engine can
carry per-node importance statistics through its round scan and hand the
commit to the fused Pallas kernel — no host round-trips for any method. The
function forms above remain the numerical ground truth; strategies call them.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def stack_params(param_list):
    """[pytree]*N -> stacked pytree with leading node axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *param_list)


def unstack_params(stacked, n: int):
    return [jax.tree.map(lambda x, i=i: x[i], stacked) for i in range(n)]


def mix(stacked, W):
    """Apply mixing matrix: θ_i ← Σ_j W[i,j] θ_j  (the gossip round).

    W: [N, N] row-stochastic (jnp or np). Leaf dtype is preserved; the
    contraction runs in fp32 at HIGHEST precision so accelerator backends
    don't drop to bf16 passes (on TPU the default matmul precision would
    cost ~3 decimal digits on every merge).
    """
    Wj = jnp.asarray(W, jnp.float32)

    def one(x):
        flat = x.reshape(x.shape[0], -1).astype(jnp.float32)
        out = jax.lax.dot(Wj, flat, precision=jax.lax.Precision.HIGHEST)
        return out.reshape(x.shape).astype(x.dtype)

    return jax.tree.map(one, stacked)


def fisher_merge(stacked, fishers, eps: float = 1e-8):
    """θ* = Σ_i F_i ⊙ θ_i / Σ_i F_i, broadcast back to every node.

    fishers: stacked pytree of diagonal Fisher estimates (same structure).
    """
    def one(x, f):
        xf = x.astype(jnp.float32)
        ff = f.astype(jnp.float32) + eps
        merged = (ff * xf).sum(0) / ff.sum(0)
        return jnp.broadcast_to(merged, x.shape).astype(x.dtype)

    return jax.tree.map(one, stacked, fishers)


def gradmatch_merge(stacked, fishers, weights: Optional[jnp.ndarray] = None,
                    eps: float = 1e-8):
    """Uncertainty-based gradient matching (arXiv:2310.12808, simplified).

    Around the weighted mean θ̄, corrects each delta by its Fisher
    preconditioner:  θ* = θ̄ + Σ_i w_i (F_i/F̄ - 1) ⊙ (θ_i - θ̄) where
    F̄ = Σ w_i F_i. Reduces to FedAvg when all Fishers are equal.
    """
    n = jax.tree.leaves(stacked)[0].shape[0]
    w = jnp.full((n,), 1.0 / n) if weights is None else jnp.asarray(weights, jnp.float32)

    def one(x, f):
        xf = x.astype(jnp.float32)
        ff = f.astype(jnp.float32) + eps
        wb = w.reshape((n,) + (1,) * (x.ndim - 1))
        mean = (wb * xf).sum(0)
        fbar = (wb * ff).sum(0)
        corr = (wb * (ff / fbar - 1.0) * (xf - mean)).sum(0)
        merged = mean + corr
        return jnp.broadcast_to(merged, x.shape).astype(x.dtype)

    return jax.tree.map(one, stacked, fishers)


def topo_weighted_merge(stacked, fishers, rows, eps: float = 1e-8):
    """Topology-restricted importance-weighted merge (per-row ratio):

        θ*_i = Σ_j rows[i,j]·(F_j+eps)⊙θ_j / Σ_j rows[i,j]·(F_j+eps)

    ``rows`` [N, N] ≥ 0 carries the graph structure: ring/dynamic swarms pass
    their (possibly traced, membership-masked) mixing rows so each node only
    merges graph-neighbour contributions. Uniform rows cancel in the ratio —
    the full-topology case reduces to :func:`fisher_merge`; rows of dataset
    weights reduce to the gradmatch weighted-fisher identity. This is the
    numerical ground truth the fused Pallas ``imp`` kernel re-contracts.
    """
    R = jnp.asarray(rows, jnp.float32)

    def one(x, f):
        n = x.shape[0]
        xf = x.astype(jnp.float32).reshape(n, -1)
        ff = f.astype(jnp.float32).reshape(n, -1) + eps
        num = jax.lax.dot(R, ff * xf, precision=jax.lax.Precision.HIGHEST)
        den = jax.lax.dot(R, ff, precision=jax.lax.Precision.HIGHEST)
        out = num / jnp.maximum(den, 1e-30)
        return out.reshape(x.shape).astype(x.dtype)

    return jax.tree.map(one, stacked, fishers)


def mask_fishers(fishers, active):
    """Zero departed nodes' Fisher mass so their stale params can't enter
    fisher/gradmatch merges. The single implementation of that invariant —
    every path reaches it through `MergeStrategy.finalize_mass` (host bools
    or traced masks)."""
    a = jnp.asarray(active)

    def one(f):
        if f is None:
            return None
        return f * a.astype(f.dtype).reshape((f.shape[0],) + (1,) * (f.ndim - 1))

    return jax.tree.map(one, fishers, is_leaf=lambda v: v is None)


def merge(stacked, method: str, *, W=None, fishers=None, weights=None):
    if method in ("mean", "fedavg"):
        if W is None:
            raise ValueError("mean/fedavg merges need a mixing matrix W")
        return mix(stacked, W)
    if method == "fisher":
        if fishers is None:
            raise ValueError("fisher merge needs fisher estimates")
        return fisher_merge(stacked, fishers)
    if method == "gradmatch":
        if fishers is None:
            raise ValueError("gradmatch merge needs fisher estimates")
        return gradmatch_merge(stacked, fishers, weights)
    raise ValueError(f"unknown merge {method!r}")


# ---------------------------------------------------------------------------
# MergeStrategy: the traceable first-class merge abstraction
# ---------------------------------------------------------------------------

class MergeStrategy:
    """Traceable merge strategy: ``init_stats`` → ``accumulate`` → ``propose``.

    The engine threads ``stats`` (a stacked pytree of per-node importance
    accumulators, or None) through its compiled round scan:

      * ``init_stats(stacked)``     zero accumulators matching the params
        (None for methods that need no statistics);
      * ``accumulate(stats, old, new, step)`` per-local-step in-graph update;
      * ``fishers(stats)``          finalize accumulators into the diagonal
        importance estimates the merge consumes;
      * ``propose(stacked, W, weights=, fishers=)`` →
        ``(candidate, W_commit, imp)``: the merge candidate for every node,
        plus the row-weight matrix and optional per-leaf importance pytree
        the fused Pallas commit re-contracts with. ``imp is None`` means the
        candidate is a plain W-row mix (mean/fedavg).

    Everything is pure jax — a strategy can run inside ``jit``/``scan``/
    ``shard_map`` with traced inputs. Candidates are computed by the module's
    function forms (``mix`` / ``fisher_merge`` / ``gradmatch_merge``) so the
    strategy path is numerically identical to ``merge(...)``.
    """

    method = "mean"
    uses_stats = False
    #: mass floor shared by every weighted-merge realization (host ratio,
    #: fused kernel imp, mesh psum/ppermute/gathered schedules and their
    #: q8 EF forms) — dispatch reads it off the strategy unconditionally
    eps = 1e-8

    def init_stats(self, stacked):
        """Per-node importance accumulators (None: method needs none)."""
        return None

    def accumulate(self, stats, old_params, new_params, step):
        """In-graph per-step stats update. Default: no-op."""
        return stats

    def accumulate_grads(self, stats, grads, step):
        """True-Fisher accumulation: consume exact per-step gradients (the
        opt-in ``train_step_fn`` 4-tuple signature returns them) instead of
        the Δθ² proxy. Default: no-op."""
        return stats

    def fishers(self, stats):
        """Finalize accumulators into diagonal importance estimates."""
        return stats

    def gossip_mass(self, fishers, weights):
        """Per-node importance mass for the collective (psum) realization —
        the one place any weight-folding identity lives for the SPMD path."""
        return fishers

    def finalize_mass(self, fishers, active=None):
        """Mask-then-finalize, in that order: a departed node's (possibly
        huge) stale mass must be zeroed BEFORE normalization, or it drags
        the normalization mean and drowns the survivors in the eps floor.
        Every merge path (the engine and gossip backends) calls this
        instead of hand-sequencing the two steps."""
        if fishers is None:
            return None
        if active is not None:
            fishers = mask_fishers(fishers, active)
        return self.fishers(fishers)

    def topo_rows(self, W, weights=None):
        """Per-row contribution weights for a topology-restricted merge
        (``rows=`` of :func:`topo_weighted_merge`). None: method is already
        row-structured (mix) or has no restricted form."""
        return None

    def propose(self, stacked, W, *, weights=None, fishers=None, rows=None):
        raise NotImplementedError


class MixStrategy(MergeStrategy):
    """mean / fedavg: candidate is the mixing-matrix contraction; the fused
    commit re-contracts the same W rows (no importance weights)."""

    def __init__(self, method: str = "fedavg"):
        self.method = method

    def propose(self, stacked, W, *, weights=None, fishers=None, rows=None):
        return mix(stacked, W), W, None


class FisherStrategy(MergeStrategy):
    """Diagonal-Fisher-weighted merging with in-graph mass accumulation.

    Without an explicit Fisher (squared-gradient) estimate, the accumulator
    is a decayed sum of squared parameter deltas: F ← γF + (θ_{t+1} − θ_t)².
    Under SGD-like updates this is lr²·ĝ² — a curvature proxy whose uniform
    scale cancels in the merge ratio Σ F_i θ_i / Σ F_i, so it needs no loss
    re-evaluation or extra backward pass inside the compiled round. Caveat:
    under adaptive optimizers (AdamW) the per-step delta is ~lr regardless
    of gradient scale, so the proxy flattens toward uniform and the merge
    approaches fedavg; pass exact squared-gradient estimates through the
    explicit ``fishers=`` channel when curvature weighting matters (see the
    ROADMAP true-Fisher accumulation hook).
    """

    method = "fisher"
    uses_stats = True

    def __init__(self, decay: float = 0.95, eps: float = 1e-8):
        self.decay = decay
        self.eps = eps

    def init_stats(self, stacked):
        return jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), stacked)

    def accumulate(self, stats, old_params, new_params, step):
        def one(s, po, pn):
            d = (pn - po).astype(jnp.float32)
            return self.decay * s + d * d

        return jax.tree.map(one, stats, old_params, new_params)

    def accumulate_grads(self, stats, grads, step):
        """Exact diagonal-Fisher mass from per-step gradients: F ← γF + g²
        (the ROADMAP true-Fisher hook — same decayed-sum shape as the Δθ²
        proxy, but scale-correct under adaptive optimizers)."""
        def one(s, g):
            gf = g.astype(jnp.float32)
            return self.decay * s + gf * gf

        return jax.tree.map(one, stats, grads)

    def fishers(self, stats):
        """Normalize accumulated mass to a global mean of 1. The merge ratio
        is scale-free, so this changes nothing when mass is already O(1) —
        it only keeps the lr²-scaled Δθ² proxy from drowning in the eps
        floor (tiny lr would otherwise collapse the merge to a uniform mean
        and re-admit `mask_fishers`-zeroed departed nodes)."""
        leaves = jax.tree.leaves(stats)
        total = sum(leaf.sum() for leaf in leaves)
        count = sum(leaf.size for leaf in leaves)
        mean = total / count
        scale = jnp.where(mean > 0, 1.0 / jnp.maximum(mean, 1e-30), 1.0)
        return jax.tree.map(lambda leaf: leaf * scale, stats)

    def _imp(self, stacked, fishers, weights):
        """Per-leaf importance for the fused commit: c_j·(F_j + eps)."""
        return jax.tree.map(lambda f: f.astype(jnp.float32) + self.eps, fishers)

    def _rows(self, n, weights):
        return jnp.ones((n, n), jnp.float32)

    def topo_rows(self, W, weights=None):
        """Graph-restricted fisher: contribution weights ARE the mixing rows,
        so only graph neighbours enter  Σ_j W[i,j]F_jθ_j / Σ_j W[i,j]F_j.
        Uniform full-topology rows cancel in the ratio (≡ global fisher)."""
        return jnp.asarray(W, jnp.float32)

    def propose(self, stacked, W, *, weights=None, fishers=None, rows=None):
        if fishers is None:
            fishers = jax.tree.map(jnp.ones_like, stacked)
        n = jax.tree.leaves(stacked)[0].shape[0]
        if rows is not None:   # ring/dynamic: per-row neighbour-restricted
            candidate = topo_weighted_merge(stacked, fishers, rows,
                                            eps=self.eps)
            return candidate, rows, self._imp(stacked, fishers, weights)
        candidate = self._merge(stacked, fishers, weights)
        return candidate, self._rows(n, weights), self._imp(stacked, fishers,
                                                            weights)

    def _merge(self, stacked, fishers, weights):
        return fisher_merge(stacked, fishers, eps=self.eps)


class GradMatchStrategy(FisherStrategy):
    """Uncertainty-based gradient matching. Algebraically
    θ* = θ̄ + Σ w(F/F̄ − 1)(θ − θ̄) = Σ w_j F_j θ_j / Σ w_j F_j — a
    dataset-weighted Fisher ratio — so the fused commit reuses the
    importance-weighted kernel with w_j folded into the row weights."""

    method = "gradmatch"

    def _rows(self, n, weights):
        w = (jnp.full((n,), 1.0 / n, jnp.float32) if weights is None
             else jnp.asarray(weights, jnp.float32))
        return jnp.broadcast_to(w[None, :], (n, n))

    def topo_rows(self, W, weights=None):
        """Graph-restricted gradmatch: dataset weights folded into the
        neighbour rows — c_ij = W[i,j]·w_j in the weighted-fisher ratio."""
        Wj = jnp.asarray(W, jnp.float32)
        if weights is None:
            return Wj
        return Wj * jnp.asarray(weights, jnp.float32)[None, :]

    def _merge(self, stacked, fishers, weights):
        return gradmatch_merge(stacked, fishers, weights, eps=self.eps)

    def gossip_mass(self, fishers, weights):
        """Fold w_j into the mass so `fisher_gossip`'s two psums realize the
        weighted ratio — the identity's single home on the SPMD path."""
        w = jnp.asarray(weights, jnp.float32)

        def one(f):
            return f * w.reshape((f.shape[0],) + (1,) * (f.ndim - 1))

        return jax.tree.map(one, fishers)


def get_strategy(cfg) -> MergeStrategy:
    """SwarmConfig → MergeStrategy (the single merge-method dispatch)."""
    method = cfg.merge
    if method in ("mean", "fedavg"):
        return MixStrategy(method)
    decay = getattr(cfg, "fisher_decay", 0.95)
    if method == "fisher":
        return FisherStrategy(decay=decay)
    if method == "gradmatch":
        return GradMatchStrategy(decay=decay)
    raise ValueError(f"unknown merge {method!r}")
