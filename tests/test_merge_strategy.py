"""The compiled MergeStrategy layer: fisher/gradmatch in-graph.

Invariants:
  * strategy propose == the `merge_impl.merge(...)` ground truth,
  * the engine's weighted commit (Pallas imp kernel) == merge + gated select,
  * fisher/gradmatch `run_rounds` trace end-to-end (zero host transfers)
    and commit through the fused Pallas kernel,
  * the jitted engine matches the float64 numpy oracle (`faults.oracle`)
    for the weighted merges on the toy quadratic model,
  * the stale-by-one overlap mode stays a convergent gossip scheme.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SwarmConfig
from repro.core import merge_impl as merge_lib
from repro.core.engine import SwarmEngine, active_weights, mixing_matrix
from repro.core.merge_impl import get_strategy
from repro.core.session import SwarmSession
from repro.faults import oracle

N = 4
SEEDS = range(3)


def _cfg(**kw):
    kw.setdefault("n_nodes", N)
    kw.setdefault("sync_every", 2)
    kw.setdefault("merge", "fisher")
    kw.setdefault("topology", "full")
    kw.setdefault("lora_only", False)
    kw.setdefault("val_threshold", 0.0)
    return SwarmConfig(**kw)


def _rand_tree(rng, n=N):
    mk = lambda *s: jnp.asarray(rng.normal(0, 1, (n, *s)), jnp.float32)
    return {"w": mk(8, 16), "b": mk(16)}


def _rand_fishers(rng, tree):
    return jax.tree.map(
        lambda x: jnp.asarray(np.abs(rng.normal(1, 0.5, x.shape)),
                              jnp.float32), tree)


def _toy_fns():
    def train_step(params, opt_state, batch, step):
        g = params["x"] - batch
        return {"x": params["x"] - 0.1 * g}, opt_state, {"loss": jnp.sum(g * g)}

    def eval_fn(params, val):
        return 1.0 - 0.0 * jnp.sum(params["x"])  # always accept, in-graph

    return train_step, eval_fn


def _targets():
    return jnp.asarray([np.full((4,), t, np.float32) for t in range(N)])


# ---------------------------------------------------------------------------
# strategy propose == merge_impl ground truth
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", ["fisher", "gradmatch"])
@pytest.mark.parametrize("seed", SEEDS)
def test_strategy_propose_matches_merge_impl(method, seed):
    rng = np.random.default_rng(seed)
    st = _rand_tree(rng)
    fishers = _rand_fishers(rng, st)
    w = jnp.asarray(rng.dirichlet(np.ones(N)), jnp.float32)
    W = jnp.asarray(mixing_matrix(_cfg(merge=method), np.ones(N)), jnp.float32)
    strategy = get_strategy(_cfg(merge=method))
    cand, W_eff, imp = strategy.propose(st, W, weights=w, fishers=fishers)
    want = merge_lib.merge(st, method, W=W, fishers=fishers, weights=w)
    for a, b in zip(jax.tree.leaves(cand), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert imp is not None and W_eff.shape == (N, N)


@pytest.mark.parametrize("seed", SEEDS)
def test_mix_strategy_matches_mix(seed):
    rng = np.random.default_rng(seed)
    st = _rand_tree(rng)
    W = jnp.asarray(mixing_matrix(_cfg(merge="fedavg"),
                                  rng.integers(1, 10, N)), jnp.float32)
    cand, W_eff, imp = get_strategy(_cfg(merge="fedavg")).propose(st, W)
    want = merge_lib.mix(st, W)
    for a, b in zip(jax.tree.leaves(cand), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert imp is None and W_eff is W


# ---------------------------------------------------------------------------
# engine sync: the fused weighted commit == merge + gated select
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", ["fisher", "gradmatch"])
def test_engine_weighted_commit_matches_host_merge(method):
    rng = np.random.default_rng(0)
    params = {"x": jnp.asarray(rng.normal(0, 1, (N, 6)), jnp.float32)}
    stats = {"x": jnp.asarray(np.abs(rng.normal(1, 0.5, (N, 6))), jnp.float32)}
    _, eval_fn = _toy_fns()
    eng = SwarmEngine(_cfg(merge=method), None, eval_fn,
                      data_sizes=[100 * (i + 1) for i in range(N)])
    committed, log = jax.jit(eng.sync)(params, jnp.zeros((N, 1)), None, stats)
    assert np.asarray(log["gates"]).all()
    w = active_weights([100 * (i + 1) for i in range(N)])
    want = merge_lib.merge(params, method, fishers=stats,
                           weights=jnp.asarray(w, jnp.float32))
    np.testing.assert_allclose(np.asarray(committed["x"]),
                               np.asarray(want["x"]), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("method", ["fisher", "gradmatch"])
def test_engine_weighted_commit_respects_gates_and_active(method):
    """Rejected / inactive nodes keep their params even on the imp path."""
    rng = np.random.default_rng(1)
    params = {"x": jnp.asarray(rng.normal(0, 1, (N, 6)), jnp.float32)}
    stats = {"x": jnp.ones((N, 6), jnp.float32)}
    _, eval_fn = _toy_fns()
    eng = SwarmEngine(_cfg(merge=method), None, eval_fn, data_sizes=[1] * N)
    active = jnp.asarray([True, True, False, True])
    committed, log = jax.jit(eng.sync)(params, jnp.zeros((N, 1)), active,
                                       stats)
    gates = np.asarray(log["gates"])
    assert not gates[2] and gates[[0, 1, 3]].all()
    np.testing.assert_allclose(np.asarray(committed["x"][2]),
                               np.asarray(params["x"][2]))
    # active nodes merge over the active membership only (uniform fishers →
    # mean of the active rows; the inactive row's eps mass is negligible)
    want = np.asarray(params["x"])[[0, 1, 3]].mean(0)
    for i in (0, 1, 3):
        np.testing.assert_allclose(np.asarray(committed["x"][i]), want,
                                   rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# compiled round: traces end-to-end, commits through Pallas
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", ["fisher", "gradmatch"])
def test_run_rounds_weighted_is_fully_traced_with_pallas_commit(method):
    """`run_rounds` with fisher/gradmatch builds one jaxpr — no host
    round-trips (a `float()` anywhere on the path would raise a tracer
    error) — and the commit goes through the Pallas fused_merge kernel."""
    train_step, eval_fn = _toy_fns()
    eng = SwarmEngine(_cfg(merge=method), train_step, eval_fn,
                      data_sizes=[1] * N)
    batches = jnp.broadcast_to(_targets(), (3, 2, N, 4))
    jaxpr = jax.make_jaxpr(eng._run_rounds)(
        {"x": jnp.zeros((N, 4))}, None, batches, jnp.zeros((N, 1)))
    assert "pallas_call" in str(jaxpr)


def test_round_returns_and_threads_stats():
    train_step, eval_fn = _toy_fns()
    eng = SwarmEngine(_cfg(merge="fisher"), train_step, eval_fn,
                      data_sizes=[1] * N)
    batches = jnp.broadcast_to(_targets(), (2, N, 4))
    p, o, out = eng.round({"x": jnp.zeros((N, 4))}, None, batches,
                          jnp.zeros((N, 1)), None, 0)
    assert "stats" in out and out["stats"]["x"].shape == (N, 4)
    assert float(jnp.abs(out["stats"]["x"]).sum()) > 0  # mass accumulated
    # stats keep riding through run_local
    p, o, _, stats = eng.run_local(p, None, batches, 2, out["stats"])
    assert stats["x"].shape == (N, 4)
    # ... and run_rounds hands the final accumulators back for chunked calls
    rb = jnp.broadcast_to(_targets(), (2, 2, N, 4))
    p, o, _, logs = eng.run_rounds(p, None, rb, jnp.zeros((N, 1)), None, 4,
                                   stats)
    assert logs["stats"]["x"].shape == (N, 4)
    assert float(jnp.abs(logs["stats"]["x"]).sum()) > 0


def test_untrained_node_does_not_dominate_fisher_merge():
    """Regression: an active node that never accumulated mass must get ~zero
    importance. A ones_like default would dwarf the trained nodes'
    lr²-scaled Δθ² mass and hand the merge its (stale) params."""
    train_step, eval_fn = _toy_fns()
    cfg = _cfg(merge="fisher", sync_every=2)
    # trained nodes start away from their targets so every step accumulates
    # Δθ² mass; node 3 (params 100.0) is active but its batches equal its
    # params, so it never moves and never gains mass
    x0 = jnp.asarray([np.full((4,), 100.0 if i == 3 else -1.0, np.float32)
                      for i in range(N)])
    sess = SwarmSession(cfg, train_step, eval_fn, params={"x": x0},
                        stacked=True, data_sizes=[100] * N)
    batches = jnp.broadcast_to(_targets().at[3].set(100.0), (2, N, 4))
    log = sess.round(batches, jnp.zeros((N, 1)))
    assert np.asarray(log["gates"]).all()
    for i in range(3):
        merged = np.asarray(sess.state.params["x"][i])
        assert np.abs(merged).max() < 5.0, "untrained node took over the merge"


def test_tiny_accumulated_mass_survives_eps_floor():
    """Regression: lr²-scaled Δθ² mass (≪ eps) must still drive the merge.
    Finalization normalizes post-mask, so relative fisher weighting is
    preserved and a departed node's huge stale mass stays excluded instead
    of re-entering as a uniform-mean term."""
    rng = np.random.default_rng(2)
    params = {"x": jnp.asarray(rng.normal(0, 1, (N, 6)), jnp.float32)}
    mass = np.full((N, 6), 1e-9, np.float32)   # ≪ the 1e-8 eps floor
    mass[0] = 3e-9                             # node 0: 3x the mass
    mass[2] = 1e6                              # node 2: huge but departed
    stats = {"x": jnp.asarray(mass)}
    _, eval_fn = _toy_fns()
    eng = SwarmEngine(_cfg(merge="fisher"), None, eval_fn, data_sizes=[1] * N)
    active = jnp.asarray([True, True, False, True])
    committed, log = jax.jit(eng.sync)(params, jnp.zeros((N, 1)), active,
                                       stats)
    x = np.asarray(params["x"])
    want = (3 * x[0] + x[1] + x[3]) / 5.0      # mass-weighted active mean
    for i in (0, 1, 3):
        np.testing.assert_allclose(np.asarray(committed["x"][i]), want,
                                   rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(committed["x"][2]), x[2])


@pytest.mark.parametrize("method", ["fisher", "gradmatch"])
def test_engine_matches_oracle_weighted(method):
    """The compiled engine == the numpy oracle for the weighted merges on
    the toy quadratic (Δθ² accumulation on both)."""
    train_step, eval_fn = _toy_fns()
    cfg = _cfg(merge=method)
    targets = _targets()
    rounds, t = 3, cfg.sync_every

    eng = SwarmEngine(cfg, train_step, eval_fn,
                      data_sizes=[100 * (i + 1) for i in range(N)])
    batches = jnp.broadcast_to(targets, (rounds, t, N, 4))
    params, _, _, logs = eng.run_rounds({"x": jnp.zeros((N, 4))}, None,
                                        batches, jnp.zeros((N, 1)), None, 0)
    assert np.asarray(logs["gates"]).all()
    want = oracle.simulate(
        np.zeros((N, 4)), np.asarray(targets), np.ones((rounds, N)),
        merge=method, topology="full", lr=0.1, steps_per_round=t,
        data_sizes=[100 * (i + 1) for i in range(N)],
        fisher_decay=cfg.fisher_decay)[-1]
    np.testing.assert_allclose(np.asarray(params["x"]), want,
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# stale-by-one overlap mode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("merge", ["fedavg", "fisher"])
def test_overlap_mode_converges_toy(merge):
    """Double-buffered rounds remain a convergent gossip scheme: nodes end
    near the serial-mode consensus, one round of staleness at most. The
    fisher case exercises the stats carry riding next to the pending-delta
    double buffer in the overlap scan body."""
    train_step, eval_fn = _toy_fns()
    targets = _targets()
    finals = {}
    for overlap in (False, True):
        cfg = _cfg(merge=merge, sync_every=1, overlap_sync=overlap)
        eng = SwarmEngine(cfg, train_step, eval_fn, data_sizes=[1] * N)
        batches = jnp.broadcast_to(targets, (12, 1, N, 4))
        p, _, _, logs = eng.run_rounds({"x": jnp.zeros((N, 4))}, None,
                                       batches, jnp.zeros((N, 1)), None, 0)
        assert np.asarray(logs["gates"]).all()
        if merge == "fisher":
            assert logs["stats"]["x"].shape == (N, 4)
        finals[overlap] = np.asarray(p["x"])
    serial, stale = finals[False], finals[True]
    # serial reaches exact consensus; stale-by-one stays within one round of
    # local drift (0.1 * max target distance) of it
    assert np.abs(serial - serial.mean(0)).max() < 1e-5
    assert np.abs(stale - serial).max() < 0.35
    assert np.abs(stale.mean() - serial.mean()) < 0.15


def test_overlap_histo_smoke_converges():
    """The stale-by-one schedule trains the tiny histo swarm end-to-end."""
    from repro.data import make_histo_dataset, paper_splits, shard_to_nodes
    from repro.experiments.histo import (HistoExperimentConfig,
                                         _make_model_fns, _train_loop)

    ecfg = HistoExperimentConfig(
        n_train=160, n_test=32, steps=6, image_size=16, batch_size=8,
        noise=0.6, growth=4, stem=8, feat_dim=32, hidden=16, n_blocks=1,
        layers_per_block=2, seed=3,
        swarm=SwarmConfig(n_nodes=4, sync_every=3, topology="full",
                          merge="fedavg", lora_only=False, val_threshold=0.8,
                          overlap_sync=True))
    images, labels = make_histo_dataset(ecfg.n_train, size=ecfg.image_size,
                                        noise=ecfg.noise, seed=ecfg.seed)
    shards = shard_to_nodes(images, labels,
                            paper_splits(ecfg.n_train, ecfg.fractions),
                            seed=ecfg.seed)
    train_step, predict, _ = _make_model_fns(ecfg)
    params, sync_log = _train_loop(ecfg, train_step, shards,
                                   swarm_cfg=ecfg.swarm)
    assert len(params) == 4 and sync_log
    for s in sync_log:
        assert all(0.0 <= m <= 1.0 for m in s["metric_local"])
    probs = np.asarray(predict(params[0], images[:64]))
    assert np.isfinite(probs).all()
