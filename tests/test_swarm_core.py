"""Property-style tests for the P2P-SL core (the paper's invariants).

`hypothesis` is not installable in this offline container; the same invariants
are asserted over seed-swept random instances instead.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SwarmConfig
from repro.core import topology as topo
from repro.core.engine import (gate_decisions, gated_commit, mixing_matrix,
                               propose_merge)
from repro.core.merge_impl import (fisher_merge, gradmatch_merge, mix,
                                   stack_params, unstack_params)
from repro.core.session import SwarmSession

SEEDS = range(8)


def _rand_tree(rng, n_nodes):
    mk = lambda *s: jnp.asarray(rng.normal(0, 1, (n_nodes, *s)), jnp.float32)
    return {"w": mk(8, 16), "b": mk(16), "nested": {"v": mk(4, 4, 2)}}


# ---------------------------------------------------------------------------
# topology properties
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [2, 3, 4, 8, 16])
def test_mixing_matrices_row_stochastic(n):
    for W in (topo.ring_matrix(n, 0.5), topo.full_matrix(n),
              topo.full_matrix(n, list(range(1, n + 1)))):
        assert np.allclose(W.sum(1), 1.0)
        assert (W >= 0).all()


@pytest.mark.parametrize("n", [4, 8])
def test_ring_is_doubly_stochastic_with_positive_gap(n):
    W = topo.ring_matrix(n, 0.5)
    assert np.allclose(W.sum(0), 1.0)
    assert 0.0 < topo.spectral_gap(W) <= 1.0


@pytest.mark.parametrize("seed", SEEDS)
def test_dynamic_matrix_isolates_absent_nodes(seed):
    rng = np.random.default_rng(seed)
    n = 5
    active = rng.random(n) > 0.4
    active[0] = True  # at least one active
    W = topo.dynamic_matrix(topo.full_matrix(n, rng.random(n) + 0.1), active)
    assert np.allclose(W.sum(1), 1.0)
    for i in np.flatnonzero(~active):
        row = np.zeros(n); row[i] = 1.0
        assert np.allclose(W[i], row)          # absent node keeps its params
        assert np.allclose(W[active][:, i], 0)  # nobody reads from it


def test_fedavg_weights_closed_form():
    w = topo.fedavg_weights([1000, 3000, 3000, 3000])
    assert np.allclose(w, [0.1, 0.3, 0.3, 0.3])
    with pytest.raises(ValueError):
        topo.fedavg_weights([0, 0])


# ---------------------------------------------------------------------------
# merge properties
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_mix_preserves_global_mean_doubly_stochastic(seed):
    """Gossip with a doubly-stochastic W preserves the parameter average."""
    rng = np.random.default_rng(seed)
    st = _rand_tree(rng, 4)
    out = mix(st, topo.ring_matrix(4, 0.3))
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(out)):
        np.testing.assert_allclose(np.asarray(a.mean(0)), np.asarray(b.mean(0)),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("seed", SEEDS)
def test_gossip_contracts_to_consensus(seed):
    """Repeated ring gossip converges to the mean at the spectral-gap rate."""
    rng = np.random.default_rng(seed)
    st = _rand_tree(rng, 8)
    W = topo.ring_matrix(8, 0.5)
    gap = topo.spectral_gap(W)
    disagreement = lambda t: max(
        float(jnp.abs(x - x.mean(0, keepdims=True)).max())
        for x in jax.tree.leaves(t))
    d0 = disagreement(st)
    cur = st
    for _ in range(60):
        cur = mix(cur, W)
    assert disagreement(cur) < d0 * (1 - gap) ** 30  # generous bound


@pytest.mark.parametrize("seed", SEEDS)
def test_fedavg_mix_equals_closed_form(seed):
    rng = np.random.default_rng(seed)
    st = _rand_tree(rng, 4)
    sizes = rng.integers(100, 1000, 4)
    W = topo.full_matrix(4, sizes)
    out = mix(st, W)
    w = sizes / sizes.sum()
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(out)):
        want = np.tensordot(w, np.asarray(a), axes=(0, 0))
        for i in range(4):
            np.testing.assert_allclose(np.asarray(b[i]), want, rtol=1e-5,
                                       atol=1e-5)


@pytest.mark.parametrize("seed", SEEDS)
def test_fisher_merge_interpolates(seed):
    """Equal Fishers -> plain mean; one-hot Fisher -> that node's params."""
    rng = np.random.default_rng(seed)
    st = _rand_tree(rng, 3)
    ones = jax.tree.map(jnp.ones_like, st)
    out = fisher_merge(st, ones)
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(out)):
        np.testing.assert_allclose(np.asarray(b[0]), np.asarray(a.mean(0)),
                                   rtol=1e-5, atol=1e-5)
    hot = jax.tree.map(
        lambda x: jnp.zeros_like(x).at[1].set(1.0), st)
    out = fisher_merge(st, hot, eps=1e-12)
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(out)):
        np.testing.assert_allclose(np.asarray(b[0]), np.asarray(a[1]),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("seed", SEEDS)
def test_gradmatch_reduces_to_fedavg_with_equal_fishers(seed):
    rng = np.random.default_rng(seed)
    st = _rand_tree(rng, 4)
    ones = jax.tree.map(jnp.ones_like, st)
    w = jnp.asarray(rng.dirichlet(np.ones(4)), jnp.float32)
    out = gradmatch_merge(st, ones, weights=w)
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(out)):
        want = np.tensordot(np.asarray(w), np.asarray(a), axes=(0, 0))
        np.testing.assert_allclose(np.asarray(b[0]), want, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# gating (the paper's 80% validation-acceptance rule)
# ---------------------------------------------------------------------------

def test_gate_decisions_relative_and_absolute():
    merged = jnp.asarray([0.9, 0.5, 0.79, 0.81])
    local = jnp.asarray([1.0, 1.0, 1.0, 1.0])
    rel = np.asarray(gate_decisions(merged, local, 0.8, "relative"))
    assert rel.tolist() == [True, False, False, True]
    ab = np.asarray(gate_decisions(merged, local, 0.8, "absolute"))
    assert ab.tolist() == [True, False, False, True]


@pytest.mark.parametrize("seed", SEEDS)
def test_gated_commit_selects_per_node(seed):
    rng = np.random.default_rng(seed)
    local = _rand_tree(rng, 4)
    cand = jax.tree.map(lambda x: x + 100.0, local)
    gates = jnp.asarray(rng.random(4) > 0.5)
    out = gated_commit(cand, local, gates)
    for lo, o in zip(jax.tree.leaves(local), jax.tree.leaves(out)):
        for i, g in enumerate(np.asarray(gates)):
            want = np.asarray(lo[i]) + (100.0 if g else 0.0)
            np.testing.assert_allclose(np.asarray(o[i]), want, rtol=1e-6)


def test_propose_merge_lora_only_leaves_base_untouched():
    from repro.core.lora import inject_lora
    rng = np.random.default_rng(0)
    base = {"attn": {"q": {"w": jnp.asarray(rng.normal(0, 1, (16, 16)),
                                            jnp.float32)}}}
    trees = [inject_lora(jax.tree.map(lambda x: x + i, base),
                         jax.random.key(i), rank=4) for i in range(3)]
    st = stack_params(trees)
    cfg = SwarmConfig(n_nodes=3, lora_only=True, merge="fedavg", topology="full")
    W = mixing_matrix(cfg, [1, 1, 1])
    cand = propose_merge(st, cfg, W)
    # base weights unchanged per node, adapters averaged
    np.testing.assert_allclose(np.asarray(cand["attn"]["q"]["w"]),
                               np.asarray(st["attn"]["q"]["w"]))
    a = np.asarray(st["attn"]["q"]["lora_A"])
    # atol floor: the merge contracts in f32 (N·eps·max|θ| ≈ 3·1.2e-7), so
    # elements produced by cancellation can't satisfy a pure rtol vs the
    # numpy pairwise mean; base-leaf passthrough above stays bit-exact.
    np.testing.assert_allclose(np.asarray(cand["attn"]["q"]["lora_A"]),
                               np.tile(a.mean(0), (3, 1, 1)), rtol=1e-5,
                               atol=1e-6)


# ---------------------------------------------------------------------------
# end-to-end session behaviour (toy quadratic "model")
# ---------------------------------------------------------------------------

TARGETS = jnp.asarray([np.full((4,), t, np.float32) for t in range(4)])


def _pull_step(params, opt_state, batch, step):
    """Each node descends toward its batch (its target); a batch equal to
    the node's params leaves it where it is."""
    g = params["x"] - batch
    return {"x": params["x"] - 0.1 * g}, opt_state, {"loss": jnp.sum(g * g)}


def _accept(params, val):
    return 1.0 - 0.0 * jnp.sum(params["x"])  # always accept, in-graph


def _toy_session(sync_every=2, merge="fedavg", threshold=0.0, *,
                 train_step=_pull_step, eval_fn=_accept, params=None,
                 stacked=False, data_sizes=(100, 200, 300, 400)):
    """Nodes descend toward different targets; swarm pulls them together."""
    cfg = SwarmConfig(n_nodes=4, sync_every=sync_every, merge=merge,
                      topology="full", lora_only=False, val_threshold=threshold)
    return SwarmSession(cfg, train_step, eval_fn,
                        params=params or {"x": jnp.zeros((4,))},
                        stacked=stacked, data_sizes=data_sizes)


def test_session_syncs_to_weighted_mean():
    sess = _toy_session()
    log = sess.round(jnp.broadcast_to(TARGETS, (2, 4, 4)), jnp.zeros((4, 1)))
    assert np.asarray(log["gates"]).all()
    xs = np.asarray(sess.state.params["x"])
    for x in xs[1:]:
        np.testing.assert_allclose(x, xs[0], rtol=1e-5, atol=1e-6)


def test_session_dynamic_membership():
    sess = _toy_session()
    sess.leave(2)
    x2_before = np.asarray(sess.state.params["x"][2]).copy()
    # node 2's padding equals its params, so its steps leave it in place
    batches = jnp.broadcast_to(TARGETS.at[2].set(x2_before), (2, 4, 4))
    log = sess.round(batches, jnp.zeros((4, 1)))
    assert not np.asarray(log["gates"])[2]
    np.testing.assert_allclose(np.asarray(sess.state.params["x"][2]),
                               x2_before)


@pytest.mark.parametrize("merge", ["fisher", "gradmatch"])
def test_inactive_node_excluded_from_weighted_merges(merge):
    """Regression: a departed node's (huge) Fisher mass and dataset weight
    must not leak into fisher/gradmatch merges — zero + renormalize over the
    active membership."""
    sess = _toy_session(sync_every=1, merge=merge,
                        train_step=lambda p, o, b, s: (p, o, {}),
                        data_sizes=[100] * 4,
                        params=[{"x": jnp.full((8,), float(i), jnp.float32)}
                                for i in range(4)])
    mass = jnp.asarray([1.0, 1.0, 1e6, 1.0])[:, None] * jnp.ones((4, 8))
    sess.load_state(dataclasses.replace(sess.state, stats={"x": mass}))
    sess.leave(2)
    log = sess.round(jnp.zeros((1, 4, 1)), jnp.zeros((4, 1)))
    assert not np.asarray(log["gates"])[2]
    # active nodes merge to mean(0, 1, 3); node 2 (params=2, fisher=1e6)
    # would drag the result toward 2.0 if it leaked in
    x = np.asarray(sess.state.params["x"])
    for i in (0, 1, 3):
        np.testing.assert_allclose(x[i], np.full(8, 4.0 / 3), rtol=1e-4)
    np.testing.assert_allclose(x[2], np.full(8, 2.0))


def test_session_gate_rejects_bad_merges():
    # each node starts at its own target and scores by its distance to it
    # (val), so the fedavg candidate (5.0, at no node's target) scores lower
    # than every local -> reject
    targets = jnp.asarray([np.full((4,), t * t, np.float32)
                           for t in range(4)])

    def eval_fn(params, val):
        return 1.0 / (1.0 + jnp.sum((params["x"] - val) ** 2))

    sess = _toy_session(threshold=0.8, eval_fn=eval_fn,
                        params={"x": jnp.array(targets)}, stacked=True)
    before = np.asarray(targets).copy()   # the locals after the steps
    log = sess.round(jnp.broadcast_to(targets, (2, 4, 4)), targets)
    assert not np.asarray(log["gates"]).any()
    for b, x in zip(before, np.asarray(sess.state.params["x"])):
        np.testing.assert_allclose(x, b)
