"""The jitted stacked swarm engine: one compiled round must follow the
float64 numpy twin of the round (`faults.oracle`) on the toy dynamics."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SwarmConfig
from repro.core import merge_impl as merge_lib
from repro.core.engine import SwarmEngine
from repro.faults import oracle

N = 4


def _toy_fns():
    """Traceable toy quadratic: each node descends toward its batch target."""
    def train_step(params, opt_state, batch, step):
        g = params["x"] - batch
        return {"x": params["x"] - 0.1 * g}, opt_state, {"loss": jnp.sum(g * g)}

    def eval_fn(params, val):
        return 1.0 - 0.0 * jnp.sum(params["x"])  # always accept, in-graph

    return train_step, eval_fn


def _cfg(**kw):
    kw.setdefault("n_nodes", N)
    kw.setdefault("sync_every", 2)
    kw.setdefault("merge", "fedavg")
    kw.setdefault("topology", "full")
    kw.setdefault("lora_only", False)
    kw.setdefault("val_threshold", 0.0)
    return SwarmConfig(**kw)


def _targets():
    return jnp.asarray([np.full((4,), t, np.float32) for t in range(N)])


def test_engine_matches_oracle_toy():
    """run_rounds == the numpy oracle's rounds on the toy quadratic model."""
    train_step, eval_fn = _toy_fns()
    cfg = _cfg()
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
        merge="fedavg", topology="full", lr=0.1, steps_per_round=t,
        data_sizes=[100 * (i + 1) for i in range(N)])[-1]
    np.testing.assert_allclose(np.asarray(params["x"]), want,
                               rtol=1e-5, atol=1e-6)


def test_engine_gate_rejects_per_node():
    """Nodes whose local metric beats the merged metric keep their params."""
    train_step, _ = _toy_fns()

    def eval_fn(params, val):  # lower params -> better metric
        return 1.0 - 0.1 * jnp.mean(params["x"])

    cfg = _cfg(val_threshold=1.0)
    eng = SwarmEngine(cfg, train_step, eval_fn, data_sizes=[1] * N)
    params = {"x": jnp.asarray([np.full((4,), i, np.float32)
                                for i in range(N)])}
    committed, log = jax.jit(eng.sync)(params, jnp.zeros((N, 1)))
    gates = np.asarray(log["gates"])
    # merged mean = 1.5 -> metric 0.85; locals 1.0, 0.9, 0.8, 0.7
    assert gates.tolist() == [False, False, True, True]
    out = np.asarray(committed["x"])
    np.testing.assert_allclose(out[0], 0.0, atol=1e-6)
    np.testing.assert_allclose(out[1], 1.0, atol=1e-6)
    np.testing.assert_allclose(out[2], 1.5, rtol=1e-6)
    np.testing.assert_allclose(out[3], 1.5, rtol=1e-6)


def test_engine_active_mask_excludes_and_freezes_node():
    train_step, eval_fn = _toy_fns()
    cfg = _cfg()
    eng = SwarmEngine(cfg, train_step, eval_fn, data_sizes=[1] * N)
    params = {"x": jnp.asarray([np.full((4,), i, np.float32)
                                for i in range(N)])}
    active = jnp.asarray([True, True, False, True])
    committed, log = jax.jit(eng.sync)(params, jnp.zeros((N, 1)), active)
    gates = np.asarray(log["gates"])
    assert not gates[2] and gates[[0, 1, 3]].all()
    out = np.asarray(committed["x"])
    np.testing.assert_allclose(out[2], 2.0)             # absent: frozen
    np.testing.assert_allclose(out[[0, 1, 3]], 4.0 / 3,  # mean over active
                               rtol=1e-5, atol=1e-6)


def test_engine_lora_only_commit_keeps_base():
    from repro.core.lora import inject_lora
    rng = np.random.default_rng(0)
    base = {"attn": {"q": {"w": jnp.asarray(rng.normal(0, 1, (8, 8)),
                                            jnp.float32)}}}
    trees = [inject_lora(jax.tree.map(lambda x: x + i, base),
                         jax.random.key(i), rank=2) for i in range(N)]
    stacked = merge_lib.stack_params(trees)

    def eval_any(params, val):
        return 1.0 - 0.0 * jnp.sum(params["attn"]["q"]["w"])

    eng = SwarmEngine(_cfg(lora_only=True), None, eval_any,
                      data_sizes=[1] * N)
    committed, log = jax.jit(eng.sync)(stacked, jnp.zeros((N, 1)))
    assert np.asarray(log["gates"]).all()
    # base leaves pass through bit-exactly; adapters hit the fused mean
    np.testing.assert_array_equal(np.asarray(committed["attn"]["q"]["w"]),
                                  np.asarray(stacked["attn"]["q"]["w"]))
    a = np.asarray(stacked["attn"]["q"]["lora_A"])
    np.testing.assert_allclose(np.asarray(committed["attn"]["q"]["lora_A"]),
                               np.tile(a.mean(0), (N, 1, 1)),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("seed", range(4))
def test_macro_auc_traced_matches_host(seed):
    """The engine's in-graph gate metric == the host macro AUC, including
    tie handling, padding masks, and absent classes."""
    from repro.metrics import macro_auc, macro_auc_traced
    rng = np.random.default_rng(seed)
    v, pad = 37, 11
    probs = np.round(rng.random((v, 3)), 1)          # coarse -> many ties
    labels = rng.integers(0, 3 if seed % 2 else 2, v)  # even seeds: no class 2
    probs_p = np.concatenate([probs, np.zeros((pad, 3))])
    labels_p = np.concatenate([labels, np.zeros(pad, np.int64)])
    mask = np.arange(v + pad) < v
    got = float(macro_auc_traced(jnp.asarray(probs_p), jnp.asarray(labels_p),
                                 jnp.asarray(mask)))
    assert abs(got - macro_auc(probs, labels)) < 1e-5


@pytest.mark.parametrize("seed", range(4))
def test_macro_auc_sorted_matches_pairwise(seed):
    """The sort-based traced AUC == the old O(V²) pairwise form on small
    inputs (many ties forced by coarse rounding, random padding masks)."""
    from repro.metrics import _macro_auc_pairwise, macro_auc_traced
    rng = np.random.default_rng(100 + seed)
    v = 29
    probs = np.round(rng.random((v, 4)), 1)
    labels = rng.integers(0, 4, v)
    mask = rng.random(v) > 0.2
    got = float(macro_auc_traced(jnp.asarray(probs), jnp.asarray(labels),
                                 jnp.asarray(mask)))
    want = float(_macro_auc_pairwise(jnp.asarray(probs), jnp.asarray(labels),
                                     jnp.asarray(mask)))
    assert abs(got - want) < 1e-6


def test_macro_auc_traced_degenerate_single_class():
    """All-one-class labels: every one-vs-rest AUC is degenerate -> 0.5,
    matching the host metric (and not NaN)."""
    from repro.metrics import macro_auc, macro_auc_traced
    rng = np.random.default_rng(0)
    probs = rng.random((20, 3))
    labels = np.full(20, 1)
    got = float(macro_auc_traced(jnp.asarray(probs), jnp.asarray(labels)))
    assert got == pytest.approx(macro_auc(probs, labels)) == 0.5
    # fully-masked input: no class present -> 0.0, bit-matching the old
    # pairwise traced form (the engine's active mask handles such nodes)
    from repro.metrics import _macro_auc_pairwise
    mask = jnp.zeros(20, bool)
    got = float(macro_auc_traced(jnp.asarray(probs), jnp.asarray(labels),
                                 mask))
    assert got == float(_macro_auc_pairwise(jnp.asarray(probs),
                                            jnp.asarray(labels), mask)) == 0.0


def test_macro_auc_traced_randomized_large():
    """Acceptance: sort-based AUC within 1e-6 of the host metric on
    randomized inputs big enough that the pairwise form would be O(V²)."""
    from repro.metrics import macro_auc, macro_auc_traced
    rng = np.random.default_rng(7)
    v = 2500
    probs = rng.random((v, 3)).astype(np.float32)
    labels = rng.integers(0, 3, v)
    got = float(macro_auc_traced(jnp.asarray(probs), jnp.asarray(labels)))
    assert abs(got - macro_auc(probs, labels)) < 1e-6


def test_engine_run_rounds_reaches_consensus():
    """Full-topology fedavg commit pulls all nodes onto one iterate."""
    train_step, eval_fn = _toy_fns()
    cfg = _cfg(sync_every=1)
    eng = SwarmEngine(cfg, train_step, eval_fn, data_sizes=[1] * N)
    batches = jnp.broadcast_to(_targets(), (5, 1, N, 4))
    params, _, _, logs = eng.run_rounds({"x": jnp.zeros((N, 4))}, None,
                                        batches, jnp.zeros((N, 1)), None, 0)
    out = np.asarray(params["x"])
    for i in range(1, N):
        np.testing.assert_allclose(out[i], out[0], rtol=1e-5, atol=1e-6)


def test_engine_round_runs_local_steps_then_sync():
    """engine.round advances exactly T local steps before the gated commit."""
    train_step, eval_fn = _toy_fns()
    cfg = _cfg(sync_every=3, merge="mean")
    eng = SwarmEngine(cfg, train_step, eval_fn, data_sizes=[1] * N)
    batches = jnp.broadcast_to(_targets(), (3, N, 4))
    params, _, out = eng.round({"x": jnp.zeros((N, 4))}, None, batches,
                               jnp.zeros((N, 1)), None, 0)
    assert out["train"]["loss"].shape == (3, N)
    # 3 gradient steps toward target i: x = i * (1 - 0.9^3), then full-mean
    iterate = np.arange(N) * (1 - 0.9 ** 3)
    np.testing.assert_allclose(np.asarray(params["x"]),
                               np.tile(iterate.mean(), (N, 4)),
                               rtol=1e-5, atol=1e-6)
