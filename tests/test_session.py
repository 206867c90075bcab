"""SwarmSession: the backend-agnostic API over one SwarmState pytree.

Pins the redesign's acceptance criteria:
  * session drivers == the SwarmEngine tuple API and the numpy oracle,
  * the backend is "engine" or "gossip", the same two names on the session
    and the engine; ``round``/``run_rounds``/``run_local`` share one
    stacked-array contract,
  * join→leave→rejoin mid-run reuses the compiled round (ZERO retraces,
    asserted via a trace counter in the train step's python body),
  * ring/dynamic fisher & gradmatch merges match the numpy host oracle
    (topology-restricted per-row ratio) to fused-kernel tolerance,
  * checkpoint/resume round-trips the FULL state (params, opt state,
    strategy stats, membership, rng, counters) — continuing from a restore
    is bit-identical to never having stopped,
  * checkpoint keys no longer collide for pytrees whose paths used to
    serialize identically (dict key "0" vs sequence index 0, "a/b" vs a→b),
  * the gate_metric knob selects traced macro-F1 / sensitivity / accuracy
    matching their host numpy oracles,
  * the opt-in 4-tuple train step feeds exact squared gradients into the
    fisher accumulators (true-Fisher hook).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SwarmConfig
from repro.core import merge_impl as merge_lib
from repro.core import topology as topo
from repro.core.engine import SwarmEngine, active_weights
from repro.core.session import SwarmSession, SwarmState
from repro.faults import oracle

N = 4


def _toy_fns():
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


def _session(cfg, train_step=None, eval_fn=None, **kw):
    ts, ef = _toy_fns()
    kw.setdefault("params", {"x": jnp.zeros((4,))})
    kw.setdefault("data_sizes", [100 * (i + 1) for i in range(N)])
    return SwarmSession(cfg, train_step or ts, eval_fn or ef, **kw)


# ---------------------------------------------------------------------------
# session == legacy engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("merge", ["fedavg", "fisher"])
def test_session_matches_legacy_engine(merge):
    """run_rounds through the SwarmState API == the legacy tuple API."""
    train_step, eval_fn = _toy_fns()
    cfg = _cfg(merge=merge)
    batches = jnp.broadcast_to(_targets(), (3, 2, N, 4))
    sizes = [100 * (i + 1) for i in range(N)]

    eng = SwarmEngine(cfg, train_step, eval_fn, data_sizes=sizes)
    want, _, _, legacy_logs = eng.run_rounds(
        {"x": jnp.zeros((N, 4))}, None, batches, jnp.zeros((N, 1)), None, 0)

    sess = _session(cfg)
    logs = sess.run_rounds(batches, jnp.zeros((N, 1)))
    np.testing.assert_allclose(np.asarray(sess.state.params["x"]),
                               np.asarray(want["x"]), rtol=1e-6, atol=1e-7)
    np.testing.assert_array_equal(np.asarray(logs["gates"]),
                                  np.asarray(legacy_logs["gates"]))
    assert int(sess.state.round) == 3 and int(sess.state.step) == 6


def test_session_overlap_mode_runs():
    """The stale-by-one double-buffered schedule works through the session."""
    cfg = _cfg(sync_every=1, overlap_sync=True)
    sess = _session(cfg)
    batches = jnp.broadcast_to(_targets(), (6, 1, N, 4))
    logs = sess.run_rounds(batches, jnp.zeros((N, 1)))
    assert np.asarray(logs["gates"]).all()
    assert np.isfinite(np.asarray(sess.state.params["x"])).all()


# ---------------------------------------------------------------------------
# dynamic membership: zero retraces (the acceptance criterion)
# ---------------------------------------------------------------------------

def test_membership_changes_trigger_zero_retraces():
    """join → leave → rejoin between rounds AND between run_rounds calls
    compiles the round exactly once: the traced-topology mixing matrix makes
    membership pure runtime data."""
    base_step, eval_fn = _toy_fns()
    traces = []

    def counting_step(p, o, b, s):
        traces.append(1)  # python body executes only while tracing
        return base_step(p, o, b, s)

    sess = _session(_cfg(topology="dynamic"), counting_step, eval_fn)
    batches = jnp.broadcast_to(_targets(), (2, N, 4))
    rbatches = jnp.broadcast_to(_targets(), (2, 2, N, 4))
    val = jnp.zeros((N, 1))

    sess.round(batches, val)
    round_traces = len(traces)
    assert round_traces >= 1

    sess.leave(2)                       # leave
    out = sess.round(batches, val)
    assert not np.asarray(out["gates"])[2]
    sess.leave(1)                       # second leave, different mask
    sess.round(batches, val)
    sess.join(1)
    sess.join(2)                        # rejoin
    out = sess.round(batches, val)
    assert np.asarray(out["gates"]).all()
    assert len(traces) == round_traces, "membership change retraced round()"

    sess.run_rounds(rbatches, val)      # separate driver: one new trace
    rounds_traces = len(traces)
    sess.leave(3)                       # ... reused across membership changes
    logs = sess.run_rounds(rbatches, val)
    assert not np.asarray(logs["gates"])[:, 3].any()
    sess.join(3)
    sess.run_rounds(rbatches, val)
    assert len(traces) == rounds_traces, "membership change retraced run_rounds()"


def test_left_node_trains_locally_and_rejoins():
    """A departed node keeps training on its own shard but is excluded from
    every merge (no sends, no receives); on rejoin it merges again."""
    sess = _session(_cfg(sync_every=1, topology="dynamic"))
    batches = jnp.broadcast_to(_targets(), (1, N, 4))
    val = jnp.zeros((N, 1))
    sess.round(batches, val)
    sess.leave(2)
    x2 = float(sess.state.params["x"][2, 0])
    for _ in range(2):
        out = sess.round(batches, val)
        assert not np.asarray(out["gates"])[2]
        # pure local descent toward target 2.0, untouched by any merge
        x2 = x2 + 0.1 * (2.0 - x2)
        np.testing.assert_allclose(np.asarray(sess.state.params["x"][2]),
                                   np.full(4, x2, np.float32), rtol=1e-6)
    sess.join(2)
    out = sess.round(batches, val)
    assert np.asarray(out["gates"])[2]
    # back in the swarm: node 2's params snap to the consensus merge again
    np.testing.assert_allclose(np.asarray(sess.state.params["x"][2]),
                               np.asarray(sess.state.params["x"][0]),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# topology-restricted weighted merges (ring/dynamic fisher & gradmatch)
# ---------------------------------------------------------------------------

def _topo_oracle(x, mass, rows, eps):
    """numpy ground truth: out[i] = Σ_j rows[ij](m_j+eps)x_j / Σ_j rows[ij](m_j+eps)."""
    ff = mass + eps
    num = rows @ (ff * x)
    den = rows @ ff
    return num / np.maximum(den, 1e-30)


@pytest.mark.parametrize("method", ["fisher", "gradmatch"])
@pytest.mark.parametrize("topology", ["ring", "dynamic"])
def test_topology_restricted_weighted_merge_matches_oracle(method, topology):
    """Engine sync for ring/dynamic fisher/gradmatch == the per-row
    neighbour-restricted numpy oracle, to fused-kernel tolerance; the
    departed node is exactly excluded (not eps-suppressed)."""
    rng = np.random.default_rng(0)
    params = {"x": jnp.asarray(rng.normal(0, 1, (N, 6)), jnp.float32)}
    stats = {"x": jnp.asarray(np.abs(rng.normal(1, 0.5, (N, 6))), jnp.float32)}
    _, eval_fn = _toy_fns()
    sizes = [100 * (i + 1) for i in range(N)]
    cfg = _cfg(merge=method, topology=topology)
    eng = SwarmEngine(cfg, None, eval_fn, data_sizes=sizes)
    active = jnp.asarray([True, True, False, True])
    committed, log = jax.jit(eng.sync)(params, jnp.zeros((N, 1)), active,
                                       stats)
    assert not np.asarray(log["gates"])[2]

    a = np.array([True, True, False, True])
    W = topo.dynamic_matrix(topo.build_matrix(topology, N), a)
    w = active_weights(sizes, a)
    strategy = merge_lib.get_strategy(cfg)
    mass = np.asarray(strategy.finalize_mass(stats, jnp.asarray(a))["x"])
    rows = np.asarray(strategy.topo_rows(jnp.asarray(W, jnp.float32),
                                         jnp.asarray(w, jnp.float32)))
    want = _topo_oracle(np.asarray(params["x"]), mass, rows, strategy.eps)
    got = np.asarray(committed["x"])
    np.testing.assert_array_equal(got[2], np.asarray(params["x"])[2])
    for i in (0, 1, 3):
        np.testing.assert_allclose(got[i], want[i], rtol=2e-4, atol=2e-5)


def test_ring_fisher_only_uses_graph_neighbours():
    """A node two hops away contributes nothing to a ring fisher merge."""
    _, eval_fn = _toy_fns()
    params = {"x": jnp.asarray([[0.0], [0.0], [100.0], [0.0]], jnp.float32)}
    stats = {"x": jnp.ones((N, 1), jnp.float32)}
    eng = SwarmEngine(_cfg(merge="fisher", topology="ring"), None, eval_fn,
                      data_sizes=[1] * N)
    committed, _ = jax.jit(eng.sync)(params, jnp.zeros((N, 1)), None, stats)
    # node 0's ring neighbours are 1 and 3 — node 2's huge params must not
    # leak in (a global merge would put ~25 here)
    assert abs(float(committed["x"][0, 0])) < 1e-3


# ---------------------------------------------------------------------------
# checkpoint / resume
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("merge", ["fedavg", "fisher"])
def test_checkpoint_resume_is_bit_identical(tmp_path, merge):
    """save → restore → continue == never stopping (params, stats, rng,
    counters, membership all round-trip through checkpointing.io)."""
    cfg = _cfg(merge=merge, topology="dynamic")
    batches = jnp.broadcast_to(_targets(), (2, N, 4))
    val = jnp.zeros((N, 1))
    path = str(tmp_path / "sess.msgpack")

    ref = _session(cfg)
    ref.leave(3)
    for _ in range(4):
        ref.round(batches, val)

    sess = _session(cfg)
    sess.leave(3)
    for _ in range(2):
        sess.round(batches, val)
    sess.save(path)

    resumed = SwarmSession.restore(path, cfg, *_toy_fns(),
                                   params={"x": jnp.zeros((4,))},
                                   data_sizes=[100 * (i + 1)
                                               for i in range(N)])
    assert int(resumed.state.round) == 2 and int(resumed.state.step) == 4
    np.testing.assert_array_equal(np.asarray(resumed.state.active),
                                  [True, True, True, False])
    for _ in range(2):
        resumed.round(batches, val)
    np.testing.assert_array_equal(np.asarray(resumed.state.params["x"]),
                                  np.asarray(ref.state.params["x"]))
    np.testing.assert_array_equal(np.asarray(resumed.state.rng),
                                  np.asarray(ref.state.rng))
    if merge == "fisher":
        np.testing.assert_array_equal(np.asarray(resumed.state.stats["x"]),
                                      np.asarray(ref.state.stats["x"]))


def test_restore_rejects_mismatched_cfg(tmp_path):
    path = str(tmp_path / "sess.msgpack")
    _session(_cfg()).save(path)
    with pytest.raises(ValueError, match="cfg mismatch"):
        _session(_cfg(merge="fisher")).load(path)


def test_checkpoint_key_collisions_fixed(tmp_path):
    """Pytree paths that used to serialize identically (dict key "0" vs
    sequence index 0; dict key "a/b" vs nested a→b) now round-trip."""
    from repro.checkpointing import load_pytree, save_pytree
    tree = {
        "d": {"0": jnp.asarray([1.0]), "1": jnp.asarray([2.0])},
        "l": [jnp.asarray([3.0]), jnp.asarray([4.0])],
        "a/b": jnp.asarray([5.0]),
        "a": {"b": jnp.asarray([6.0])},
    }
    path = str(tmp_path / "tree.msgpack")
    save_pytree(path, tree)
    out = load_pytree(path, jax.tree.map(jnp.zeros_like, tree))
    for want, got in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_checkpoint_legacy_keys_still_load(tmp_path):
    """Old checkpoints ("/"-joined key format) remain readable."""
    import msgpack
    tree = {"a": {"b": jnp.asarray([1.5, 2.5])}}
    path = str(tmp_path / "legacy.msgpack")
    arr = np.asarray(tree["a"]["b"])
    payload = {"leaves": {"a/b": {"dtype": str(arr.dtype),
                                  "shape": list(arr.shape),
                                  "data": arr.tobytes()}},
               "metadata": {}}
    with open(path, "wb") as f:
        f.write(msgpack.packb(payload, use_bin_type=True))
    from repro.checkpointing import load_pytree
    out = load_pytree(path, jax.tree.map(jnp.zeros_like, tree))
    np.testing.assert_array_equal(np.asarray(out["a"]["b"]), arr)


# ---------------------------------------------------------------------------
# the backends and the drivers' one contract
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["host", "spmd"])
def test_backend_names_are_engine_and_gossip(backend):
    """The session and the engine take the same two backend names and
    refuse any other, naming the two."""
    with pytest.raises(ValueError, match='"engine" or "gossip"'):
        _session(_cfg(), backend=backend)
    with pytest.raises(ValueError, match='"engine" or "gossip"'):
        SwarmEngine(_cfg(), *_toy_fns(), backend=backend)


def test_session_passes_its_backend_to_the_engine():
    sess = _session(_cfg())
    assert sess.backend == sess.engine.backend == "engine"
    assert SwarmEngine(_cfg(), *_toy_fns()).backend == "engine"


def test_session_matches_oracle_through_leave_and_join():
    """The toy schedule through the session lands on the numpy oracle's
    params — including after a leave(): a departed node that still receives
    batches keeps training locally and is only excluded from merges."""
    cfg = _cfg(topology="dynamic")
    sess = _session(cfg)
    batches = jnp.broadcast_to(_targets(), (2, N, 4))
    val = jnp.zeros((N, 1))
    sess.round(batches, val)
    sess.leave(3)
    sess.round(batches, val)
    sess.join(3)
    sess.round(batches, val)
    want = oracle.simulate(
        np.zeros((N, 4)), np.asarray(_targets()),
        np.asarray([[1, 1, 1, 1], [1, 1, 1, 0], [1, 1, 1, 1]]),
        merge="fedavg", topology="dynamic", lr=0.1, steps_per_round=2,
        data_sizes=[100 * (i + 1) for i in range(N)])[-1]
    np.testing.assert_allclose(np.asarray(sess.state.params["x"]), want,
                               rtol=1e-5, atol=1e-6)
    assert int(sess.state.round) == 3


def test_run_rounds_logs_are_round_logs_stacked():
    """run_rounds returns what R round() calls return, stacked over R."""
    cfg = _cfg(merge="fisher", quorum=2)
    batches = jnp.broadcast_to(_targets(), (3, 2, N, 4))
    val = jnp.zeros((N, 1))
    scanned = _session(cfg)
    logs = scanned.run_rounds(batches, val)
    looped = _session(cfg)
    each = [looped.round(batches[r], val) for r in range(3)]
    assert set(logs) == set(each[0])
    for key in logs:
        want = jax.tree.map(lambda *xs: np.stack(xs), *each)[key]
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), b, rtol=1e-6), logs[key], want)
    assert np.asarray(logs["gates"]).shape == (3, N)
    assert np.asarray(logs["train"]["loss"]).shape == (3, 2, N)
    np.testing.assert_allclose(np.asarray(scanned.state.params["x"]),
                               np.asarray(looped.state.params["x"]),
                               rtol=1e-6)


def test_run_local_trains_without_sync():
    """run_local: S local steps on stacked [S, N, ...] batches, [S, N]
    metrics back, the step counter advanced and no merge."""
    sess = _session(_cfg())
    tm = sess.run_local(jnp.broadcast_to(_targets(), (3, N, 4)))
    assert np.asarray(tm["loss"]).shape == (3, N)
    # pure local descent toward each node's target: x = t·(1 − 0.9³)
    want = np.asarray(_targets()) * (1 - 0.9 ** 3)
    np.testing.assert_allclose(np.asarray(sess.state.params["x"]), want,
                               rtol=1e-6)
    assert int(sess.state.step) == 3 and int(sess.state.round) == 0


# ---------------------------------------------------------------------------
# gate metrics beyond AUC
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(3))
def test_traced_gate_metrics_match_host_oracles(seed):
    """Traced macro-F1 / sensitivity / accuracy == the numpy confusion-stats
    oracles, including padding masks and absent classes."""
    from repro.metrics import (accuracy, accuracy_traced, confusion_stats,
                               macro_f1_traced, sensitivity_traced)
    rng = np.random.default_rng(seed)
    v, pad = 41, 7
    probs = rng.random((v, 3)).astype(np.float32)
    labels = rng.integers(0, 3 if seed % 2 else 2, v)  # even seeds: no class 2
    preds = probs.argmax(-1)
    want = confusion_stats(preds, labels, 3)
    probs_p = np.concatenate([probs, np.zeros((pad, 3), np.float32)])
    labels_p = np.concatenate([labels, np.zeros(pad, np.int64)])
    mask = np.arange(v + pad) < v
    args = (jnp.asarray(probs_p), jnp.asarray(labels_p), jnp.asarray(mask))
    assert float(macro_f1_traced(*args)) == pytest.approx(want["f1"], abs=1e-6)
    assert float(sensitivity_traced(*args)) == pytest.approx(
        want["sensitivity"], abs=1e-6)
    assert float(accuracy_traced(*args)) == pytest.approx(
        accuracy(preds, labels), abs=1e-6)


def test_gate_metric_knob_selects_traced_metric():
    from repro.metrics import (accuracy_traced, gate_metric_fn,
                               macro_auc_traced, macro_f1_traced,
                               sensitivity_traced)
    assert gate_metric_fn("auc") is macro_auc_traced
    assert gate_metric_fn("f1") is macro_f1_traced
    assert gate_metric_fn("sensitivity") is sensitivity_traced
    assert gate_metric_fn("accuracy") is accuracy_traced
    with pytest.raises(ValueError, match="unknown gate_metric"):
        gate_metric_fn("bleu")


def test_histo_loop_with_f1_gate_runs():
    """The gate_metric knob drives the histo swarm loop end-to-end."""
    from repro.data import make_histo_dataset, paper_splits, shard_to_nodes
    from repro.experiments.histo import (HistoExperimentConfig,
                                         _make_model_fns, _train_loop)
    ecfg = HistoExperimentConfig(
        n_train=120, n_test=24, steps=4, image_size=16, batch_size=8,
        noise=0.6, growth=4, stem=8, feat_dim=32, hidden=16, n_blocks=1,
        layers_per_block=2, seed=5,
        swarm=SwarmConfig(n_nodes=4, sync_every=2, topology="full",
                          merge="fedavg", lora_only=False, val_threshold=0.8,
                          gate_metric="f1"))
    images, labels = make_histo_dataset(ecfg.n_train, size=ecfg.image_size,
                                        noise=ecfg.noise, seed=ecfg.seed)
    shards = shard_to_nodes(images, labels,
                            paper_splits(ecfg.n_train, ecfg.fractions),
                            seed=ecfg.seed)
    train_step, _, _ = _make_model_fns(ecfg)
    params, sync_log = _train_loop(ecfg, train_step, shards,
                                   swarm_cfg=ecfg.swarm)
    assert len(params) == 4 and sync_log
    for s in sync_log:
        assert all(0.0 <= m <= 1.0 for m in s["metric_local"])


# ---------------------------------------------------------------------------
# true-Fisher accumulation hook
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("merge", ["fisher", "gradmatch"])
def test_four_tuple_train_step_accumulates_exact_grad_squares(merge):
    """A train step returning (params, opt, metrics, grads) feeds F ← γF + g²
    (exact squared gradients) instead of the Δθ² proxy."""
    decay = 0.5

    def grad_step(p, o, b, s):
        g = p["x"] - b
        return {"x": p["x"] - 0.1 * g}, o, {"loss": jnp.sum(g * g)}, {"x": g}

    _, eval_fn = _toy_fns()
    cfg = _cfg(merge=merge, fisher_decay=decay)
    eng = SwarmEngine(cfg, grad_step, eval_fn, data_sizes=[1] * N)
    batches = jnp.broadcast_to(_targets(), (2, N, 4))
    p0 = {"x": jnp.zeros((N, 4))}
    _, _, stats, _ = jax.jit(eng.local_steps)(p0, None, batches, 0,
                                              eng.init_stats(p0))
    t = np.stack([np.full(4, float(i), np.float32) for i in range(N)])
    # g0 = -t; θ1 = 0.1t; g1 = -0.9t  ->  F = γ·g0² + g1²
    want = decay * t ** 2 + (0.9 * t) ** 2
    np.testing.assert_allclose(np.asarray(stats["x"]), want, rtol=1e-5)
