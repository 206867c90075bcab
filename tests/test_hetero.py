"""Heterogeneous swarm (ISSUE 10): adapter-only ``payload="lora"`` mode.

Pins the tentpole semantics end to end on the engine backend:

  * `comms.payload_mode` / `split_payload_at_sync` validation and the lora
    payload-class tagging of every picked schedule,
  * `lora.flatten_payload` / `unflatten_payload` — THE sole adapter
    flatten implementation (swarmlint SWL004 `adapter_flatten`),
  * the model zoo (`models.zoo`): heterogeneous frozen backbones around one
    shared LoRA'd head, structurally identical payload rows,
  * zoo closure lists through `zoo_vstep`/`zoo_veval` — per-node dispatch
    with the stacked-state contract, zero retraces across join/leave,
  * the fairness gate (`SwarmConfig.fairness_floor`): worst-active-site
    metric floor ANDed into the commit gate like quorum,
  * committed-adapter parity vs the numpy mixing oracle,
  * checkpoint round-trips of the adapter-only state (incl. the int8 EF
    wire residuals) bit-identically, with cfg-mismatch rejection,
  * the scenario grid (`experiments.scenarios`): biased-label partitions,
    synthetic augmentation, and the BENCH_hetero row contract.

The multi-device HLO bytes / mesh-wire checks live in tests/test_hetero_spmd.py.
"""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SwarmConfig
from repro.core import comms
from repro.core import topology as topo
from repro.core.engine import zoo_veval, zoo_vstep
from repro.core.lora import flatten_payload, inject_lora, unflatten_payload
from repro.core.session import SwarmSession
from repro.experiments import scenarios
from repro.models import zoo

N = 4


def _cfg(**kw):
    kw.setdefault("n_nodes", N)
    kw.setdefault("sync_every", 2)
    kw.setdefault("merge", "fedavg")
    kw.setdefault("topology", "full")
    kw.setdefault("lora_only", False)
    kw.setdefault("val_threshold", 0.0)
    return SwarmConfig(**kw)


# ---------------------------------------------------------------------------
# payload mode plumbing
# ---------------------------------------------------------------------------

def test_payload_mode_validation():
    assert comms.payload_mode(_cfg()) == "full"
    assert comms.payload_mode(_cfg(payload="lora")) == "lora"
    with pytest.raises(ValueError, match="unknown payload mode"):
        comms.payload_mode(_cfg(payload="int8"))


def test_split_payload_at_sync_semantics():
    """lora_only still means "only adapters cross the wire" — but in
    payload="lora" mode there is nothing to carve out at sync time."""
    assert not comms.split_payload_at_sync(_cfg())
    assert comms.split_payload_at_sync(_cfg(lora_only=True))
    assert not comms.split_payload_at_sync(_cfg(lora_only=True,
                                                payload="lora"))
    assert not comms.split_payload_at_sync(_cfg(payload="lora"))


def test_every_candidate_schedule_carries_the_payload_class():
    for cfg in (_cfg(payload="lora"), _cfg(lora_only=True)):
        for s in comms.candidate_schedules(cfg):
            assert s.payload == "lora", s.name
            assert "/lora" in s.describe(), s.describe()
    for s in comms.candidate_schedules(_cfg()):
        assert s.payload == "full", s.name
        assert "/lora" not in s.describe()


# ---------------------------------------------------------------------------
# the sole adapter flatten implementation
# ---------------------------------------------------------------------------

def _lora_params():
    base = {"attn": {"w": jnp.arange(12.0).reshape(4, 3)},
            "mlp": {"w": jnp.ones((3, 2)), "b": jnp.zeros((2,))}}
    return inject_lora(base, jax.random.PRNGKey(0), rank=2, targets="attn")


def test_flatten_payload_roundtrip():
    params = _lora_params()
    flat = flatten_payload(params)
    assert sorted(flat) == ["attn/lora_A", "attn/lora_B", "attn/lora_scale"]
    assert flat["attn/lora_A"].shape == (4, 2)
    # substitution: unflatten writes the payload rows back into the template
    bumped = {k: v + 1.0 for k, v in flat.items()}
    full = unflatten_payload(bumped, params)
    np.testing.assert_array_equal(np.asarray(full["attn"]["lora_A"]),
                                  np.asarray(params["attn"]["lora_A"]) + 1.0)
    # non-payload leaves come straight from the template
    np.testing.assert_array_equal(np.asarray(full["attn"]["w"]),
                                  np.asarray(params["attn"]["w"]))


def test_flatten_payload_custom_select_and_errors():
    params = _lora_params()
    flat = flatten_payload(params, lambda p: p.startswith("mlp/"))
    assert sorted(flat) == ["mlp/b", "mlp/w"]
    with pytest.raises(ValueError, match="no leaf matched"):
        flatten_payload(params, lambda p: False)
    with pytest.raises(ValueError, match="not present in"):
        unflatten_payload({"nope/w": jnp.zeros(())}, params)


def test_adapter_flatten_is_sole_impl_registered():
    """The swarmlint SWL004 registry guards the single implementation; the
    repo tree must be clean of rogue copies (the fixture corpus in
    tests/lint_fixtures/swl004_adapter_flatten.py proves the positive)."""
    from repro.analysis.lint import run_paths
    from repro.analysis.rules import SOLE_IMPLS
    spec = {s.name: s for s in SOLE_IMPLS}["adapter_flatten"]
    assert spec.allowed == "src/repro/core/lora.py"
    assert run_paths(["src"], rules=["SWL004"]) == []


# ---------------------------------------------------------------------------
# the model zoo
# ---------------------------------------------------------------------------

_PAYLOAD_KEYS = ["head/out/b", "head/out/w", "head/proj/lora_A",
                 "head/proj/lora_B", "head/proj/lora_scale"]


def _tiny_zoo(n=N):
    return zoo.build_zoo(jax.random.PRNGKey(0), n, image_size=16,
                         feat_dim=8, hidden=8, rank=2)


def test_zoo_payload_rows_are_structurally_identical():
    nodes = _tiny_zoo()
    assert [nd.family for nd in nodes] == list(zoo.DEFAULT_FAMILIES)
    payloads = [nd.payload() for nd in nodes]
    for p in payloads:
        assert sorted(p) == _PAYLOAD_KEYS
    # one shared head key: every node's payload row starts identical, and
    # the frozen backbones (never in the payload) differ per family
    for p in payloads[1:]:
        for k in p:
            np.testing.assert_array_equal(np.asarray(p[k]),
                                          np.asarray(payloads[0][k]))
    structs = {str(jax.tree.structure(nd.template["backbone"]))
               for nd in nodes}
    assert len(structs) > 1, "zoo backbones should be heterogeneous"


def test_zoo_apply_emits_logits_through_each_backbone():
    nodes = _tiny_zoo()
    x = jnp.asarray(np.random.default_rng(0).normal(0, 1, (5, 16, 16, 3)),
                    jnp.float32)
    for nd in nodes:
        logits = nd.apply(nd.payload(), x)
        assert logits.shape == (5, 3)
        assert np.isfinite(np.asarray(logits)).all()


def test_zoo_grads_flow_only_through_the_payload():
    nd = _tiny_zoo(1)[0]
    x = jnp.asarray(np.random.default_rng(1).normal(0, 1, (4, 16, 16, 3)),
                    jnp.float32)

    def loss(payload):
        return jnp.sum(nd.apply(payload, x) ** 2)

    g = jax.grad(loss)(nd.payload())
    # the trainable surface is exactly the payload; gradients reach it
    assert sorted(g) == _PAYLOAD_KEYS
    assert float(jnp.abs(g["head/out/b"]).max()) > 0
    assert float(jnp.abs(g["head/proj/lora_B"]).max()) > 0


def test_zoo_vstep_rejects_mixed_tuple_forms():
    def three(p, o, b, s):
        return p, o, {}

    def four(p, o, b, s):
        return p, o, {}, p

    p = {"x": np.zeros((2, 3))}
    with pytest.raises(ValueError, match="3-tuple vs"):
        zoo_vstep([three, four])(p, p, np.zeros((2, 1)), 0)
    out = zoo_veval([lambda p, v: jnp.asarray(0.25),
                     lambda p, v: jnp.asarray(0.75)])(p, np.zeros((2, 1)))
    np.testing.assert_allclose(np.asarray(out), [0.25, 0.75])


# ---------------------------------------------------------------------------
# zoo sessions: closures + adapter-only state on the engine backend
# ---------------------------------------------------------------------------

def _payload_session(cfg, nodes=None, metric_vals=None, trace_log=None,
                     decay=0.01, seed=0):
    """payload="lora" session over the tiny zoo with decay-toward-zero
    train closures (payload-only dynamics keep oracles analytic)."""
    nodes = nodes or _tiny_zoo(cfg.n_nodes)

    def make(i):
        def step(p, o, b, s):
            if trace_log is not None:
                trace_log.append(i)
            return ({k: v * (1.0 - decay) for k, v in p.items()}, o,
                    {"loss": 0.0 * jnp.sum(p["head/out/w"])})

        def ev(p, v):
            c = 1.0 if metric_vals is None else metric_vals[i]
            return c - 0.0 * jnp.sum(p["head/out/w"])

        return step, ev

    fns = [make(i) for i in range(cfg.n_nodes)]
    payloads = [nd.payload() for nd in nodes]
    return SwarmSession(cfg, [f[0] for f in fns], [f[1] for f in fns],
                        params=payloads, data_sizes=[10.0 * (i + 1) for i in
                                                     range(cfg.n_nodes)],
                        seed=seed)


def _batches(cfg, t=None):
    return jnp.zeros(((t or cfg.sync_every), cfg.n_nodes, 1))


def _val(cfg):
    return jnp.zeros((cfg.n_nodes, 1))


def test_payload_lora_session_zero_retrace_across_membership():
    cfg = _cfg(payload="lora", wire_dtype="int8", wire_block=128,
               topology="ring")
    trace_log = []
    sess = _payload_session(cfg, trace_log=trace_log)
    assert sess.sync_schedule.payload == "lora"
    assert sess.payload_params == sum(
        int(v.size) for v in _tiny_zoo(1)[0].payload().values())
    sess.round(_batches(cfg), _val(cfg))       # the one and only trace
    warm = len(trace_log)
    assert warm >= cfg.n_nodes                 # every closure traced
    sess.leave(2)
    sess.round(_batches(cfg), _val(cfg))
    sess.join(2)
    sess.leave(0)
    out = sess.round(_batches(cfg), _val(cfg))
    assert len(trace_log) == warm, "membership flips must not retrace"
    gates = np.asarray(out["gates"])
    assert not gates[0] and gates[1]           # inactive node never commits
    # the int8 EF wire rides SwarmState next to the flat payload
    assert sess.state.wire is not None
    assert sorted(sess.state.params) == _PAYLOAD_KEYS


def test_fairness_floor_gates_on_worst_active_site():
    metric_vals = [0.2, 0.4, 0.6, 0.8]
    cfg = _cfg(payload="lora", fairness_floor=0.3)
    sess = _payload_session(cfg, metric_vals=metric_vals)
    before = jax.tree.map(np.asarray, sess.state.params)
    out = sess.round(_batches(cfg), _val(cfg))
    # site 0's merged metric (0.2) is under the floor: the WHOLE swarm
    # holds its locals — params advance by local steps only, no commit
    assert not bool(np.asarray(out["fairness_ok"]))
    assert np.asarray(out["worst_site"]) == pytest.approx(0.2)
    assert not np.asarray(out["gates"]).any()
    # inactive sites never drag the min: with site 0 gone the worst
    # ACTIVE site (0.4) clears the floor and the commit lands
    sess.leave(0)
    out2 = sess.round(_batches(cfg), _val(cfg))
    assert bool(np.asarray(out2["fairness_ok"]))
    assert np.asarray(out2["worst_site"]) == pytest.approx(0.4)
    assert np.asarray(out2["gates"])[1:].all()
    del before


def test_fairness_floor_disabled_and_validated():
    cfg = _cfg(payload="lora")
    sess = _payload_session(cfg)
    out = sess.round(_batches(cfg), _val(cfg))
    assert "fairness_ok" not in out and "worst_site" not in out
    with pytest.raises(ValueError, match="fairness_floor"):
        _payload_session(_cfg(payload="lora", fairness_floor=1.5))


def test_fairness_floor_composes_with_quorum():
    cfg = _cfg(payload="lora", fairness_floor=0.3, quorum=4)
    sess = _payload_session(cfg, metric_vals=[0.5] * N)
    out = sess.round(_batches(cfg), _val(cfg))
    assert bool(np.asarray(out["fairness_ok"]))
    assert bool(np.asarray(out["quorum_ok"]))
    assert np.asarray(out["gates"]).all()
    sess.leave(3)                              # below quorum, floor still ok
    out2 = sess.round(_batches(cfg), _val(cfg))
    assert bool(np.asarray(out2["fairness_ok"]))
    assert not bool(np.asarray(out2["quorum_ok"]))
    assert not np.asarray(out2["gates"]).any()


def test_committed_adapters_match_numpy_mixing_oracle():
    """Identity local steps + accepting gates: one round commits exactly
    W @ payload_rows for every flat payload leaf (numpy host oracle)."""
    for topology in ("full", "ring"):
        cfg = _cfg(payload="lora", topology=topology, sync_every=1)
        sess = _payload_session(cfg, decay=0.0)
        start = {k: np.asarray(v).copy()
                 for k, v in sess.state.params.items()}
        sizes = [10.0 * (i + 1) for i in range(N)]
        W = topo.build_matrix(
            topology, N,
            weights=topo.fedavg_weights(sizes) if topology == "full" else None)
        out = sess.round(_batches(cfg), _val(cfg))
        assert np.asarray(out["gates"]).all()
        for k, v in sess.state.params.items():
            got = np.asarray(v)
            want = np.tensordot(W, start[k], axes=(1, 0))
            np.testing.assert_allclose(got, want, atol=1e-6, err_msg=k)


def test_payload_lora_checkpoint_bit_identical():
    """save → restore → continue == never stopping, for the flat adapter
    state AND the int8 EF wire residuals (ISSUE 10 satellite)."""
    cfg = _cfg(payload="lora", wire_dtype="int8", wire_block=128,
               topology="ring")

    def run(rounds, resume_at=None, path=None):
        sess = _payload_session(cfg)
        for r in range(rounds):
            if resume_at is not None and r == resume_at:
                sess.save(path)
                sess = SwarmSession.restore(
                    path, cfg, sess.train_step_fn, sess.eval_fn,
                    params=[nd.payload() for nd in _tiny_zoo()],
                    data_sizes=[10.0 * (i + 1) for i in range(N)], seed=0)
            sess.round(_batches(cfg), _val(cfg))
        return sess

    path = os.path.join(tempfile.mkdtemp(), "hetero.msgpack")
    ref = run(4)
    got = run(4, resume_at=2, path=path)
    for k in ref.state.params:
        np.testing.assert_array_equal(np.asarray(got.state.params[k]),
                                      np.asarray(ref.state.params[k]))
    for a, b in zip(jax.tree.leaves(got.state.wire),
                    jax.tree.leaves(ref.state.wire)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_payload_mode_mismatch_rejected():
    cfg = _cfg(payload="lora")
    sess = _payload_session(cfg)
    path = os.path.join(tempfile.mkdtemp(), "hetero_mismatch.msgpack")
    sess.save(path)
    other = _payload_session(_cfg())           # payload="full" session
    with pytest.raises(ValueError, match="payload"):
        other.load(path)


def test_zoo_closure_list_length_must_match_n_nodes():
    nodes = _tiny_zoo()
    payloads = [nd.payload() for nd in nodes]
    fns = [lambda p, o, b, s: (p, o, {})] * (N - 1)
    with pytest.raises(ValueError, match="one closure per node"):
        SwarmSession(_cfg(payload="lora"), fns, lambda p, v: 1.0,
                     params=payloads)


# ---------------------------------------------------------------------------
# scenario grid
# ---------------------------------------------------------------------------

def test_scenario_grid_shape():
    grid = scenarios.scenario_grid()
    assert len(grid) >= 4
    names = [s.name for s in grid]
    assert len(set(names)) == len(names)
    parts = {s.partition for s in grid}
    assert {"label_skew", "label_synth", "dirichlet"} <= parts


def _corpus(n=240):
    from repro.data import make_histo_dataset
    return make_histo_dataset(n, size=16, noise=1.1,
                              class_probs=(0.5, 0.3, 0.2), seed=0)


def test_build_shards_label_skew_biases_labels():
    images, labels = _corpus()
    scn = next(s for s in scenarios.scenario_grid()
               if s.partition == "label_skew")
    shards, n_synth = scenarios.build_shards(scn, images, labels, N)
    assert n_synth == [0] * N
    assert sum(len(y) for _, y in shards) <= len(labels)
    for i, (_, y) in enumerate(shards):
        counts = np.bincount(y, minlength=3)
        assert counts.argmax() == i % 3, (i, counts)


def test_build_shards_synth_augments_starved_classes():
    images, labels = _corpus()
    scn = next(s for s in scenarios.scenario_grid()
               if s.partition == "label_synth")
    skew = next(s for s in scenarios.scenario_grid()
                if s.partition == "label_skew")
    shards, n_synth = scenarios.build_shards(scn, images, labels, N)
    plain, _ = scenarios.build_shards(skew, images, labels, N)
    assert all(k > 0 for k in n_synth)
    for i, ((_, y), (_, y0)) in enumerate(zip(shards, plain)):
        assert len(y) == len(y0) + n_synth[i]
        # the synthetic tail inverts the skew: site i's starved classes
        # gain share relative to the un-augmented shard
        starved = [c for c in range(3) if c != i % 3]
        frac = lambda yy: np.isin(yy, starved).mean()
        assert frac(y) > frac(y0), (i, frac(y), frac(y0))


def test_build_shards_dirichlet_floors_starved_sites():
    images, labels = _corpus()
    scn = next(s for s in scenarios.scenario_grid()
               if s.partition == "dirichlet")
    shards, _ = scenarios.build_shards(scn, images, labels, N)
    assert all(len(y) >= 8 for _, y in shards)
    with pytest.raises(ValueError, match="unknown partition"):
        scenarios.build_shards(
            scenarios.Scenario("x", "bogus"), images, labels, N)


@pytest.fixture(scope="module")
def scenario_row():
    """One fast biased-label cell end-to-end — the BENCH_hetero row."""
    rcfg = scenarios.ScenarioRunConfig(
        n_train=96, n_test=48, feat_dim=8, hidden=8, steps=8, batch_size=4,
        swarm=SwarmConfig(
            n_nodes=4, sync_every=4, topology="ring", merge="fedavg",
            payload="lora", wire_dtype="int8", wire_block=128,
            val_threshold=0.0, gate_metric="auc", fairness_floor=0.05))
    scn = next(s for s in scenarios.scenario_grid()
               if s.partition == "label_skew")
    return scenarios.run_scenario(scn, rcfg)


def test_scenario_row_contract(scenario_row):
    row = scenario_row
    for key in ("scenario", "families", "schedule", "payload_class",
                "payload_params", "wire_bytes_per_sync",
                "full_f32_bytes_per_sync", "wire_fraction_of_full",
                "retraces", "per_site", "site_auc_spread", "worst_site_auc",
                "oracle", "gates_last", "fairness_ok_last"):
        assert key in row, key
    assert row["payload_class"] == "lora"
    assert len(row["per_site"]) == 4
    assert len(set(row["families"])) == 4
    assert all("auc" in r and "sensitivity" in r for r in row["per_site"])
    assert row["site_auc_spread"] >= 0


def test_scenario_row_zero_retraces(scenario_row):
    assert scenario_row["retraces"] == 0


def test_scenario_row_wire_under_five_percent_of_full(scenario_row):
    """The headline acceptance ratio at the cost-model level: adapter-only
    int8 sync ≤ 5% of the full-payload f32 bytes (HLO-measured twin lives
    in tests/test_hetero_spmd.py)."""
    assert scenario_row["wire_fraction_of_full"] <= 0.05


# ---------------------------------------------------------------------------
# the fused head kernel dispatcher
# ---------------------------------------------------------------------------

def test_lora_apply_matches_unfused_form():
    from repro.kernels.lora_matmul import lora_apply, lora_matmul
    rng = np.random.default_rng(0)
    # zoo-head shapes: nothing tileable — the dispatcher must fall back
    x = jnp.asarray(rng.normal(0, 1, (5, 8)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 1, (8, 6)), jnp.float32)
    a = jnp.asarray(rng.normal(0, 1, (8, 2)), jnp.float32)
    b = jnp.asarray(rng.normal(0, 1, (2, 6)), jnp.float32)
    got = np.asarray(lora_apply(x, w, a, b, 0.5))
    want = np.asarray(x @ w + 0.5 * (x @ a) @ b)
    np.testing.assert_allclose(got, want, atol=1e-5)
    # MXU-tileable shapes: parity with the fused kernel (interpret mode)
    xt = jnp.asarray(rng.normal(0, 1, (128, 512)), jnp.float32)
    wt = jnp.asarray(rng.normal(0, 0.1, (512, 128)), jnp.float32)
    at = jnp.asarray(rng.normal(0, 0.1, (512, 4)), jnp.float32)
    bt = jnp.asarray(rng.normal(0, 0.1, (4, 128)), jnp.float32)
    fused = np.asarray(lora_matmul(xt, wt, at, bt, 2.0, interpret=True))
    unfused = np.asarray(lora_apply(xt, wt, at, bt, 2.0, interpret=True))
    np.testing.assert_allclose(fused, unfused, atol=2e-4)
