"""Chaos plane: deterministic fault injection + graceful degradation.

Pins the acceptance criteria of the faults subsystem:
  * `faults.retry.with_retry` — bounded attempts, exponential backoff,
    timeout budget, exception routing (RetryError wrap vs raise_last);
  * `FaultPlan` — declarative, validated, seeded; lowering produces the
    exact membership/corruption/rejoin/preempt matrices;
  * `comms.payload_checksum` + `faults.signals.flip_payload_bits` — any
    injected bit flip is detected, deterministically per (seed, round);
  * checkpoint durability — atomic writes (a failed save never tears the
    previous checkpoint), truncated/corrupt files raise a clear error,
    transient IO errors are retried;
  * degradation policies — corrupt-wire senders are quarantined for the
    round (reject-and-keep-local), sub-quorum rounds hold every node's
    locals (f32 and int8 wires), crash→rejoin resets the EF wire;
  * parity — every FaultPlan kind runs to completion with no hang and the
    committed params match the float64 numpy oracle (`faults.oracle`):
    full-trajectory ≤2e-5 on the f32 engine, settled ≤1e-5 on the int8
    EF wire; preempt-and-restore is bit-identical to the uninterrupted
    twin; the whole plan replays against ONE compiled round (zero
    retraces across crash/straggle/drop/corrupt).
"""
import dataclasses

import jax
import jax.numpy as jnp
import msgpack
import numpy as np
import pytest

import repro.faults.oracle as oracle
from repro.checkpointing import io as ckpt_io
from repro.checkpointing import load_pytree, save_pytree
from repro.configs.base import SwarmConfig
from repro.core import comms
from repro.core.session import SwarmSession
from repro.faults import (FaultEvent, FaultPlan, RetryError, flip_payload_bits,
                          idle_signals, run_plan, with_retry)
from repro.faults.signals import FaultSignals, plan_key

N = 4


# ---------------------------------------------------------------------------
# toy session plumbing (same dynamics the oracle replicates)
# ---------------------------------------------------------------------------

def _pull_step(p, o, b, s):
    """x ← x + 0.1·(target − x): the oracle's linear local step."""
    g = p["x"] - b
    return {"x": p["x"] - 0.1 * g}, o, {"loss": jnp.sum(g * g)}


def _id_step(p, o, b, s):
    return p, o, {"loss": 0.0 * jnp.sum(p["x"])}


def _accept_eval(p, v):
    return 1.0 - 0.0 * jnp.sum(p["x"])


def _cfg(**kw):
    kw.setdefault("n_nodes", N)
    kw.setdefault("sync_every", 2)
    kw.setdefault("merge", "fedavg")
    kw.setdefault("topology", "full")
    kw.setdefault("lora_only", False)
    kw.setdefault("val_threshold", 0.0)
    return SwarmConfig(**kw)


def _targets(d=8):
    return jnp.asarray([np.full((d,), t, np.float32) for t in range(N)])


def _session(cfg, train_step=_pull_step, eval_fn=_accept_eval, *,
             params=None, sizes=None, **kw):
    params = {"x": jnp.zeros((8,))} if params is None else params
    sizes = [1.0, 2.0, 3.0, 4.0] if sizes is None else sizes
    return SwarmSession(cfg, train_step, eval_fn, params=params,
                        data_sizes=sizes, **kw)


# ---------------------------------------------------------------------------
# retry helper
# ---------------------------------------------------------------------------

def test_retry_transient_success_and_backoff_schedule():
    delays, calls = [], []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("transient")
        return "ok"

    got = with_retry(flaky, attempts=5, base_delay=0.02, backoff=2.0,
                     sleep=delays.append)
    assert got == "ok" and len(calls) == 3
    assert delays == [0.02, 0.04]          # base · backoff^attempt


def test_retry_exhaustion_wraps_in_retryerror():
    boom = OSError("disk on fire")

    def always_fails():
        raise boom

    with pytest.raises(RetryError, match="3 attempt") as exc_info:
        with_retry(always_fails, attempts=3, sleep=lambda s: None,
                   describe="checkpoint write")
    assert exc_info.value.last_exception is boom
    assert exc_info.value.__cause__ is boom
    assert "checkpoint write" in str(exc_info.value)


def test_retry_raise_last_surfaces_original_type():
    def missing():
        raise FileNotFoundError("no such checkpoint")

    with pytest.raises(FileNotFoundError):
        with_retry(missing, attempts=2, sleep=lambda s: None, raise_last=True)


def test_retry_timeout_budget_stops_early():
    clock = {"t": 0.0}

    def tick():
        return clock["t"]

    def sleep(s):
        clock["t"] += s

    def always_fails():
        clock["t"] += 0.5
        raise OSError("slow failure")

    with pytest.raises(RetryError):
        with_retry(always_fails, attempts=100, base_delay=0.4, backoff=1.0,
                   timeout=1.0, sleep=sleep, clock=tick)
    # each attempt burns 0.5 s + 0.4 s backoff: the 1.0 s budget admits at
    # most two attempts, nowhere near the 100-attempt bound
    assert clock["t"] < 2.5


def test_retry_unlisted_exception_propagates_immediately():
    delays, calls = [], []

    def typo():
        calls.append(1)
        raise ValueError("not transient")

    with pytest.raises(ValueError):
        with_retry(typo, attempts=5, retry_on=(OSError,), sleep=delays.append)
    assert len(calls) == 1 and delays == []


def test_retry_validates_attempts():
    with pytest.raises(ValueError):
        with_retry(lambda: 1, attempts=0)


# ---------------------------------------------------------------------------
# FaultPlan: declarative grammar + lowering
# ---------------------------------------------------------------------------

def test_plan_builders_validate():
    plan = FaultPlan(N, 6)
    with pytest.raises(ValueError):
        plan.crash(7, at=0)                     # node out of range
    with pytest.raises(ValueError):
        plan.crash(0, at=6)                     # round out of range
    with pytest.raises(ValueError):
        plan.crash(0, at=3, rejoin=3)           # rejoin must be later
    with pytest.raises(ValueError):
        plan.straggle(0, at=1, rounds=0)
    with pytest.raises(ValueError):
        FaultPlan(0, 6)
    with pytest.raises(ValueError):
        FaultPlan(N, 6, events=(FaultEvent("meteor", 0, 0),))


def test_plan_builders_are_pure():
    base = FaultPlan(N, 6)
    withcrash = base.crash(1, at=2)
    assert base.events == () and len(withcrash.events) == 1


def test_plan_lowering_windows():
    plan = (FaultPlan(N, 6, seed=5)
            .crash(1, at=1, rejoin=3)     # out rounds 1-2, back at 3
            .straggle(3, at=2, rounds=2)  # out rounds 2-3
            .drop(0, at=4)                # out round 4 only
            .corrupt(2, at=5)
            .preempt(at=3))
    low = plan.lower(corrupt_in_graph=True)
    want_active = np.ones((6, N), bool)
    want_active[1:3, 1] = False
    want_active[2:4, 3] = False
    want_active[4, 0] = False
    np.testing.assert_array_equal(low.active, want_active)
    want_corrupt = np.zeros((6, N), bool)
    want_corrupt[5, 2] = True
    np.testing.assert_array_equal(low.corrupt, want_corrupt)
    # rejoin = first active round after an absence
    assert low.rejoin[3, 1] and low.rejoin[4, 3] and low.rejoin[5, 0]
    assert low.rejoin.sum() == 3
    np.testing.assert_array_equal(low.preempt,
                                  np.arange(6) == 3)
    # without in-graph support, corruption lowers to a drop
    low2 = plan.lower(corrupt_in_graph=False)
    assert not low2.corrupt.any()
    assert not low2.active[5, 2]


def test_crash_without_rejoin_is_permanent():
    low = FaultPlan(N, 5).crash(2, at=1).lower()
    np.testing.assert_array_equal(low.active[:, 2],
                                  [True, False, False, False, False])


# ---------------------------------------------------------------------------
# checksum + deterministic bit flips
# ---------------------------------------------------------------------------

def _payload(seed=0, d=32):
    rng = np.random.default_rng(seed)
    return {"a": jnp.asarray(rng.normal(0, 1, (N, d)), jnp.float32),
            "b": jnp.asarray(rng.normal(0, 1, (N, 2, d)), jnp.float32),
            "none": None}


def test_checksum_localizes_a_single_bit_flip():
    payload = _payload()
    before = np.asarray(comms.payload_checksum(payload))
    raw = np.asarray(payload["b"]).copy()
    raw_bits = raw.view(np.uint32)
    raw_bits[2, 1, 7] ^= np.uint32(1) << 3       # one bit, node 2
    after = np.asarray(comms.payload_checksum(
        dict(payload, b=jnp.asarray(raw))))
    changed = before != after
    np.testing.assert_array_equal(changed, [False, False, True, False])


def test_flip_payload_bits_is_targeted_and_deterministic():
    payload = _payload()
    corrupt = jnp.asarray([False, True, False, True])
    key = plan_key(seed=9, round_index=4)
    out1 = flip_payload_bits(payload, corrupt, key)
    out2 = flip_payload_bits(payload, corrupt, key)
    for leaf_name in ("a", "b"):
        x, y = np.asarray(payload[leaf_name]), np.asarray(out1[leaf_name])
        np.testing.assert_array_equal(x[0], y[0])       # clean rows intact
        np.testing.assert_array_equal(x[2], y[2])
        assert (x[1] != y[1]).any() and (x[3] != y[3]).any()
        assert np.isfinite(y).all()                     # mantissa-only flips
        np.testing.assert_array_equal(y, np.asarray(out2[leaf_name]))
    assert out1["none"] is None
    # every injected flip is caught by the checksum
    ok = np.asarray(comms.payload_checksum(payload)) == np.asarray(
        comms.payload_checksum(out1))
    np.testing.assert_array_equal(ok, ~np.asarray(corrupt))


def test_idle_signals_flip_nothing():
    payload = _payload()
    sig = idle_signals(N)
    out = flip_payload_bits(payload, sig.corrupt, sig.key)
    for name in ("a", "b"):
        np.testing.assert_array_equal(np.asarray(payload[name]),
                                      np.asarray(out[name]))


# ---------------------------------------------------------------------------
# checkpoint durability (atomic write + clear corruption errors + retry)
# ---------------------------------------------------------------------------

def _tree():
    return {"x": jnp.arange(12, dtype=jnp.float32).reshape(3, 4)}


def test_failed_save_never_tears_the_previous_checkpoint(tmp_path,
                                                         monkeypatch):
    path = str(tmp_path / "ckpt.msgpack")
    save_pytree(path, _tree(), metadata={"v": 1})

    def broken_replace(src, dst):
        raise OSError("simulated rename failure")

    monkeypatch.setattr(ckpt_io.os, "replace", broken_replace)
    with pytest.raises(RetryError):
        save_pytree(path, {"x": jnp.zeros((3, 4))}, metadata={"v": 2})
    monkeypatch.undo()
    # old checkpoint intact, no temp-file litter
    assert ckpt_io.load_metadata(path) == {"v": 1}
    np.testing.assert_array_equal(
        np.asarray(load_pytree(path, _tree())["x"]),
        np.asarray(_tree()["x"]))
    assert [p.name for p in tmp_path.iterdir()] == ["ckpt.msgpack"]


def test_transient_save_failure_is_retried(tmp_path, monkeypatch):
    path = str(tmp_path / "ckpt.msgpack")
    real_replace = ckpt_io.os.replace
    fails = {"left": 2}

    def flaky_replace(src, dst):
        if fails["left"] > 0:
            fails["left"] -= 1
            raise OSError("transient")
        return real_replace(src, dst)

    monkeypatch.setattr(ckpt_io.os, "replace", flaky_replace)
    save_pytree(path, _tree(), metadata={"v": 3})
    assert fails["left"] == 0
    assert ckpt_io.load_metadata(path) == {"v": 3}


def test_truncated_checkpoint_raises_clear_error(tmp_path):
    path = str(tmp_path / "ckpt.msgpack")
    save_pytree(path, _tree())
    blob = open(path, "rb").read()
    open(path, "wb").write(blob[:len(blob) // 2])
    with pytest.raises(ValueError, match="corrupt or truncated"):
        load_pytree(path, _tree())
    with pytest.raises(ValueError, match="corrupt or truncated"):
        ckpt_io.load_metadata(path)


def test_non_checkpoint_msgpack_raises_clear_error(tmp_path):
    path = str(tmp_path / "notckpt.msgpack")
    open(path, "wb").write(msgpack.packb([1, 2, 3]))
    with pytest.raises(ValueError, match="corrupt or truncated"):
        load_pytree(path, _tree())


def test_missing_checkpoint_raises_file_not_found(tmp_path):
    with pytest.raises(FileNotFoundError):
        load_pytree(str(tmp_path / "nope.msgpack"), _tree())


# ---------------------------------------------------------------------------
# fault trajectories match the numpy oracle (f32 engine, every plan kind)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("merge,topology", [
    ("fedavg", "full"), ("fedavg", "ring"),
    ("fisher", "full"), ("fisher", "ring"),
    ("gradmatch", "full"),
])
def test_fault_trajectory_matches_oracle(merge, topology):
    """crash+rejoin / straggle / drop against the float64 oracle: the full
    committed-params trajectory, every round, ≤2e-5."""
    plan = (FaultPlan(N, 7)
            .crash(1, at=1, rejoin=3)
            .straggle(3, at=4, rounds=1)
            .drop(0, at=5))
    cfg = _cfg(merge=merge, topology=topology)
    sess = _session(cfg)
    targets = _targets()
    batches = jnp.broadcast_to(targets, (cfg.sync_every, N, 8))
    traj = []
    _, logs = run_plan(sess, plan, batches, jnp.zeros((N, 1)),
                       on_round=lambda r, lg: traj.append(
                           np.asarray(sess.state.params["x"]).copy()))
    assert all(not lg["gates"][~lg["active"]].any() for lg in logs)
    want = oracle.simulate(
        np.zeros((N, 8)), np.asarray(targets), plan.lower().active,
        merge=merge, topology=topology, lr=0.1,
        steps_per_round=cfg.sync_every, data_sizes=[1.0, 2.0, 3.0, 4.0],
        fisher_decay=cfg.fisher_decay)
    assert len(traj) == plan.n_rounds
    for r, (got, exp) in enumerate(zip(traj, want)):
        np.testing.assert_allclose(got, exp, atol=2e-5,
                                   err_msg=f"round {r} diverged from oracle")


@pytest.mark.parametrize("wire", ["f32", "int8"])
def test_quorum_holds_locals_engine_and_recovers(wire):
    """Sub-quorum membership: local training continues, every gate closes,
    nobody commits; the first round back at quorum merges again. The int8
    wire runs the quantized commit: closed gates keep exact locals there
    too, and each node's constant row quantizes exactly, so the merge
    lands on the oracle at the f32 tolerance."""
    wire_kw = {} if wire == "f32" else dict(wire_dtype="int8",
                                            wire_block=128)
    cfg = _cfg(quorum=3, sync_every=1, **wire_kw)
    sess = _session(cfg, train_step=_id_step,
                    params={"x": _targets()}, stacked=True)
    batches = jnp.zeros((1, N, 8))
    val = jnp.zeros((N, 1))
    x0 = np.asarray(sess.state.params["x"]).copy()
    sess.set_active([True, True, False, False])      # 2 < quorum
    out = sess.round(batches, val)
    assert not bool(out["quorum_ok"])
    assert not np.asarray(out["gates"]).any()
    np.testing.assert_array_equal(np.asarray(sess.state.params["x"]), x0)
    sess.join(2)                                     # 3 == quorum
    out = sess.round(batches, val)
    assert bool(out["quorum_ok"])
    np.testing.assert_array_equal(np.asarray(out["gates"]),
                                  [True, True, True, False])
    want = oracle.commit(x0, oracle.merge_candidate(
        x0, [1, 1, 1, 0], merge="fedavg", topology="full",
        data_sizes=[1.0, 2.0, 3.0, 4.0]), [1, 1, 1, 0], quorum=3)
    np.testing.assert_allclose(np.asarray(sess.state.params["x"]), want,
                               atol=2e-6)


def test_quorum_rejects_unsatisfiable_config():
    with pytest.raises(ValueError, match="quorum"):
        _session(_cfg(quorum=N + 1))


# ---------------------------------------------------------------------------
# int8 EF wire: corrupt quarantine + crash→rejoin settled parity
# ---------------------------------------------------------------------------

def _settled_int8_state(merge, topology, *, plan=None, rounds=6, x0=None):
    """Phase 1 of the two-phase settle idiom: reject-gate rounds
    (val_threshold 1.5 > any relative metric) freeze the params while the
    EF wire telescopes onto them — optionally under a fault plan."""
    cfg = _cfg(merge=merge, topology=topology, sync_every=1,
               val_threshold=1.5, wire_dtype="int8", wire_block=128)
    rng = np.random.default_rng(11)
    x0 = (rng.normal(0, 1, (N, 128)).astype(np.float32)
          if x0 is None else np.asarray(x0))
    sess = _session(cfg, train_step=_id_step, params={"x": jnp.asarray(x0)},
                    stacked=True)
    batches = jnp.zeros((1, N, 8))
    val = jnp.zeros((N, 1))
    if plan is not None:
        sess, logs = run_plan(sess, plan, batches, val)
        assert not any(lg["gates"].any() for lg in logs)
    else:
        for _ in range(rounds):
            out = sess.round(batches, val)
            assert not np.asarray(out["gates"]).any()
    state = sess.state
    np.testing.assert_array_equal(np.asarray(state.params["x"]), x0)
    return cfg, state, x0                            # params never moved


@pytest.mark.parametrize("merge,topology", [("fedavg", "full"),
                                            ("fisher", "ring")])
def test_int8_crash_rejoin_settled_parity(merge, topology):
    """crash → rejoin (EF quarantine) on the quantized wire: after the
    residual re-settles, one accepting round commits ≤1e-5 of the numpy
    oracle — the rejoined node's stale reference never poisons the merge."""
    plan = FaultPlan(N, 8).crash(1, at=1, rejoin=2)   # 6 settle rounds after
    cfg, state, x0 = _settled_int8_state(merge, topology, plan=plan)
    accept = _session(dataclasses.replace(cfg, val_threshold=0.0),
                      train_step=_id_step, params={"x": jnp.zeros((N, 128))},
                      stacked=True)
    accept.load_state(state)
    out = accept.round(jnp.zeros((1, N, 8)), jnp.zeros((N, 1)))
    assert np.asarray(out["gates"]).all()
    want = oracle.commit(x0, oracle.merge_candidate(
        x0, np.ones(N, bool), merge=merge, topology=topology,
        data_sizes=[1.0, 2.0, 3.0, 4.0]), np.ones(N, bool))
    np.testing.assert_allclose(np.asarray(accept.state.params["x"]), want,
                               atol=1e-5)


def test_corrupt_wire_quarantines_sender_and_matches_oracle():
    """An injected bit flip is detected (wire_ok), the sender is excluded
    from the merge AND keeps its own locals, and the survivors' commit
    matches the oracle merge over the clean membership ≤1e-5."""
    cfg, state, x0 = _settled_int8_state("fedavg", "full", rounds=6)
    accept = _session(dataclasses.replace(cfg, val_threshold=0.0),
                      train_step=_id_step, params={"x": jnp.zeros((N, 128))},
                      stacked=True)
    accept.load_state(state)
    faults = FaultSignals(corrupt=jnp.asarray([False, False, True, False]),
                          key=plan_key(seed=7, round_index=0))
    out = accept.round(jnp.zeros((1, N, 8)), jnp.zeros((N, 1)), faults=faults)
    np.testing.assert_array_equal(np.asarray(out["wire_ok"]),
                                  [True, True, False, True])
    np.testing.assert_array_equal(np.asarray(out["gates"]),
                                  [True, True, False, True])
    got = np.asarray(accept.state.params["x"])
    clean = np.asarray([True, True, False, True])
    want = oracle.commit(x0, oracle.merge_candidate(
        x0, clean, merge="fedavg", topology="full",
        data_sizes=[1.0, 2.0, 3.0, 4.0]), clean)
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_array_equal(got[2], x0[2])     # sender kept locals


def test_faults_rejected_off_the_wire_path():
    sess = _session(_cfg())                          # f32: no wire state
    sig = idle_signals(N)
    with pytest.raises(ValueError, match="corrupt-wire injection"):
        sess.round(jnp.zeros((2, N, 8)), jnp.zeros((N, 1)), faults=sig)


# ---------------------------------------------------------------------------
# zero retraces + preempt bit-identity
# ---------------------------------------------------------------------------

def test_whole_plan_replays_against_one_compiled_round():
    """crash, straggle, drop, AND corrupt across 8 rounds: the trace count
    after round 0 never moves again — every fault is runtime data."""
    traces = []

    def counting_step(p, o, b, s):
        traces.append(1)
        return _id_step(p, o, b, s)

    cfg = _cfg(sync_every=1, val_threshold=1.5, wire_dtype="int8",
               wire_block=128, quorum=2)
    sess = _session(cfg, train_step=counting_step,
                    params={"x": _targets(128)}, stacked=True)
    batches = jnp.zeros((1, N, 8))
    val = jnp.zeros((N, 1))
    sess.round(batches, val, faults=idle_signals(N))  # compile once
    warm = len(traces)
    plan = (FaultPlan(N, 8, seed=1)
            .crash(1, at=1, rejoin=3)
            .straggle(3, at=2, rounds=2)
            .drop(0, at=5)
            .corrupt(2, at=6))
    run_plan(sess, plan, batches, val)
    assert len(traces) == warm, "a fault event retraced the round"


def test_preempt_restore_is_bit_identical(tmp_path):
    """preempt-and-restore mid-plan (save → fresh session → load) == the
    uninterrupted twin, bit for bit — params, EF wire, rng, counters."""
    def make(cfg):
        return lambda: _session(cfg, params={"x": jnp.zeros((N, 128))},
                                stacked=True)

    def run(with_preempt):
        cfg = _cfg(sync_every=1, wire_dtype="int8", wire_block=128)
        plan = FaultPlan(N, 6).crash(2, at=1, rejoin=4)
        if with_preempt:
            plan = plan.preempt(at=3)
        sess = make(cfg)()
        targets = _targets(128)
        batches = jnp.broadcast_to(targets, (1, N, 128))
        sess, logs = run_plan(sess, plan, batches, jnp.zeros((N, 1)),
                              make_session=make(cfg),
                              checkpoint_path=str(tmp_path / "preempt.msgpack"))
        return sess.state, logs

    a, logs_a = run(with_preempt=True)
    b, logs_b = run(with_preempt=False)
    assert any(lg["preempted"] for lg in logs_a)
    np.testing.assert_array_equal(np.asarray(a.params["x"]),
                                  np.asarray(b.params["x"]))
    np.testing.assert_array_equal(np.asarray(a.wire["x"]),
                                  np.asarray(b.wire["x"]))
    np.testing.assert_array_equal(np.asarray(a.rng), np.asarray(b.rng))
    assert int(a.round) == int(b.round) and int(a.step) == int(b.step)
    for la, lb in zip(logs_a, logs_b):
        np.testing.assert_array_equal(la["gates"], lb["gates"])


def test_run_plan_requires_preempt_plumbing():
    sess = _session(_cfg())
    plan = FaultPlan(N, 3).preempt(at=1)
    with pytest.raises(ValueError, match="preempt"):
        run_plan(sess, plan, jnp.zeros((2, N, 8)), jnp.zeros((N, 1)))


def test_run_plan_checks_node_count():
    sess = _session(_cfg())
    with pytest.raises(ValueError, match="nodes"):
        run_plan(sess, FaultPlan(N + 1, 3), jnp.zeros((2, N, 8)),
                 jnp.zeros((N, 1)))
