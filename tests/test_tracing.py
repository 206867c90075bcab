"""`repro.tracing`: the host span recorder, off and on, and the round's
device scopes read back from a compiled round (`scope_of_ops`), with the
spans the session and `histo._swarm_session` open."""
import threading
import time

import jax
import numpy as np
import pytest

from repro import tracing
from repro.configs.base import SwarmConfig
from repro.experiments import histo


@pytest.fixture
def recorder():
    """The program's recorder, on for the test and off and empty after."""
    tracing.drain()
    tracing.enable()
    try:
        yield
    finally:
        tracing.disable()
        tracing.drain()


def test_off_records_nothing_and_returns_one_shared_object():
    assert not tracing._recorder.on
    first, second = tracing.span("round", id=1), tracing.span("x")
    assert first is second is tracing._OFF
    with first as sp:
        assert sp is None
    assert tracing.drain() == []


def test_on_records_nesting_parent_id_and_cpu_time(recorder):
    t0 = time.time_ns()
    with tracing.span("round", id=7):
        with tracing.span("inner") as inner:
            end = time.thread_time_ns() + 20_000_000
            while time.thread_time_ns() < end:   # 20 ms of this thread's CPU
                pass
        time.sleep(0.05)                          # 50 ms of wall, no CPU
    t1 = time.time_ns()
    outer, got = tracing.drain()
    assert got is inner
    assert [outer.name, inner.name] == ["round", "inner"]
    assert (outer.id, outer.parent) == (7, None)
    assert (inner.id, inner.parent) == (None, 0)
    assert t0 <= outer.start_ns <= inner.start_ns < inner.end_ns \
        <= outer.end_ns <= t1
    # the CPU clock is read inside the wall clock's readings (1 ms for the
    # two clocks' resolutions)
    assert 20_000_000 <= inner.cpu_ns <= inner.end_ns - inner.start_ns \
        + 1_000_000
    wall = outer.end_ns - outer.start_ns
    assert wall >= 70_000_000 and outer.cpu_ns < wall - 40_000_000
    assert tracing.drain() == []


def test_spans_of_another_thread_have_their_own_parents(recorder):
    def work():
        with tracing.span("worker"):
            pass

    with tracing.span("main"):
        t = threading.Thread(target=work)
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
        with tracing.span("child"):
            pass
    by_name = {sp.name: sp for sp in tracing.drain()}
    assert by_name["worker"].parent is None
    assert by_name["child"].parent == 0


def test_scope_of_ops_reads_the_innermost_round_scope():
    text = "\n".join([
        '  %while.5 = (s32[]) while(%t), body=%b, metadata={op_type="while" '
        'op_name="jit(f)/swarm.local_steps/while" stack_frame_id=3}',
        '  ROOT %tanh.0 = f32[8]{0} tanh(%p), metadata={op_name="jit(f)/'
        'swarm.propose/inner/swarm.gate/tanh"}',
        '  %copy.1 = f32[8]{0} copy(%p)',
        '  %add.2 = f32[] add(%a, %b), metadata={op_name="jit(f)/swarm.gates'
        '/add"}',
    ])
    assert tracing.scope_of_ops(text) == {"while.5": "swarm.local_steps",
                                          "tanh.0": "swarm.gate"}


def _tiny_session(wire):
    ecfg = histo.HistoExperimentConfig(
        image_size=8, growth=2, stem=4, feat_dim=8, hidden=4, n_blocks=1,
        layers_per_block=1, batch_size=2, sync_every=2, steps=4)
    swarm = SwarmConfig(n_nodes=2, sync_every=2, topology="full",
                        merge="fedavg", lora_only=False, val_threshold=0.8,
                        gate_metric="auc", wire_dtype=wire)
    step = histo._make_model_fns(ecfg)[0]
    shards = [(None, np.zeros(5)), (None, np.zeros(7))]
    return histo._swarm_session(ecfg, step, shards, swarm)


def _inputs(rounds=None):
    rng = np.random.default_rng(0)
    lead = (2, 2) if rounds is None else (rounds, 2, 2)
    xs = rng.normal(size=lead + (2, 8, 8, 3)).astype(np.float32)
    ys = rng.integers(0, 3, size=lead + (2,)).astype(np.int32)
    val = (rng.normal(size=(2, 4, 8, 8, 3)).astype(np.float32),
           rng.integers(0, 3, size=(2, 4)).astype(np.int32),
           np.ones((2, 4), bool))
    return (xs, ys), val


@pytest.mark.parametrize("wire", ["f32", "int8"])
def test_every_round_scope_names_ops_of_the_compiled_round(wire):
    session = _tiny_session(wire)
    batches, val = _inputs()
    text = session._round_jit.lower(session._state, batches, val,
                                    None).compile().as_text()
    scopes = tracing.scope_of_ops(text)
    assert set(scopes.values()) == set(tracing.ROUND_SCOPES)
    # the local steps' loop carries its scope, so its body counts once
    loops = [n for n, s in scopes.items() if n.startswith("while")]
    assert any(scopes[n] == "swarm.local_steps" for n in loops)


def test_the_session_opens_round_spans_with_host_ids(recorder):
    session = _tiny_session("f32")
    batches, val = _inputs()
    session.round(batches, val)
    session.round(batches, val)
    many, _ = _inputs(rounds=3)
    session.run_rounds(many, val)
    session.run_local((many[0][0], many[1][0]))
    jax.block_until_ready(session.state.params)
    spans = tracing.drain()
    assert spans[0].name == "session.build"
    assert spans[0].end_ns > spans[0].start_ns
    rounds = [sp for sp in spans if sp.name == "round"]
    assert [sp.id for sp in rounds] == [0, 1, 2, 5]
    assert all(sp.parent is None and sp.cpu_ns > 0 for sp in rounds)
    assert [sp.name for sp in spans] == ["session.build"] + ["round"] * 4
