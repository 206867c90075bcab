"""`swarmbench.stages`: device time per round stage on intervals laid out
by hand, the program's host spans in the traced slice, and one run of a
cell at a CPU size with the program's recorder on."""
import json
from pathlib import Path

import jax
import pytest

from repro import tracing

from swarmbench import run, stages, trace
from swarmbench.clock import SetupClock

ROOT = Path(__file__).resolve().parents[2]
SCOPES = {"while.1": "swarm.local_steps", "fusion.2": "swarm.local_steps",
          "fusion.3": "swarm.gate", "fused_merge_all.4": "swarm.commit"}


def test_scope_seconds_counts_a_loop_and_its_body_once():
    tr = trace.Trace(ops={
        # a while spanning its body, an op of no scope inside it and one
        # outside every scope, a gate op cut by the slice's end
        0: [(0, 50, "while.1"), (5, 20, "fusion.2"), (20, 30, "copy.9"),
            (50, 60, "copy.9"), (60, 70, "fused_merge_all.4"),
            (70, 130, "fusion.3")],
        # the second device runs the same ops in less time
        1: [(0, 30, "while.1"), (30, 40, "fused_merge_all.4"),
            (40, 60, "fusion.3"), (80, 90, "copy.9")],
    })
    got = stages.scope_seconds(tr, SCOPES, 0, 100)
    assert got == pytest.approx({
        "swarm.local_steps": (50 + 30) / 2 * 1e-9,
        "swarm.commit": (10 + 10) / 2 * 1e-9,
        "swarm.gate": (30 + 20) / 2 * 1e-9,
        stages.OTHER: (10 + 10) / 2 * 1e-9})
    busy = trace.summarize(trace.Trace(ops=tr.ops, spans=[(0, 100, "x")]))
    assert sum(got.values()) == pytest.approx(busy["busy_s"])


def test_host_spans_keeps_the_rounds_of_the_slice():
    sp = tracing.Span
    spans = [sp("session.build", None, None, 0, 40),
             sp("round", 0, None, 50, 60, 4_000_000),
             sp("round", 1, None, 100, 180, 1_000_000),
             sp("round", 2, None, 200, 240, 3_000_000),
             sp("round", 3, None, 290, 310, 9_000_000)]
    got = stages.host_spans(spans, 100, 300)
    assert got == pytest.approx({"round_spans": 2, "setup_session_s": 40e-9,
                                 "round_host_cpu_ms": 2.0,
                                 "round_host_wall_ms": 60e-6})
    assert stages.host_spans([], 0, 1) == {"round_spans": 0,
                                           "setup_session_s": None}


def _tiny(mix):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec = next(w for w in bench["workloads"] if w["traffic"] == mix)
    config = json.loads(
        (ROOT / "swarmbench/configs/histo_densenet_paper.json").read_text())
    config.update(image_size=16, stem=8, growth=4, n_blocks=2,
                  layers_per_block=1, feat_dim=16, hidden=8)
    config["data"]["n_train"] = 128
    traffic = json.loads(
        (ROOT / f"swarmbench/traffic/{mix}.json").read_text())
    traffic.update(batch=8, pool_rounds=3, in_flight=2)
    return spec, config, traffic


def test_a_cpu_run_records_the_programs_spans(monkeypatch, tmp_path):
    """The program's ``session.build`` and ``round`` spans and the set-up
    count come through a whole run; a CPU trace holds no TPU plane, so no
    device stage is reported."""
    # a traced slice long enough for whole rounds on a loaded CPU
    monkeypatch.setattr(run, "TRACE_S", 1.5)
    spec, config, traffic = _tiny("fedavg_s5")
    tracing.drain()
    tracing.enable()
    try:
        out = stages.measure(spec, config, traffic, seed=2**33 + 1,
                             seconds=2.0, clock=SetupClock(),
                             trace_dir=str(tmp_path))
    finally:
        tracing.disable()
        tracing.drain()
    assert out["setup_programs"] > 0 and out["window_programs"] == 0
    assert out["setup_session_s"] > 0
    assert out["round_spans"] >= 1 and out["traced_rounds"] >= 1
    assert out["round_host_cpu_ms"] > 0
    assert "busy_ms" not in out and "local_step_ms" not in out
    assert jax.devices()[0].platform == "cpu"
