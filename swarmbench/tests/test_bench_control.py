"""The correctness check's control and faults, at a size a CPU test run can
hold (the cell's own widths and data cut down; the cells' own limits).

- A sound run of the harness passes.
- The control, the reference with float8 operands put in the program's
  place, fails the limits. It runs at the paper's widths (32 px images,
  one dense layer per encoder module): at much smaller widths its error in
  the gradients stays under the limits.
- With the timed path broken underneath (the program's local step swapped
  for one that returns its state unchanged, or one that trains on half of
  each batch), a whole run of the harness, all but its look for a chip,
  comes out not correct.
"""
import json
from pathlib import Path

import jax
import pytest

from swarmbench import check, run
from swarmbench.clock import SetupClock
from swarmbench.families import histo_densenet

ROOT = Path(__file__).resolve().parents[2]
MIXES = ["fedavg_s5", "ring_int8_s1"]


# the control's size: the paper's widths, cut in image size and depth
CONTROL = dict(image_size=32, stem=64, growth=32, n_blocks=4,
               layers_per_block=1, feat_dim=1152, hidden=512)


def _tiny(mix, **sizes):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec = next(w for w in bench["workloads"] if w["traffic"] == mix)
    config = json.loads(
        (ROOT / "swarmbench/configs/histo_densenet_paper.json").read_text())
    config.update(sizes or dict(image_size=16, stem=8, growth=4, n_blocks=2,
                                layers_per_block=1, feat_dim=16, hidden=8))
    config["data"]["n_train"] = 128
    traffic = json.loads(
        (ROOT / f"swarmbench/traffic/{mix}.json").read_text())
    traffic.update(batch=8, pool_rounds=3)
    limits = json.loads(
        (ROOT / f"swarmbench/limits/{spec['name']}.json").read_text())
    return bench, spec, config, traffic, limits


def _run(mix, seed=2**32 + 5):
    bench, spec, config, traffic, limits = _tiny(mix)
    return run.run(bench, spec, config, traffic, limits, seed=seed,
                   seconds=0.5, trace=False, devices=jax.devices()[:1],
                   clock=SetupClock())


def _unchanged(ecfg):
    step = histo_densenet.histo._make_model_fns(ecfg)[0]
    return lambda p, o, b, s: (p, o, step(p, o, b, s)[2])


def _half_batch(ecfg):
    step = histo_densenet.histo._make_model_fns(ecfg)[0]

    def half(p, o, b, s):
        x, y = b
        return step(p, o, (x[:x.shape[0] // 2], y[:y.shape[0] // 2]), s)
    return half


def test_a_traced_run_traces_a_slice_and_goes_on(monkeypatch, tmp_path):
    """The profiler records the window's last ``TRACE_S`` seconds only; the
    run is correct and leaves no trace behind."""
    from swarmbench import flops

    monkeypatch.setattr(run, "TRACE_S", 0.2)
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    # round_mfu needs the chip's peaks; any will do for the slice's logic
    monkeypatch.setitem(flops.PEAKS, jax.devices()[0].device_kind,
                        flops.PEAKS["TPU v5 lite"])
    bench, spec, config, traffic, limits = _tiny("ring_int8_s1")
    out = run.run(bench, spec, config, traffic, limits, seed=2**32 + 7,
                  seconds=1.0, trace=True, devices=jax.devices()[:1],
                  clock=SetupClock())
    assert out["correct"], out["checks"]
    # a CPU trace holds no TPU plane: no device numbers, nothing invented
    assert "busy_s" not in out["device"] and "breakdown" not in out
    assert set(out["metrics"]) == {"host_round_ms", "window_compiles",
                                   "round_mfu"}
    assert list(tmp_path.iterdir()) == []


@pytest.mark.parametrize("mix", MIXES)
def test_a_sound_run_is_correct(mix):
    out = _run(mix)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", [_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch"])
@pytest.mark.parametrize("mix", MIXES)
def test_a_broken_step_is_not_correct(mix, fault, monkeypatch):
    monkeypatch.setattr(histo_densenet, "program_train_step", fault)
    out = _run(mix)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("band", [0.0, 2.0])
def test_the_reference_follows_gates_only_in_a_tie(band):
    _, _, config, traffic, _ = _tiny("ring_int8_s1")
    cell = histo_densenet.Cell(config, traffic, 2**40 + 3)
    cell.free()
    own = cell.reference(2)
    flipped = ~own["gates"]
    ref = cell.reference(2, follow=flipped, band=band)
    # a band of 2 holds every gate (AUCs lie in [0, 1]); a band of 0 none
    want = flipped if band else own["gates"]
    assert (ref["gates"] == want).all()
    assert ref["followed"] == (flipped.size if band else 0)
    assert check.numbers(dict(own, gates=flipped), own)["gate_flips"] == \
        flipped.size


@pytest.mark.parametrize("mix", MIXES)
def test_the_bfloat16_control_fails(mix):
    """The control the limits were set against on the chip: the reference
    with float8 operands (PERF.md section 2 says why not bfloat16)."""
    from swarmbench import calibrate

    _, _, config, traffic, limits = _tiny(mix, **CONTROL)
    cell = histo_densenet.Cell(config, traffic, 77)
    cell.free()
    ref = cell.reference(run.CHECK_ROUNDS)
    low = cell.reference(run.CHECK_ROUNDS, operands=calibrate.CONTROL)
    ok, rows = check.judge(check.numbers(low, ref), limits)
    assert not ok, rows
