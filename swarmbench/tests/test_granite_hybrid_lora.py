"""The LM LoRA swarm cell's family, reference and check at a size a CPU
test run can hold: the cell's own configuration file and traffic cut to
d_model 64, a 3-layer pattern (mamba, attention, mamba) and 48-token
reports, under the cell's own limits. The weights are float32 here: at
the backend optimization level 0 that conftest.py sets, XLA's CPU backend
returns a zero gradient for the bfloat16 program's query adapters (at the
default level it agrees with the reference as on the chip).

- The reference draws the program's base and adapters bit for bit.
- A sound run of the harness is correct.
- With the timed path broken underneath (a local step that returns its
  state unchanged, or one that trains on half of each report), a whole
  run of the harness, all but its look for a chip, comes out not correct.
- With the gate broken (every site scored on site 0's validation report,
  the accuracy's labels one token off, or the decision inverted), a whole
  run comes out not correct, by flipped gates.
- The control, the reference with float8 operands put in the program's
  place, fails the limits.
- The benchmark's operation count stays under XLA's.
"""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine
from repro.experiments import lm_swarm
from swarmbench import calibrate, check, run
from swarmbench.clock import SetupClock
from swarmbench.families import granite_hybrid_lora as family
from swarmbench import reference_hybrid

ROOT = Path(__file__).resolve().parents[2]
CELL = "granite4_h_micro_lora.fedavg_seq4k_s2"
TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
            shared_intermediate_size=96, intermediate_size=96,
            vocab_size=512, mamba_d_state=16, mamba_n_heads=8,
            mamba_d_head=16, mamba_chunk_size=16, num_hidden_layers=3,
            layer_types=["mamba", "attention", "mamba"], dtype="float32")


def _tiny():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec = next(w for w in bench["workloads"] if w["name"] == CELL)
    config = json.loads(
        (ROOT / "swarmbench/configs/granite4_h_micro_lora.json").read_text())
    config.update(TINY)
    config["data"]["n_train"] = 16
    traffic = json.loads(
        (ROOT / "swarmbench/traffic/fedavg_seq4k_s2.json").read_text())
    traffic.update(seq_len=48, pool_rounds=3)
    limits = json.loads((ROOT / f"swarmbench/limits/{CELL}.json").read_text())
    return bench, spec, config, traffic, limits


def _run(seed=2**32 + 11):
    bench, spec, config, traffic, limits = _tiny()
    return run.run(bench, spec, config, traffic, limits, seed=seed,
                   seconds=0.5, trace=False, devices=jax.devices()[:1],
                   clock=SetupClock())


def test_the_reference_draws_the_programs_weights():
    """In the cell's bfloat16, to float32's last bit: the program draws
    under jit, the reference op by op, and XLA may fuse a float32 draw or
    ``A_log`` otherwise (rarely, a last-bit difference before the rounding
    to bfloat16 moves that bfloat16's last bit too)."""
    _, _, config, _, _ = _tiny()
    config["dtype"] = "bfloat16"
    base, adapters = lm_swarm.init_base_and_adapters(
        family.model_config(config), jax.random.key(1234))
    ref_base, ref_adapters = reference_hybrid.init(config, 1234)
    assert list(adapters) == list(ref_adapters)
    for k in adapters:
        np.testing.assert_allclose(np.asarray(adapters[k]),
                                   np.asarray(ref_adapters[k]), rtol=1e-6,
                                   err_msg=k)
    np.testing.assert_allclose(
        np.asarray(base["embed_tied"]["table"], np.float32),
        np.asarray(ref_base["embed"], np.float32), rtol=1e-6)
    for run_p, ref_p in zip(base["layers"], ref_base["layers"]):
        scales = {jax.tree_util.keystr(p) for p, _ in
                  jax.tree_util.tree_flatten_with_path(run_p)[0]
                  if "lora_scale" in jax.tree_util.keystr(p)}
        assert scales
        mine = {jax.tree_util.keystr(p): a for p, a in
                jax.tree_util.tree_flatten_with_path(run_p)[0]
                if "lora_scale" not in jax.tree_util.keystr(p)}
        theirs = {jax.tree_util.keystr(p): a for p, a in
                  jax.tree_util.tree_flatten_with_path(ref_p)[0]}
        assert mine.keys() == theirs.keys()
        for k in mine:
            np.testing.assert_allclose(np.asarray(mine[k], np.float32),
                                       np.asarray(theirs[k], np.float32),
                                       rtol=1e-6, err_msg=k)


def test_a_sound_run_is_correct():
    out = _run()
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "checks"


MAKE_FNS = lm_swarm.make_fns


def _unchanged(model_cfg, train_cfg):
    step, ev = MAKE_FNS(model_cfg, train_cfg)
    return (lambda a, o, b, s, base: (a, o, step(a, o, b, s, base)[2])), ev


def _half_batch(model_cfg, train_cfg):
    step, ev = MAKE_FNS(model_cfg, train_cfg)

    def half(a, o, b, s, base):
        tokens, labels = b
        n = tokens.shape[-1] // 2
        return step(a, o, (tokens[..., :n], labels[..., :n]), s, base)
    return half, ev


@pytest.mark.parametrize("fault", [_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_a_broken_step_is_not_correct(fault, monkeypatch):
    monkeypatch.setattr(lm_swarm, "make_fns", fault)
    out = _run()
    assert not out["correct"], out["checks"]


def _other_report(monkeypatch):
    """The gate scores every site on site 0's validation report."""
    reset = family.Cell.reset

    def site0(self, seed):
        reset(self, seed)
        self.val = jax.tree.map(lambda a: jnp.broadcast_to(a[:1], a.shape),
                                self.val)
    monkeypatch.setattr(family.Cell, "reset", site0)


def _labels_off_by_one(monkeypatch):
    """The accuracy compares each prediction with the token after next."""
    def fns(model_cfg, train_cfg):
        step, ev = MAKE_FNS(model_cfg, train_cfg)
        return step, lambda a, val, base: ev(
            a, (val[0], jnp.roll(val[1], -1, axis=-1)), base)
    monkeypatch.setattr(lm_swarm, "make_fns", fns)


def _inverted_gate(monkeypatch):
    """Each gate accepts what it should refuse."""
    decide = engine.gate_decisions
    monkeypatch.setattr(engine, "gate_decisions",
                        lambda *a, **k: ~decide(*a, **k))


@pytest.mark.parametrize("fault", [_other_report, _labels_off_by_one,
                                   _inverted_gate],
                         ids=["other_report", "labels_off_by_one",
                              "inverted_gate"])
def test_a_broken_gate_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    out = _run()
    assert not out["correct"], out["checks"]
    assert out["checks"]["gate_flips"]["value"] > 0, out["checks"]


def test_the_float8_control_fails():
    _, _, config, traffic, limits = _tiny()
    cell = family.Cell(config, traffic, 77)
    cell.free()
    ref = cell.reference(run.CHECK_ROUNDS)
    low = cell.reference(run.CHECK_ROUNDS, operands=calibrate.CONTROL)
    ok, rows = check.judge(check.numbers(low, ref), limits)
    assert not ok, rows


def test_the_forward_flops_are_the_products_xla_counts():
    """`flops_lm` against XLA's count of the program's forward, at widths
    where the products dominate (one layer of each run a scan of one, so
    XLA counts each once). XLA adds the norms, activations, the masked half
    of causal products and the SSD's scores per head, so ours is lower."""
    from repro.core.lora import attach_payload
    from repro.models.transformer import forward_lm
    from swarmbench import flops_lm

    _, _, config, _, _ = _tiny()
    config.update(hidden_size=256, shared_intermediate_size=512,
                  num_attention_heads=4, num_key_value_heads=2,
                  mamba_n_heads=8, mamba_d_head=64, mamba_d_state=64,
                  mamba_chunk_size=64)
    model = family.model_config(config)
    base, adapters = lm_swarm.init_base_and_adapters(model,
                                                     jax.random.key(0))
    s = 256
    cost = jax.jit(lambda p, t: forward_lm(p, model, t)[0]).lower(
        attach_payload(adapters, base),
        jax.numpy.zeros((1, s), jax.numpy.int32)).compile().cost_analysis()
    xla = (cost[0] if isinstance(cost, list) else cost)["flops"]
    ours = flops_lm.forward_token(config, s) * s
    assert 0.85 * xla <= ours <= xla
