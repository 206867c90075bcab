#!/usr/bin/env python3
"""Chip smoke test: the paper's histopathology swarm round on a TPU.

Drives the main path of `repro.experiments.histo` — its swarm training loop
on `SwarmSession(backend="engine")`: four sites vmapped on one chip, the
in-graph AUC gate and the fused Pallas commit — at the paper's width
(`configs/paper_histo.PAPER_FULL`: 224 px, stem 64, growth 32, 4x4 dense
layers, 1152 features, hidden 512), batch 32 per site, the 10/30/30/30
split and a sync every 5 steps. Weights and data are random from ``--seed``.

Phases on one chip (the default):

  swarm_fedavg  the paper's swarm config (full topology, fedavg, f32 wire,
                AUC gate), 3 rounds; commit kernel `fused_merge_all`
  swarm_int8    one round on the int8 error-feedback wire; commit kernel
                `fused_quant_merge_all`
  swarm_fisher  one round of fisher merging; commit kernel
                `fused_merge_all` with per-element importance

After each phase its commit kernel runs once more on the phase's final
state, beside the plain XLA form of the same commit (the engine's merge
candidate, gate-selected), and the largest difference is checked.

``--four-chips`` runs only the four-chip phase: one site per chip on a
``("node",)`` mesh, `backend="gossip"`, for the paper's config and a
ring/int8 config, each compared with the engine backend on the first chip
from the same seed and batches (per-round gates and losses, trained
params, and one commit from the same inputs). Both backends run at HIGHEST
matmul precision, so the comparison sees the mesh path's arithmetic and
not the TPU's default bf16-pass rounding, which one site per chip and four
vmapped sites incur differently. To pay for HIGHEST's compile time the
phase cuts the network's depth to one dense layer in each of the four
encoder modules; every width stays the paper's.

Every phase prints its name and PASS/FAIL with its per-round loss and
gates, commit errors, set-up (trace + compile) seconds and peak device
bytes; the run ends with its persistent compile-cache hits and misses. The last line of stdout is ``{"ok": true, "device": {...}}``,
printed only when every phase passed on a TPU; otherwise the exit code is
non-zero. It prints no rates.

Usage:  python chip_smoke.py [--four-chips] [--seed N]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.base import SwarmConfig  # noqa: E402
from repro.configs.paper_histo import PAPER_FULL  # noqa: E402
from repro.core import comms  # noqa: E402
from repro.core.engine import gated_commit, host_commit  # noqa: E402
from repro.data import (make_histo_dataset, paper_splits,  # noqa: E402
                        shard_to_nodes)
from repro.experiments.histo import (HistoExperimentConfig,  # noqa: E402
                                     _make_model_fns, _stack_vals,
                                     _swarm_session, _train_loop)
from repro.kernels.fused_merge import fused_quant_merge_tree  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_swarm_mesh  # noqa: E402

SYNC_EVERY = 5
PAPER_SWARM = SwarmConfig(n_nodes=4, sync_every=SYNC_EVERY, topology="full",
                          merge="fedavg", lora_only=False, val_threshold=0.8,
                          gate_metric="auc")
# one gate rejected, so the check covers both sides of the select
CHECK_GATES = (True, False, True, True)
# f32 commit: kernel vs XLA, |got - want| / (1 + |want|)
COMMIT_TOL = 2e-5
# four chips vs one, both backends at HIGHEST matmul precision, by wire:
# (per-round mean loss |Δ| / (1 + |loss|), trained params ||Δ||₂ / ||θ||₂).
# f32: summation order alone leaves <= 3e-7 / 7.5e-5 on the CPU, where
# matmuls are exact f32; the TPU's default precision left 6.9e-4 in the
# loss. int8: the backends' error-feedback wires agree only once settled
# (the engine merges every site's reconstruction, the mesh ring keeps its
# own site exact), which leaves <= 1.1e-4 / 2.7e-3 on the CPU.
TRAIN_TOL = {"f32": (1e-4, 1e-3), "int8": (1e-3, 1e-2)}
# the four-chip phase's depth cut (widths stay PAPER_FULL's)
FOUR_CHIP_DEPTH = dict(n_blocks=4, layers_per_block=1)
# syncs that settle an int8 wire before the backends' commits are compared
SETTLE_SYNCS = 6


def paper_config(*, rounds: int, seed: int = 0, **overrides):
    """The histo experiment at PAPER_FULL width, ``rounds`` syncs long.
    ``overrides`` shrink it for CPU rehearsals (image_size, widths, ...)."""
    width = {k: getattr(PAPER_FULL, k) for k in (
        "image_size", "growth", "stem", "feat_dim", "hidden", "n_blocks",
        "layers_per_block")}
    kw = dict(width, n_train=512, n_test=128, batch_size=32,
              sync_every=SYNC_EVERY, seed=seed, swarm=PAPER_SWARM)
    kw.update(overrides)
    kw["steps"] = rounds * kw["swarm"].sync_every
    return HistoExperimentConfig(**kw)


def make_shards(ecfg):
    """The 10/30/30/30 site shards, as `run_experiment` builds them."""
    images, labels = make_histo_dataset(
        ecfg.n_train, size=ecfg.image_size, noise=ecfg.noise,
        class_probs=ecfg.class_probs, seed=ecfg.seed)
    return shard_to_nodes(images, labels,
                          paper_splits(ecfg.n_train, ecfg.fractions),
                          seed=ecfg.seed)


class SetupClock:
    """Seconds JAX spends tracing, lowering and compiling (set-up time), and
    the persistent compile cache's hits and misses."""

    _EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
               "/jax/core/compile/jaxpr_to_mlir_module_duration",
               "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        self.cache = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        jax.monitoring.register_event_listener(self._on_count)

    def _on_event(self, name, secs, **_):
        if name in self._EVENTS:
            self.seconds += secs

    def _on_count(self, name, **_):
        kind = name.removeprefix("/jax/compilation_cache/cache_")
        if kind in self.cache:
            self.cache[kind] += 1


def tree_error(got, want):
    """(max |got - want|, max |got - want| / (1 + |want|), fraction of
    elements beyond COMMIT_TOL, ||got - want||₂ / ||want||₂) over two
    pytrees."""
    pairs = [(np.asarray(a, np.float64), np.asarray(b, np.float64))
             for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))]
    rel = [np.abs(a - b) / (1.0 + np.abs(b)) for a, b in pairs]
    norm = np.sqrt(sum(float(np.sum(b * b)) for _, b in pairs))
    diff = np.sqrt(sum(float(np.sum((a - b) ** 2)) for a, b in pairs))
    return (max(float(np.abs(a - b).max()) for a, b in pairs),
            max(float(r.max()) for r in rel),
            sum(int((r > COMMIT_TOL).sum()) for r in rel)
            / sum(r.size for r in rel),
            diff / max(norm, 1e-30))


def commit_pair(sess, gates=CHECK_GATES):
    """Run the session's fused commit kernel once more on its current state
    beside the plain XLA form of the same commit: the engine's merge
    candidate (`SwarmEngine.propose`, matmuls at full f32 precision) gate-
    selected by `gated_commit`. On the int8 wire both read the EF
    reconstruction of the same reference. Returns (kernel result, XLA
    result, whether the kernel lowered to a Mosaic custom call)."""
    eng, st = sess.engine, sess.state

    def both(params, stats, wire, active, g):
        if wire is None:
            cand, W, imp = eng.propose(params, active, stats=stats)
            got = host_commit(params, cand, W, g, sess.cfg, imp=imp,
                              block=eng.block, interpret=eng.interpret)
        else:
            eff = comms.wire_effective(params, wire, eng.wire_dtype,
                                       eng.wire_block)
            cand, W, imp = eng.propose(eff, active, stats=stats)
            got, _ = fused_quant_merge_tree(
                params, wire, W, g, imp=imp, wire_dtype=eng.wire_dtype,
                wire_block=eng.wire_block, block=eng.block,
                interpret=eng.interpret)
        return got, gated_commit(cand, params, g)

    args = (st.params, st.stats, st.wire, st.active,
            jnp.asarray(gates, bool))
    with jax.default_matmul_precision("highest"):
        lowered = jax.jit(both).lower(*args)
        got, want = lowered.compile()(*args)
    return got, want, "tpu_custom_call" in lowered.as_text()


def sync_pair(gossip, engine, val):
    """One sync — propose, in-graph gate, commit — of each backend from the
    same inputs: the gossip session's params and importance stats. An int8
    wire is first settled on those params (SETTLE_SYNCS syncs with every
    site out, so nothing commits while the error-feedback reference
    converges), the regime in which the mesh and engine wires agree. Every
    call passes a wire and a membership mask, so each backend compiles its
    sync once. Returns [(committed, gates)] for gossip, then engine."""
    st = gossip.state
    params = jax.tree.map(np.asarray, st.params)
    stats = None if st.stats is None else jax.tree.map(np.asarray, st.stats)
    nobody = np.zeros(gossip.cfg.n_nodes, bool)
    settle = SETTLE_SYNCS if gossip.cfg.wire_dtype == "int8" else 0
    out = []
    for eng in (gossip.engine, engine.engine):
        sync, wire = jax.jit(eng.sync), eng._auto_wire(params, None)
        for _ in range(settle):
            wire = sync(params, val, nobody, stats, wire)[1]["wire"]
        committed, log = sync(params, val, ~nobody, stats, wire)
        out.append((committed, np.asarray(log["gates"]).tolist()))
    return out


def _peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _report(name, ok, **fields):
    print(f"[{'PASS' if ok else 'FAIL'}] {name} "
          + " ".join(f"{k}={v}" for k, v in fields.items()), flush=True)
    return ok


def _round_lines(name, sync_log):
    for r, rec in enumerate(sync_log):
        print(f"  {name} round={r} loss={rec['loss']:.6f} "
              f"gates={[int(g) for g in rec['gates']]} "
              f"auc_local={np.round(rec['metric_local'], 4).tolist()} "
              f"auc_merged={np.round(rec['metric_merged'], 4).tolist()}",
              flush=True)


def _trained(ecfg, swarm, shards, **session_kw):
    """The histo swarm's session after `_train_loop`, and its sync log."""
    ecfg = dataclasses.replace(ecfg, swarm=swarm)
    train_step, _, _ = _make_model_fns(ecfg)
    sess = _swarm_session(ecfg, train_step, shards, swarm, **session_kw)
    _, log = _train_loop(ecfg, train_step, shards, session=sess)
    return sess, log


def swarm_phase(name, ecfg, swarm, shards, clock):
    """One main-path phase on the engine backend plus its commit check. On
    a TPU the commit kernel must have run compiled: interpret mode passes
    only on the CPU rehearsal."""
    t0 = clock.seconds
    sess, log = _trained(ecfg, swarm, shards)
    _round_lines(name, log)
    got, want, compiled = commit_pair(sess)
    abs_err, rel_err, off, _ = tree_error(got, want)
    finite = all(np.isfinite(rec["loss"]) for rec in log) and all(
        np.isfinite(np.asarray(x)).all()
        for x in jax.tree.leaves(sess.state.params))
    on_chip = (compiled and not sess.engine.interpret
               if jax.devices()[0].platform == "tpu"
               else compiled or sess.engine.interpret)
    ok = (finite and len(log) * swarm.sync_every == ecfg.steps
          and rel_err <= COMMIT_TOL and on_chip)
    return _report(
        name, ok, schedule=sess.sync_schedule.name,
        interpret=sess.engine.interpret, kernel_compiled=compiled,
        commit_max_abs_err=f"{abs_err:.3e}",
        commit_max_rel_err=f"{rel_err:.3e}",
        commit_off_fraction=f"{off:.3e}", finite=finite,
        setup_s=f"{clock.seconds - t0:.1f}",
        peak_bytes_in_use=_peak_bytes(jax.devices()[0]))


def one_chip_phases(ecfg, shards, clock):
    """The default run: fedavg (ecfg.steps long), then one round each of the
    int8 wire and fisher merging. Returns one pass/fail per phase."""
    one_round = dataclasses.replace(ecfg, steps=SYNC_EVERY)
    phases = [
        ("swarm_fedavg", ecfg, PAPER_SWARM),
        ("swarm_int8", one_round,
         dataclasses.replace(PAPER_SWARM, wire_dtype="int8")),
        ("swarm_fisher", one_round,
         dataclasses.replace(PAPER_SWARM, merge="fisher")),
    ]
    return [_guarded(name, swarm_phase, name, e, s, shards, clock)
            for name, e, s in phases]


def backend_phase(name, ecfg, swarm, shards, mesh, clock):
    """The histo swarm with one site per device (gossip backend) against the
    engine backend on the first device, same seed and batches, both at
    HIGHEST matmul precision: per-round gates must be equal, per-round
    losses and trained params within the wire's TRAIN_TOL, and one sync of
    each backend from the same params must commit the same values."""
    t0 = clock.seconds
    with jax.default_matmul_precision("highest"):
        gossip, g_log = _trained(ecfg, swarm, shards, backend="gossip",
                                 mesh=mesh, axis=mesh.axis_names[0])
        for d in mesh.devices.flat:
            print(f"  {name} after gossip device={d.id} "
                  f"memory_stats={d.memory_stats()}", flush=True)
        engine, e_log = _trained(ecfg, swarm, shards)
        val = _stack_vals([(x[:16], y[:16]) for x, y in shards])
        (g_commit, g_gates), (e_commit, e_gates) = sync_pair(gossip, engine,
                                                             val)
    logs = {"gossip": g_log, "engine": e_log}
    for backend, log in logs.items():
        _round_lines(f"{name}/{backend}", log)
    gates_equal = ([r["gates"] for r in g_log] == [r["gates"] for r in e_log])
    loss_diff = max(abs(g["loss"] - e["loss"]) / (1.0 + abs(e["loss"]))
                    for g, e in zip(g_log, e_log))
    trained_abs, _, trained_off, trained_l2 = tree_error(
        gossip.state.params, engine.state.params)
    sync_abs, sync_rel, sync_off, _ = tree_error(g_commit, e_commit)
    loss_tol, param_tol = TRAIN_TOL[swarm.wire_dtype]
    ok = (gates_equal and loss_diff <= loss_tol and trained_l2 <= param_tol
          and g_gates == e_gates and sync_rel <= COMMIT_TOL)
    for d in mesh.devices.flat:
        print(f"  {name} device={d.id} memory_stats={d.memory_stats()}",
              flush=True)
    return _report(
        name, ok, schedule=gossip.sync_schedule.name,
        round_gates_equal=gates_equal, round_loss_max_rel_diff=f"{loss_diff:.3e}",
        trained_params_rel_l2_diff=f"{trained_l2:.3e}",
        trained_params_max_abs_diff=f"{trained_abs:.3e}",
        trained_params_off_fraction=f"{trained_off:.3e}",
        sync_gates=g_gates, sync_gates_equal=g_gates == e_gates,
        sync_commit_max_abs_err=f"{sync_abs:.3e}",
        sync_commit_max_rel_err=f"{sync_rel:.3e}",
        sync_commit_off_fraction=f"{sync_off:.3e}",
        setup_s=f"{clock.seconds - t0:.1f}",
        peak_bytes_in_use=[_peak_bytes(d) for d in mesh.devices.flat])


def four_chip_phases(ecfg, shards, mesh, clock):
    """``--four-chips``: the paper's config and a ring/int8 config, each on
    the gossip backend against the engine backend."""
    phases = [
        ("gossip_fedavg", PAPER_SWARM),
        ("gossip_ring_int8",
         dataclasses.replace(PAPER_SWARM, topology="ring", wire_dtype="int8")),
    ]
    return [_guarded(name, backend_phase, name, ecfg, s, shards, mesh, clock)
            for name, s in phases]


def _guarded(name, fn, *args):
    """Run one phase; an exception fails that phase (printed) and the run
    goes on to the next, so one run reports every phase."""
    try:
        return fn(*args)
    except Exception:  # noqa: BLE001 — reported as FAIL; main exits 1
        traceback.print_exc()
        return _report(name, False, error="exception (traceback on stderr)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip gossip phase (4 devices)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    n_chips = 4 if args.four_chips else 1
    if len(jax.devices()) < n_chips:
        print(f"chip_smoke: needs {n_chips} chips, found "
              f"{len(jax.devices())}", file=sys.stderr)
        return 2
    cache_dir = Path(use_compile_cache())
    print(f"device platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())} compile_cache={cache_dir}", flush=True)
    clock = SetupClock()
    t0 = time.perf_counter()
    ecfg = paper_config(rounds=3, seed=args.seed)
    shards = make_shards(ecfg)
    print(f"data n_train={ecfg.n_train} image={ecfg.image_size} "
          f"sites={[len(y) for _, y in shards]} "
          f"make_s={time.perf_counter() - t0:.1f}", flush=True)
    if args.four_chips:
        mesh, _ = make_swarm_mesh(4)
        print(f"four-chip depth {FOUR_CHIP_DEPTH} precision=highest",
              flush=True)
        results = four_chip_phases(
            dataclasses.replace(ecfg, **FOUR_CHIP_DEPTH), shards, mesh, clock)
    else:
        results = one_chip_phases(ecfg, shards, clock)
    entries = len(list(cache_dir.glob("*"))) if cache_dir.is_dir() else 0
    print(f"wall_s={time.perf_counter() - t0:.1f} "
          f"setup_s={clock.seconds:.1f} compile_cache_entries={entries} "
          f"compile_cache_hits={clock.cache['hits']} "
          f"compile_cache_misses={clock.cache['misses']}", flush=True)
    if not all(results):
        print("chip_smoke: a phase failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
