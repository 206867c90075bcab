"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (us_per_call = wall-time of the
jitted op where timing is meaningful; derived = the figure's headline metric).

  fig2_node0        paper Fig 2: centralized vs swarm vs local on Node 0 (10%)
  fig3_node3        paper Fig 3: Node 3 swarm recovery of centralized AUC
  fig4_node2_25pct  paper Fig 4: Node 2 down-sampled to 25%: swarm vs local
  scarcity_node3_5pct  §4.1 extreme-scarcity trial (5%)
  tbl_dbi           §4.3 embedding quality: swarm DBI < local DBI
  tbl_minority      §4.3 minority-class recall improvement
  merge_kernel      fused swarm-merge: Pallas-fused vs unfused XLA timing,
                    incl. the importance-weighted (fisher/gradmatch) form
  lora_payload      §3.2 LoRA-only sync payload vs full-model payload
  gossip_spectrum   consensus rate (spectral gap) per topology
  engine_roundtrip  jitted stacked engine round (local steps + gated sync)
  overlap_roundtrip double-buffered stale-by-one rounds vs serial rounds
  dynamic_membership SwarmSession join/leave schedule: wall time per round +
                    retrace count (must stay at the single warmup trace —
                    membership is runtime data in the compiled round)
  spmd_parity       full SwarmEngine(backend="gossip") round vs the host
                    backend on a forced CPU device mesh (subprocess):
                    wall time + estimated collective bytes per sync
  swarm_sync        wire-efficiency suite: wall time + cost-model predicted
                    bytes/sync for every sync schedule × topology × wire
                    dtype, written machine-readable to BENCH_swarm_sync.json
  ring_sync_parity  ring-native two-ppermute topo-fisher gossip vs the
                    single-gather fallback on a forced CPU mesh
                    (subprocess): committed-params diff vs the host oracle
                    + HLO-measured collective bytes (~4·P vs 2·N·P)
  mesh_wire         int8 EF wire on the mesh gossip path: q8 schedules vs
                    their f32 forms on a forced CPU mesh (subprocess) —
                    settled-parity diff, wall time, HLO-measured collective
                    bytes (the ~4x shrink)
  hier_sync         hierarchical pod-delegate q8 schedule vs the flat ring
                    q8 on a forced-CPU 2x2 ("pod", "node") mesh
                    (subprocess): wall time + HLO-measured bytes split per
                    link class (intra-pod vs cross-pod) next to the cost
                    model's per-class prediction
  serve             serving plane (PR 8): continuous batching vs naive
                    one-request-at-a-time dispatch × consensus/average
                    ensemble modes — requests/sec, p99 latency and timed-
                    region retrace counts, written to BENCH_serve.json
  hetero_swarm      heterogeneous swarm (ISSUE 10): the scenario grid
                    (iid / paper / biased-label / synthetic-augmented /
                    dirichlet partitions) over the frozen-backbone model
                    zoo with adapter-only ``payload="lora"`` int8 sync —
                    per-cell wall time, wire bytes vs the full-payload f32
                    counterfactual, per-site gate-metric spread vs the
                    centralized oracle and retrace counters, written to
                    BENCH_hetero.json
  fault_matrix      chaos plane (ISSUE 9): every FaultPlan kind (crash,
                    straggle, drop, corrupt, preempt) × backend × merge,
                    replayed against a fault-free twin — rounds-to-recover,
                    final loss delta and retrace counts per cell, written
                    to BENCH_faults.json (gossip q8 cells run in a
                    forced-CPU-mesh subprocess on full runs)

``--smoke`` runs a seconds-scale subset (tiny shapes, no cached experiment
protocol) so CI can exercise every benchmark entry point; a tier-1 test
invokes it, keeping this harness from rotting. Smoke JSON sections land in
the gitignored ``.bench/`` scratch copy, never in the committed
BENCH_swarm_sync.json (CI asserts the tree stays clean).

Full protocol runs live in examples/histopathology_swarm.py; these benchmarks
use a reduced-but-faithful configuration (and reuse cached full results from
experiments/histo/*.json when present).
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

RESULT_DIR = "experiments/histo"
_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
BENCH_SYNC_JSON = os.path.join(_ROOT, "BENCH_swarm_sync.json")
# --smoke sections land in a gitignored scratch file: tier-1 / CI runs must
# never read-modify-write the committed perf-trajectory artifact (machine-
# local timings would dirty the tree on every test run)
BENCH_SCRATCH_JSON = os.path.join(_ROOT, ".bench", "BENCH_swarm_sync.json")
BENCH_SERVE_JSON = os.path.join(_ROOT, "BENCH_serve.json")


def _bench_json_update(section: str, data, smoke: bool = False,
                       filename: str = "BENCH_swarm_sync.json") -> str:
    """Merge one section into a machine-readable BENCH json (the committed
    file for explicit full runs, the ``.bench/`` scratch copy for --smoke)."""
    path = os.path.abspath(os.path.join(_ROOT, ".bench", filename) if smoke
                           else os.path.join(_ROOT, filename))
    os.makedirs(os.path.dirname(path), exist_ok=True)
    doc = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                doc = json.load(f)
        except Exception:  # noqa: BLE001 — regenerate a corrupt file
            doc = {}
    doc["schema"] = 1
    doc[section] = data
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, default=float)
    return path


def _time_us(fn, *args, reps=20):
    # block BEFORE t0 so compile + the warmup's async dispatch don't leak
    # into the timed region; block after so the queue is drained at t1.
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def _histo_result(tag: str, **kw):
    """Cached-or-computed paper experiment."""
    from repro.experiments.histo import HistoExperimentConfig, run_experiment
    os.makedirs(RESULT_DIR, exist_ok=True)
    path = os.path.join(RESULT_DIR, f"{tag}.json")
    if os.path.exists(path):
        return json.load(open(path))
    cfg = HistoExperimentConfig(**kw)
    r = run_experiment(cfg)
    with open(path, "w") as f:
        json.dump(r, f, indent=2, default=float)
    return r


_BASE = dict(noise=0.8, steps=400, n_train=2000, n_test=500)


def fig2_node0():
    r = _histo_result("unbalanced", **_BASE)
    c, l, s = r["centralized"]["auc"], r["local"][0]["auc"], r["swarm"][0]["auc"]
    print(f"fig2_node0_central_auc,0,{c:.4f}")
    print(f"fig2_node0_local_auc,0,{l:.4f}")
    print(f"fig2_node0_swarm_auc,0,{s:.4f}")
    print(f"fig2_node0_swarm_gain,0,{s - l:.4f}")


def fig3_node3():
    r = _histo_result("unbalanced", **_BASE)
    s = r["swarm"][3]["auc"]
    rec = r["recovery"][3]
    print(f"fig3_node3_swarm_auc,0,{s:.4f}")
    print(f"fig3_node3_recovery_of_central,0,{rec:.4f}")


def fig4_node2_25pct():
    r = _histo_result("scarcity25", scarcity={2: 0.25}, **_BASE)
    l, s = r["local"][2]["auc"], r["swarm"][2]["auc"]
    print(f"fig4_node2_local_auc,0,{l:.4f}")
    print(f"fig4_node2_swarm_auc,0,{s:.4f}")
    print(f"fig4_node2_swarm_gain,0,{s - l:.4f}")


def scarcity_node3_5pct():
    r = _histo_result("scarcity5", scarcity={3: 0.05}, **_BASE)
    l, s = r["local"][3]["auc"], r["swarm"][3]["auc"]
    print(f"scarcity_node3_local_auc,0,{l:.4f}")
    print(f"scarcity_node3_swarm_auc,0,{s:.4f}")


def tbl_dbi():
    r = _histo_result("unbalanced", **_BASE)
    ld = float(np.mean([x["dbi"] for x in r["local"]]))
    sd = float(np.mean([x["dbi"] for x in r["swarm"]]))
    print(f"tbl_dbi_local,0,{ld:.3f}")
    print(f"tbl_dbi_swarm,0,{sd:.3f}")
    print(f"tbl_dbi_reduction_pct,0,{100 * (ld - sd) / ld:.1f}")


def tbl_minority():
    r = _histo_result("unbalanced", **_BASE)
    minority = 2  # rarest class by construction
    lr = float(np.mean([x["per_class_recall"][minority] for x in r["local"]]))
    sr = float(np.mean([x["per_class_recall"][minority] for x in r["swarm"]]))
    print(f"tbl_minority_recall_local,0,{lr:.4f}")
    print(f"tbl_minority_recall_swarm,0,{sr:.4f}")
    print(f"tbl_minority_recall_gain_pts,0,{100 * (sr - lr):.2f}")


def merge_kernel(d: int = 1 << 20):
    from repro.core.merge_impl import fisher_merge
    from repro.kernels.fused_merge import fused_merge
    from repro.kernels.ref import fused_merge_ref
    n = 4
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 1, (n, d)), jnp.float32)
    w = jnp.asarray(rng.dirichlet(np.ones(n)), jnp.float32)
    ref_jit = jax.jit(lambda: fused_merge_ref(x, w, 0, True))
    us_ref = _time_us(lambda: ref_jit())
    print(f"merge_unfused_xla_4x{d},{us_ref:.1f},baseline")
    # correctness of the fused kernel on the same inputs (interpret on CPU)
    got = fused_merge(x, w, 0, True, interpret=True)
    err = float(jnp.max(jnp.abs(got - ref_jit())))
    print(f"merge_fused_pallas_validated,0,maxerr={err:.2e}")
    # all-nodes form (the engine's commit): one launch for every node's row
    from repro.kernels.fused_merge import fused_merge_all
    Wm = jnp.tile(w[None, :], (n, 1))
    gates = jnp.asarray([1, 0, 1, 1], jnp.int32)
    got_all = fused_merge_all(x, Wm, gates, interpret=True)
    want_all = jnp.where(gates[:, None].astype(bool), Wm @ x, x)
    err = float(jnp.max(jnp.abs(got_all - want_all)))
    print(f"merge_fused_all_nodes_validated,0,maxerr={err:.2e}")
    # importance-weighted form (the fisher/gradmatch commit)
    f = jnp.asarray(np.abs(rng.normal(1, 0.5, (n, d))), jnp.float32) + 1e-8
    got_imp = fused_merge_all(x, jnp.ones((n, n)), gates, f, interpret=True)
    want_m = fisher_merge({"x": x}, {"x": f - 1e-8})["x"]
    want_imp = jnp.where(gates[:, None].astype(bool), want_m, x)
    err = float(jnp.max(jnp.abs(got_imp - want_imp)))
    print(f"merge_fused_weighted_validated,0,maxerr={err:.2e}")
    # derived: HBM-roofline time for the fused passes on TPU v5e
    bytes_moved = (n + 1) * d * 4
    print(f"merge_fused_v5e_roofline_us,0,{bytes_moved / 819e9 * 1e6:.1f}")
    bytes_weighted = (2 * n + 1) * d * 4  # params + importance tiles in
    print(f"merge_fused_weighted_v5e_roofline_us,0,"
          f"{bytes_weighted / 819e9 * 1e6:.1f}")


def lora_payload():
    from repro.configs import get_config, smoke_variant
    from repro.core.lora import inject_lora, payload_bytes
    from repro.models import build_model
    cfg = get_config("internvl2-1b")
    model = build_model(smoke_variant(cfg).replace(vocab_size=2048))
    params = model.init(jax.random.key(0))
    lp = inject_lora(params, jax.random.key(1), rank=16)
    full = payload_bytes(lp, False)
    lora = payload_bytes(lp, True)
    print(f"lora_payload_bytes,0,{lora}")
    print(f"full_payload_bytes,0,{full}")
    print(f"lora_payload_fraction,0,{lora / full:.4f}")
    # production-scale derived numbers (analytic, bf16)
    big = get_config("command-r-plus-104b")
    full_b = big.param_count() * 2
    d, f, L = big.d_model, big.d_ff, big.n_layers
    ad = L * 16 * (4 * 2 * d + 3 * (d + f)) * 2  # rank-16 adapters, bf16
    print(f"command-r_full_sync_GiB,0,{full_b / 2**30:.1f}")
    print(f"command-r_lora_sync_GiB,0,{ad / 2**30:.3f}")


def gossip_spectrum():
    from repro.core.topology import build_matrix, spectral_gap
    for topo_name, n in [("full", 4), ("ring", 4), ("ring", 16)]:
        W = build_matrix(topo_name, n)
        print(f"gossip_gap_{topo_name}{n},0,{spectral_gap(W):.4f}")


def engine_roundtrip():
    """The jitted stacked engine: sync_every local steps + propose + gate +
    fused commit as ONE compiled call."""
    from repro.configs.base import SwarmConfig
    from repro.core.engine import SwarmEngine
    rng = np.random.default_rng(0)
    n, t = 4, 4
    params = {"w": jnp.asarray(rng.normal(0, 1, (n, 64, 64)), jnp.float32)}
    opt = {"m": jnp.zeros_like(params["w"])}

    def train_step(p, o, b, s):
        g = p["w"] * 1e-3
        return {"w": p["w"] - g}, {"m": o["m"] + g}, {"loss": jnp.sum(g * g)}

    def eval_fn(p, v):
        return 1.0 - 0.0 * jnp.sum(p["w"])  # always accept, stays in-graph

    eng = SwarmEngine(
        SwarmConfig(n_nodes=n, sync_every=t, lora_only=False, topology="full"),
        train_step, eval_fn)
    batches = jnp.zeros((t, n, 1))
    val = jnp.zeros((n, 1))
    state = {"p": params, "o": opt}

    def once():  # buffers are donated, so thread the state through
        p, o, _ = eng.round(state["p"], state["o"], batches, val, None, 0)
        state["p"], state["o"] = p, o
        return p["w"]

    us = _time_us(once)
    print(f"engine_round_4node_{t}steps,{us:.1f},"
          f"jitted local+propose+gate+fused_commit")


def overlap_roundtrip(reps: int = 10):
    """Stale-by-one double-buffered rounds vs serial rounds, engine backend:
    the overlap schedule must cost no more than serial (same work + one add;
    on hardware with async collectives the merge then hides behind the next
    round's local steps)."""
    from repro.configs.base import SwarmConfig
    from repro.core.engine import SwarmEngine
    rng = np.random.default_rng(0)
    n, t, r = 4, 8, 4
    w0 = jnp.asarray(rng.normal(0, 0.1, (n, 128, 128)), jnp.float32)
    batches = jnp.zeros((r, t, n, 1))
    val = jnp.zeros((n, 1))

    def train_step(p, o, b, s):
        # a real (matmul) local step so the sync/compute share is
        # representative — overlap's extra adds must amortize against it
        g = jnp.tanh(p["w"] @ p["w"].T) * 1e-3
        return {"w": p["w"] - g}, {"m": o["m"] + g}, {"loss": jnp.sum(g * g)}

    def eval_fn(p, v):
        return 1.0 - 0.0 * jnp.sum(p["w"])

    def make_runner(overlap):
        cfg = SwarmConfig(n_nodes=n, sync_every=t, topology="full",
                          merge="fedavg", lora_only=False, val_threshold=0.0,
                          overlap_sync=overlap)
        eng = SwarmEngine(cfg, train_step, eval_fn)
        # fresh buffers per config: the engine donates (params, opt_state)
        state = {"p": {"w": w0.copy()}, "o": {"m": jnp.zeros_like(w0)}}

        def once():
            p, o, _, _ = eng.run_rounds(state["p"], state["o"], batches, val,
                                        None, 0)
            state["p"], state["o"] = p, o
            return p["w"]

        return once

    runners = {ov: make_runner(ov) for ov in (False, True)}
    # alternate measurement passes and keep the per-mode minimum — the
    # robust floor estimate on a noisy shared-CPU runner
    times = {False: float("inf"), True: float("inf")}
    for _ in range(3):
        for ov in (False, True):
            times[ov] = min(times[ov], _time_us(runners[ov], reps=reps))
    for ov in (False, True):
        name = "overlap" if ov else "serial"
        print(f"engine_round_{name}_us,{times[ov] / r:.1f},"
              f"{r}rounds_x{t}steps_fedavg")
    print(f"overlap_vs_serial_ratio,0,{times[True] / times[False]:.3f}")


def dynamic_membership(rounds_per_phase: int = 4, d: int = 128):
    """ROADMAP dynamic-membership scenario: a join→leave→rejoin schedule
    driven through `SwarmSession.round` — wall time per round plus the
    retrace count across the whole schedule (the compiled round must be
    traced exactly once; membership flips are pure state updates)."""
    from repro.configs.base import SwarmConfig
    from repro.core.session import SwarmSession

    rng = np.random.default_rng(0)
    n, t = 4, 4
    traces = []

    def train_step(p, o, b, s):
        traces.append(1)  # python body runs once per (re)trace only
        g = jnp.tanh(p["w"] @ p["w"].T) * 1e-3
        return {"w": p["w"] - g}, {"m": o["m"] + g}, {"loss": jnp.sum(g * g)}

    def eval_fn(p, v):
        return 1.0 - 0.0 * jnp.sum(p["w"])

    w0 = jnp.asarray(rng.normal(0, 0.1, (d, d)), jnp.float32)
    sess = SwarmSession(
        SwarmConfig(n_nodes=n, sync_every=t, topology="dynamic",
                    merge="fedavg", lora_only=False, val_threshold=0.0),
        train_step, eval_fn, params={"w": w0},
        opt_state={"m": jnp.zeros_like(w0)}, data_sizes=[1.0] * n)
    batches = jnp.zeros((t, n, 1))
    val = jnp.zeros((n, 1))

    # schedule: all-active -> node 3 leaves -> node 3 rejoins & node 1 leaves
    phases = [lambda: None, lambda: sess.leave(3),
              lambda: (sess.join(3), sess.leave(1))]
    sess.round(batches, val)  # warmup: the one and only trace/compile
    warmup_traces = len(traces)
    t0 = time.perf_counter()
    n_rounds = 0
    for phase in phases:
        phase()
        for _ in range(rounds_per_phase):
            out = sess.round(batches, val)
            n_rounds += 1
    jax.block_until_ready(out["gates"])
    us = (time.perf_counter() - t0) / n_rounds * 1e6
    print(f"dynamic_membership_round_us,{us:.1f},"
          f"{n_rounds}rounds_join_leave_rejoin")
    print(f"dynamic_membership_retraces,0,"
          f"{len(traces) - warmup_traces}")
    print(f"dynamic_membership_final_active,0,"
          f"{''.join(str(int(b)) for b in sess.active)}")


def dynamic_membership_smoke():
    dynamic_membership(rounds_per_phase=2, d=32)


def _spmd_parity_inner(n: int, t: int, d: int, reps: int):
    """Runs inside the forced-device-count subprocess: one full engine round
    per backend (host vs gossip) on identical state, timed + compared."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs.base import SwarmConfig
    from repro.core.engine import SwarmEngine

    assert jax.device_count() >= n, "inner bench needs the forced device count"
    mesh = jax.make_mesh((n,), ("node",), devices=jax.devices()[:n])
    rng = np.random.default_rng(0)
    w0 = jnp.asarray(rng.normal(0, 1, (n, d)), jnp.float32)
    batches = jnp.zeros((t, n, 1))
    val = jnp.zeros((n, 1))
    sizes = [float(i + 1) for i in range(n)]

    finals = {}
    for backend in ("host", "gossip"):
        cfg = SwarmConfig(n_nodes=n, sync_every=t, topology="full",
                          merge="fedavg", lora_only=False, val_threshold=0.0)
        # fresh buffers per backend: the engine donates (params, opt_state)
        params, opt = {"w": w0.copy()}, {"m": jnp.zeros_like(w0)}
        kw = {}
        if backend == "gossip":
            kw = dict(backend="gossip", mesh=mesh, axis="node")

        def train_step(p, o, b, s):
            g = p["w"] * 1e-3 + 0.0 * b.mean()
            return ({"w": p["w"] - g}, {"m": o["m"] + g},
                    {"loss": jnp.sum(g * g)})

        def eval_fn(p, v):
            return 1.0 - 0.0 * jnp.sum(p["w"])

        eng = SwarmEngine(cfg, train_step, eval_fn, data_sizes=sizes, **kw)
        if backend == "gossip":
            sh = NamedSharding(eng.mesh, P("node"))
            params = jax.device_put(params, sh)
            opt = jax.device_put(opt, sh)
        state = {"p": params, "o": opt}

        def once():
            p, o, _ = eng.round(state["p"], state["o"], batches, val, None, 0)
            state["p"], state["o"] = p, o
            return p["w"]

        us = _time_us(once, reps=reps)
        finals[backend] = (us, np.asarray(state["p"]["w"]))
        print(f"spmd_parity_{backend}_round_us,{us:.1f},n={n};t={t};d={d}")

    err = float(np.abs(finals["host"][1] - finals["gossip"][1]).max())
    print(f"spmd_parity_max_abs_diff,0,{err:.2e}")
    print(f"spmd_parity_gossip_over_host,0,"
          f"{finals['gossip'][0] / finals['host'][0]:.3f}")
    # estimated collective bytes per sync, per device: the fedavg psum
    # lowers to a ring allreduce over the [d] merged payload
    bytes_sync = 2 * d * 4 * (n - 1) / n
    print(f"spmd_parity_collective_bytes_per_sync,0,{bytes_sync:.0f}")


def _cpu_child(mode: str, args, n_devices: int) -> str:
    """Run ``--inner <mode>`` of this script in a child pinned to the CPU
    (``JAX_PLATFORMS=cpu``) with ``n_devices`` forced host devices: a
    rehearsal mesh. The parent may hold the accelerator, and a chip belongs
    to one process, so a child never asks for it. Returns the child's
    stdout, whose first row names the platform it ran on."""
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n_devices}").strip()
    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--inner", mode,
         ",".join(map(str, args))],
        capture_output=True, text=True, env=env, timeout=600)
    if out.returncode != 0:
        raise RuntimeError(f"{mode} subprocess failed: {out.stderr[-800:]}")
    return out.stdout


def spmd_parity(smoke: bool = False):
    """ROADMAP SPMD engine parity: a full SwarmEngine(backend="gossip") round
    vs the host backend on a multi-device CPU mesh. Runs in a subprocess so
    the forced host device count doesn't leak into other benchmarks."""
    n, t, d, reps = (4, 2, 1 << 12, 3) if smoke else (4, 4, 1 << 16, 10)
    print(_cpu_child("spmd-parity", (n, t, d, reps), n), end="")


def spmd_parity_smoke():
    spmd_parity(smoke=True)


def swarm_sync(smoke: bool = False):
    """Wire-efficiency suite (ISSUE 4): one engine-backend session per sync
    schedule × topology × merge × wire dtype, reporting the comms cost
    model's predicted bytes/sync next to measured round wall time; rows are
    written machine-readable to BENCH_swarm_sync.json so the perf
    trajectory populates."""
    from repro.configs.base import SwarmConfig
    from repro.core.session import SwarmSession

    n, t, d, reps = (4, 2, 1 << 12, 3) if smoke else (4, 4, 1 << 16, 10)
    if smoke:
        combos = [("full", "fedavg", "f32"), ("ring", "fisher", "f32"),
                  ("ring", "fisher", "int8"), ("dynamic", "fisher", "bf16")]
    else:
        combos = [(topo, merge, wd)
                  for topo in ("full", "ring", "dynamic")
                  for merge in ("fedavg", "fisher")
                  for wd in ("f32", "bf16", "int8")]
    rng = np.random.default_rng(0)
    w0 = jnp.asarray(rng.normal(0, 0.1, (d,)), jnp.float32)
    batches = jnp.zeros((t, n, 1))
    val = jnp.zeros((n, 1))

    def train_step(p, o, b, s):
        g = p["w"] * 1e-3 + 0.0 * b.mean()
        return {"w": p["w"] - g}, {"m": o["m"] + g}, {"loss": jnp.sum(g * g)}

    def eval_fn(p, v):
        return 1.0 - 0.0 * jnp.sum(p["w"])

    rows = []
    for topo, merge, wd in combos:
        cfg = SwarmConfig(n_nodes=n, sync_every=t, topology=topo, merge=merge,
                          lora_only=False, val_threshold=0.0, wire_dtype=wd)
        sess = SwarmSession(cfg, train_step, eval_fn, params={"w": w0},
                            opt_state={"m": jnp.zeros_like(w0)},
                            data_sizes=[float(i + 1) for i in range(n)])

        def once():
            return sess.round(batches, val)["gates"]

        us = _time_us(once, reps=reps)
        s = sess.sync_schedule
        link = sess.predicted_link_bytes
        rows.append(dict(
            schedule=s.name, collective=s.collective, topology=topo,
            merge=merge, wire_dtype=wd, n_nodes=n,
            # engine-backend sessions simulate a flat 1-D swarm mesh; the
            # per-link-class split keys every row the same way the two-level
            # hier_sync rows are keyed (cross is 0 on a flat mesh)
            mesh_shape=[n],
            payload_params=sess.payload_params,
            predicted_bytes_per_sync=sess.predicted_sync_bytes,
            predicted_intra_bytes=link["intra"],
            predicted_cross_bytes=link["cross"],
            wall_us_per_round=us, simulated=s.simulated))
        print(f"swarm_sync_{topo}_{merge}_{wd},{us:.1f},"
              f"sched={s.name};bytes={sess.predicted_sync_bytes:.0f}")
    # smoke writes its own section INTO THE SCRATCH FILE so CI runs never
    # touch the committed full-grid rows (the perf-trajectory artifact)
    path = _bench_json_update("schedules_smoke" if smoke else "schedules",
                              rows, smoke=smoke)
    print(f"swarm_sync_json,0,{path}")


def swarm_sync_smoke():
    swarm_sync(smoke=True)


def _ring_sync_parity_inner(n: int, d: int, reps: int):
    """Runs inside the forced-device-count subprocess: ring-native
    two-ppermute topo-fisher gossip vs the single-gather fallback, both
    against the host numpy oracle, with HLO-measured collective bytes."""
    from repro.core import gossip
    from repro.core.merge_impl import topo_weighted_merge
    from repro.core.topology import build_matrix, ring_structured
    from repro.launch import hlo_stats

    assert jax.device_count() >= n, "inner bench needs the forced device count"
    mesh = jax.make_mesh((n,), ("node",), devices=jax.devices()[:n])
    rng = np.random.default_rng(0)
    x = {"w": jnp.asarray(rng.normal(0, 1, (n, d)), jnp.float32)}
    f = {"w": jnp.asarray(np.abs(rng.normal(1, 0.4, (n, d))), jnp.float32)}
    W = build_matrix("ring", n)
    assert ring_structured(W)
    want = np.asarray(topo_weighted_merge(x, f, W)["w"])

    fns = {
        "ppermute": jax.jit(lambda a, b: gossip.ring_topo_fisher_gossip(
            a, b, W, mesh, "node")),
        "gathered": jax.jit(lambda a, b: gossip.topo_fisher_gossip(
            a, b, W, mesh, "node")),
    }
    got = {}
    for name, fn in fns.items():
        out = np.asarray(fn(x, f)["w"])
        err = float(np.abs(out - want).max())
        us = _time_us(lambda fn=fn: fn(x, f)["w"], reps=reps)
        coll = hlo_stats.collective_bytes(fn.lower(x, f).compile().as_text())
        got[name] = (us, err, coll["total"])
        print(f"ring_sync_{name}_us,{us:.1f},n={n};d={d}")
        print(f"ring_sync_{name}_max_diff,0,{err:.2e}")
        print(f"ring_sync_{name}_coll_bytes,0,{coll['total']}")
    # per the collective-bytes estimator: ring two-ppermute payload is the
    # fused (F⊙θ ⊕ F) side-channel = ~4·P f32 values; the gather is 2·N·P
    print(f"ring_sync_ppermute_P_values,0,{got['ppermute'][2] / 4 / d:.2f}")
    print(f"ring_sync_bytes_ratio,0,"
          f"{got['ppermute'][2] / got['gathered'][2]:.3f}")


def ring_sync_parity(smoke: bool = False):
    """Forced-CPU-mesh ring-ppermute parity (subprocess, like spmd_parity):
    keeps the ring-native schedule honest on dev boxes without a mesh."""
    n, d, reps = (4, 1 << 12, 3) if smoke else (4, 1 << 16, 10)
    out = _cpu_child("ring-sync", (n, d, reps), n)
    print(out, end="")
    rows = [dict(zip(("name", "us", "derived"), line.split(",", 2)))
            for line in out.strip().splitlines() if "," in line]
    _bench_json_update("ring_parity_smoke" if smoke else "ring_parity", rows,
                       smoke=smoke)


def ring_sync_parity_smoke():
    ring_sync_parity(smoke=True)


def _mesh_wire_inner(n: int, d: int, reps: int):
    """Runs inside the forced-device-count subprocess: the int8 mesh EF wire
    (q8 ring + q8 psum schedules) vs their f32 forms — committed-params
    parity after EF settling, wall time, and HLO-measured collective bytes
    (the ~4x wire shrink the cost model promises)."""
    from repro.core import gossip
    from repro.core.merge_impl import topo_weighted_merge
    from repro.core.topology import build_matrix
    from repro.launch import hlo_stats

    assert jax.device_count() >= n, "inner bench needs the forced device count"
    mesh = jax.make_mesh((n,), ("node",), devices=jax.devices()[:n])
    rng = np.random.default_rng(0)
    wb = 128
    x = {"w": jnp.asarray(rng.normal(0, 1, (n, d)), jnp.float32)}
    f = {"w": jnp.asarray(np.abs(rng.normal(1, 0.4, (n, d))), jnp.float32)}
    W = build_matrix("ring", n)
    want = np.asarray(topo_weighted_merge(x, f, W)["w"])

    wire0 = gossip.init_mesh_wire("ring_topo_ppermute", x, n_shards=n,
                                  wire_block=wb)
    q8 = jax.jit(lambda t, ff, w: gossip.ring_topo_fisher_gossip_q8(
        t, ff, W, w, mesh, "node", wire_block=wb))
    f32 = jax.jit(lambda t, ff: gossip.ring_topo_fisher_gossip(
        t, ff, W, mesh, "node"))
    wire = wire0
    for _ in range(6):   # settle the EF references
        merged, wire = q8(x, f, wire)
    err = float(np.abs(np.asarray(merged["w"]) - want).max())
    us_q8 = _time_us(lambda: q8(x, f, wire0)[0]["w"], reps=reps)
    us_f32 = _time_us(lambda: f32(x, f)["w"], reps=reps)
    cq = hlo_stats.collective_bytes(
        q8.lower(x, f, wire0).compile().as_text())
    cf = hlo_stats.collective_bytes(f32.lower(x, f).compile().as_text())
    print(f"mesh_wire_q8_round_us,{us_q8:.1f},n={n};d={d};wb={wb}")
    print(f"mesh_wire_f32_round_us,{us_f32:.1f},n={n};d={d}")
    print(f"mesh_wire_q8_settled_max_diff,0,{err:.2e}")
    print(f"mesh_wire_q8_coll_bytes,0,{cq['total']}")
    print(f"mesh_wire_f32_coll_bytes,0,{cf['total']}")
    print(f"mesh_wire_bytes_ratio,0,{cq['total'] / cf['total']:.3f}")
    # the compression-aware psum: int8 reduce-scatter chunks vs f32 psum
    wv = jnp.full((n,), 1.0 / n, jnp.float32)
    pw0 = gossip.init_mesh_wire("fedavg_psum_q8", x, n_shards=n,
                                wire_block=wb)
    pq = jax.jit(lambda t, w: gossip.fedavg_psum_q8(t, wv, w, mesh, "node",
                                                    wire_block=wb))
    pf = jax.jit(lambda t: gossip.fedavg_gossip(t, wv, mesh, "node"))
    cq2 = hlo_stats.collective_bytes(pq.lower(x, pw0).compile().as_text())
    cf2 = hlo_stats.collective_bytes(pf.lower(x).compile().as_text())
    print(f"mesh_wire_psum_q8_coll_bytes,0,{cq2['total']}")
    print(f"mesh_wire_psum_f32_coll_bytes,0,{cf2['total']}")


def mesh_wire(smoke: bool = False):
    """int8 EF wire on the mesh gossip path (ISSUE 5): forced-CPU-mesh
    subprocess measuring the q8 schedules' parity + collective bytes; rows
    land in BENCH_swarm_sync.json (committed on full runs, scratch on
    --smoke)."""
    n, d, reps = (4, 1 << 12, 3) if smoke else (4, 1 << 16, 10)
    out = _cpu_child("mesh-wire", (n, d, reps), n)
    print(out, end="")
    rows = [dict(zip(("name", "us", "derived"), line.split(",", 2)))
            for line in out.strip().splitlines() if "," in line]
    _bench_json_update("mesh_wire_smoke" if smoke else "mesh_wire", rows,
                       smoke=smoke)


def mesh_wire_smoke():
    mesh_wire(smoke=True)


def _hier_sync_inner(k: int, m: int, d: int, reps: int):
    """Runs inside the forced-device-count subprocess: the hierarchical
    pod-delegate q8 schedule vs the flat ring q8 over the joint axis on a
    (k pods, m nodes/pod) two-level mesh — wall time plus HLO-measured
    collective bytes split per link class (`hlo_stats.
    collective_bytes_by_link`), next to the cost model's per-class
    prediction."""
    import json as json_mod
    from repro.configs.base import SwarmConfig
    from repro.core import comms, gossip
    from repro.core.topology import ring_matrix
    from repro.launch import hlo_stats
    from repro.launch.mesh import make_two_level_swarm_mesh

    n = k * m
    assert jax.device_count() >= n, "inner bench needs the forced device count"
    mesh, axis = make_two_level_swarm_mesh(k, m)
    wb = 128
    rng = np.random.default_rng(0)
    x = {"w": jnp.asarray(rng.normal(0, 1, (n, d)), jnp.float32)}
    wv = jnp.full((n,), 1.0 / n, jnp.float32)
    Wp = jnp.asarray(ring_matrix(k, 0.5), jnp.float32)
    Wn = jnp.asarray(ring_matrix(n, 0.5), jnp.float32)
    pod_of = hlo_stats.pod_device_map(k, m)

    def predicted(cross_pod_cost):
        cfg = SwarmConfig(n_nodes=n, topology="ring", merge="fedavg",
                          lora_only=False, wire_dtype="int8", wire_block=wb,
                          cross_pod_cost=cross_pod_cost)
        return comms.pick_schedule(cfg, mesh_shape=(k, m))

    hier_sched = predicted(10.0)      # dominant DCN cost -> hierarchical
    flat_sched = predicted(1.0)       # neutral costs -> flat ring
    assert hier_sched.name == "hier_fedavg_ring_q8", hier_sched.name
    assert flat_sched.name == "ring_ppermute", flat_sched.name

    hw0 = gossip.init_mesh_wire("hier_fedavg_ring_q8", x, n_shards=n,
                                wire_block=wb, mesh_shape=(k, m))
    fw0 = gossip.init_mesh_wire("ring_ppermute", x, n_shards=n, wire_block=wb)
    cases = [
        ("hier_fedavg_ring_q8", hier_sched, hw0, jax.jit(
            lambda t, w: gossip.hier_fedavg_ring_q8(
                t, wv, Wp, w, mesh, axis, wire_block=wb))),
        ("flat_ring_q8", flat_sched, fw0, jax.jit(
            lambda t, w: gossip.ring_rows_gossip_q8(
                t, Wn, w, mesh, axis, wire_block=wb))),
    ]
    rows = []
    for name, sched, w0_, fn in cases:
        us = _time_us(lambda fn=fn, w0_=w0_: fn(x, w0_)[0]["w"], reps=reps)
        link = hlo_stats.collective_bytes_by_link(
            fn.lower(x, w0_).compile().as_text(), pod_of)
        pred = sched.bytes_by_link_class(d)
        rows.append(dict(
            schedule=sched.name, collective=sched.collective,
            topology="ring", merge="fedavg", wire_dtype="int8", n_nodes=n,
            mesh_shape=[k, m], payload_params=d,
            predicted_intra_bytes=pred["intra"],
            predicted_cross_bytes=pred["cross"],
            measured_intra_bytes=link["intra"],
            measured_cross_bytes=link["cross"],
            wall_us_per_round=us))
        print(f"hier_sync_{name}_us,{us:.1f},k={k};m={m};d={d};wb={wb}")
        print(f"hier_sync_{name}_intra_bytes,0,{link['intra']}")
        print(f"hier_sync_{name}_cross_bytes,0,{link['cross']}")
    ratio = rows[0]["measured_cross_bytes"] / rows[1]["measured_cross_bytes"]
    print(f"hier_sync_cross_bytes_ratio,0,{ratio:.3f}")
    print("hier_sync_rows_json,0," + json_mod.dumps(rows))


def hier_sync(smoke: bool = False):
    """Hierarchical two-level comms (ISSUE 7): forced-CPU 2x2 ("pod",
    "node") mesh subprocess comparing the pod-delegate q8 schedule against
    the flat ring q8 per link class; rows (intra- vs cross-pod bytes,
    predicted and HLO-measured) land in BENCH_swarm_sync.json (committed on
    full runs, scratch on --smoke)."""
    k, m, d, reps = (2, 2, 1 << 12, 3) if smoke else (2, 2, 1 << 16, 10)
    rows = []
    for line in _cpu_child("hier-sync", (k, m, d, reps), k * m).splitlines():
        if line.startswith("hier_sync_rows_json,"):
            rows = json.loads(line.split(",", 2)[2])
        elif line:
            print(line)
    if not rows:
        raise RuntimeError("hier sync subprocess emitted no JSON rows")
    path = _bench_json_update("hier_sync_smoke" if smoke else "hier_sync",
                              rows, smoke=smoke)
    print(f"hier_sync_json,0,{path}")


def hier_sync_smoke():
    hier_sync(smoke=True)


# ---------------------------------------------------------------------------
# serve — continuous-batching consensus inference (PR 8)
# ---------------------------------------------------------------------------

_SERVE_CONFIGS = {
    # naive: one request at a time, the pre-PR-8 dispatch discipline
    "naive_b1": dict(max_slots=1, batch_buckets=(1,)),
    # continuous batching: up to 8 co-resident requests, bucketed table
    "continuous_b8": dict(max_slots=8, batch_buckets=(1, 2, 4, 8)),
}


def serve(smoke: bool = False):
    """Requests/sec + p99 latency for batching config × consensus mode over
    a 4-node vmapped ensemble; writes BENCH_serve.json. The full bucket grid
    is warmed before t0 and the timed region asserts zero retraces — the
    comparison is dispatch discipline, not compile noise."""
    from repro.configs import get_config, smoke_variant
    from repro.models import build_model
    from repro.serve import BucketPolicy, ServeEngine

    cfg = smoke_variant(get_config("minicpm-2b")).replace(vocab_size=256)
    model = build_model(cfg)
    n_nodes = 4
    params = jax.vmap(model.init)(
        jax.random.split(jax.random.key(0), n_nodes))
    n_requests, max_new = (8, 8) if smoke else (32, 16)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, size=int(n)).astype(np.int32)
               for n in rng.integers(4, 16, size=n_requests)]

    rows, tput = [], {}
    for config, knobs in _SERVE_CONFIGS.items():
        for mode in ("consensus", "average"):
            eng = ServeEngine(
                model, params, mode=mode, max_len=48,
                max_slots=knobs["max_slots"],
                policy=BucketPolicy(batch_buckets=knobs["batch_buckets"],
                                    seq_buckets=(16,)))
            # warm every (batch, seq) bucket the timed run will touch
            for p in prompts[:min(8, n_requests)]:
                eng.submit(p, max_new=2)
            eng.drain()
            warm_traces = eng.total_traces
            t0 = time.perf_counter()
            for p in prompts:
                eng.submit(p, max_new=max_new)
            done = eng.drain()
            wall = time.perf_counter() - t0
            lat_ms = np.array([r.latency_s for r in done]) * 1e3
            new_tokens = sum(len(r.node_tokens) for r in done)
            row = {
                "config": config, "mode": mode,
                "max_slots": knobs["max_slots"],
                "batch_buckets": list(knobs["batch_buckets"]),
                "n_nodes": n_nodes, "n_requests": len(done),
                "max_new": max_new, "wall_s": wall,
                "requests_per_s": len(done) / wall,
                "tokens_per_s": new_tokens / wall,
                "p50_ms": float(np.percentile(lat_ms, 50)),
                "p99_ms": float(np.percentile(lat_ms, 99)),
                "retraces_timed": eng.total_traces - warm_traces,
            }
            rows.append(row)
            tput[config, mode] = row["requests_per_s"]
            print(f"serve_{config}_{mode},{wall / len(done) * 1e6:.0f},"
                  f"req_s={row['requests_per_s']:.2f};"
                  f"p99_ms={row['p99_ms']:.1f};"
                  f"retraces={row['retraces_timed']}")
    ratios = {mode: tput["continuous_b8", mode] / tput["naive_b1", mode]
              for mode in ("consensus", "average")}
    for mode, r in ratios.items():
        print(f"serve_continuous_vs_naive_{mode},0,{r:.2f}")
    data = {"model": "minicpm-2b (smoke variant, vocab 256)",
            "n_nodes": n_nodes, "n_requests": n_requests, "max_new": max_new,
            "rows": rows, "continuous_over_naive_throughput": ratios}
    path = _bench_json_update("serve_smoke" if smoke else "serve", data,
                              smoke=smoke, filename="BENCH_serve.json")
    print(f"serve_json,0,{path}")


def serve_smoke():
    serve(smoke=True)


# ---------------------------------------------------------------------------
# fault matrix — chaos plane (ISSUE 9)
# ---------------------------------------------------------------------------

def _fault_matrix_plans(n: int, rounds: int):
    from repro.faults import FaultPlan
    base = lambda: FaultPlan(n_nodes=n, n_rounds=rounds, seed=0)
    return {
        "crash": base().crash(1, at=2, rejoin=4),
        "straggle": base().straggle(2, at=2, rounds=2),
        "drop": base().drop(3, at=2),
        "corrupt": base().corrupt(1, at=2),
        "preempt": base().preempt(at=4),
    }


def _fault_matrix_cells(merges, fault_kinds, rounds: int, d: int, *,
                        backend: str = "engine", session_kw=None,
                        tol: float = 1e-3):
    """One (fault × merge) grid on one backend: each cell replays a fault
    plan against a fresh int8-wire session under contractive pull-to-target
    dynamics and reports rounds-to-recover (first round, counted from the
    fault's last affected round, within ``tol`` of the fault-free twin's
    trajectory), the final loss delta, and excess retraces (compiles beyond
    the one-per-session warmup — must be 0: faults are runtime data)."""
    import tempfile
    from repro.configs.base import SwarmConfig
    from repro.core.session import SwarmSession
    from repro.faults import FaultPlan, run_plan

    n, steps, lr = 4, 3, 0.5
    rng = np.random.default_rng(0)
    targets = jnp.asarray(rng.normal(0, 1, (n, d)), jnp.float32)
    # the per-node pull target rides in as the batch (train_step is vmapped
    # over nodes, so it can't index the stacked target itself)
    batches = jnp.tile(targets[None], (steps, 1, 1))
    val = jnp.zeros((n, 1))
    session_kw = dict(session_kw or {})
    tmp = tempfile.mkdtemp()
    plans = _fault_matrix_plans(n, rounds)

    def make(merge, traces):
        topo = "ring" if merge == "fisher" else "full"
        cfg = SwarmConfig(n_nodes=n, sync_every=steps, topology=topo,
                          merge=merge, lora_only=False, val_threshold=0.0,
                          wire_dtype="int8", wire_block=128)

        def pull_step(p, o, b, s):
            traces.append(1)      # python body runs only at (re)trace
            g = p["x"] - b
            return {"x": p["x"] - lr * g}, o, {"loss": jnp.sum(g * g)}

        def eval_fn(p, v):
            return 1.0 - 0.0 * jnp.sum(p["x"])

        return SwarmSession(cfg, pull_step, eval_fn,
                            params={"x": jnp.zeros((n, d), jnp.float32)},
                            stacked=True, data_sizes=[1.0] * n, **session_kw)

    def run(merge, plan):
        traces, traj = [], []
        box = {"sess": make(merge, traces)}

        def mk():                 # preempt rebuild: track the live session
            box["sess"] = make(merge, traces)
            return box["sess"]

        def obs(r, log):
            traj.append(np.asarray(box["sess"].state.params["x"],
                                   np.float64).copy())

        run_plan(box["sess"], plan, batches, val, make_session=mk,
                 checkpoint_path=os.path.join(tmp, "fault_preempt.msgpack"),
                 on_round=obs)
        n_sessions = 1 + sum(e.kind == "preempt" for e in plan.events)
        return np.stack(traj), len(traces) - n_sessions

    t64 = np.asarray(targets, np.float64)
    loss = lambda x: float(np.mean((x - t64) ** 2))
    rows = []
    for merge in merges:
        ref, _ = run(merge, FaultPlan(n_nodes=n, n_rounds=rounds, seed=0))
        for kind in fault_kinds:
            plan = plans[kind]
            traj, excess = run(merge, plan)
            low = plan.lower()
            faulty = (~low.active.all(axis=1)) | low.corrupt.any(axis=1) \
                | low.preempt
            fault_end = int(np.flatnonzero(faulty).max())
            delta = np.abs(traj - ref).max(axis=(1, 2))
            rec = next((r - fault_end for r in range(fault_end, rounds)
                        if delta[r] <= tol), -1)
            # diagnostic-quality recovery: fisher's mean-normalized Δθ²
            # importance remembers the fault window ~forever, so the exact
            # parameter trajectory may never rejoin the twin's — while the
            # quality metric (mean squared distance to the per-node optima)
            # still re-converges; report both
            ldelta = np.array([abs(loss(traj[r]) - loss(ref[r]))
                               / max(loss(ref[r]), 1e-9)
                               for r in range(rounds)])
            rec_loss = next((r - fault_end for r in range(fault_end, rounds)
                             if ldelta[r] <= tol), -1)
            rows.append(dict(
                backend=backend, merge=merge, fault=kind, rounds=rounds,
                fault_end_round=fault_end, rounds_to_recover=rec,
                rounds_to_recover_loss=rec_loss,
                final_max_delta=float(delta[-1]),
                final_rel_loss_delta=float(ldelta[-1]),
                excess_retraces=excess))
            print(f"fault_{backend}_{merge}_{kind},0,"
                  f"recover={rec};recover_loss={rec_loss};"
                  f"delta={delta[-1]:.2e};retraces={excess}")
    return rows


def _fault_matrix_gossip_inner(n: int, d: int, rounds: int):
    """Runs inside the forced-device-count subprocess: the gossip-backend
    q8 cells (corrupt degrades to a one-round drop — no in-graph wire
    injection on the mesh schedules, by design)."""
    import json as json_mod
    assert jax.device_count() >= n, "inner bench needs the forced device count"
    mesh = jax.make_mesh((n,), ("node",), devices=jax.devices()[:n])
    rows = _fault_matrix_cells(
        ("fedavg", "fisher"), ("crash", "drop", "corrupt"), rounds, d,
        backend="gossip",
        session_kw=dict(backend="gossip", mesh=mesh, axis="node"))
    print("fault_rows_json,0," + json_mod.dumps(rows))


def fault_matrix(smoke: bool = False):
    """Chaos-plane recovery matrix (ISSUE 9): every FaultPlan kind replayed
    against engine-backend int8 sessions (plus gossip q8 cells in full runs,
    forced-CPU-mesh subprocess), each versus its fault-free twin; rows land
    in BENCH_faults.json (committed on full runs, scratch on --smoke)."""
    kinds = ("crash", "straggle", "drop", "corrupt", "preempt")
    rounds, d = (8, 256) if smoke else (12, 1024)
    merges = ("fedavg",) if smoke else ("fedavg", "fisher")
    rows = _fault_matrix_cells(merges, kinds, rounds, d)
    if not smoke:
        n = 4
        for line in _cpu_child("fault-gossip", (n, d, rounds), n).splitlines():
            if line.startswith("fault_rows_json,"):
                rows += json.loads(line.split(",", 2)[2])
            elif line:
                print(line)
    data = dict(n_nodes=4, rounds=rounds, tol=1e-3, rows=rows)
    path = _bench_json_update("fault_smoke" if smoke else "fault_matrix",
                              data, smoke=smoke, filename="BENCH_faults.json")
    print(f"fault_matrix_json,0,{path}")


def fault_matrix_smoke():
    fault_matrix(smoke=True)


def hetero_swarm(smoke: bool = False):
    """Heterogeneous swarm scenario grid (ISSUE 10): every grid cell runs
    the frozen-backbone model zoo with adapter-only ``payload="lora"`` int8
    sync and the fairness floor; rows (wire bytes vs full-payload f32,
    per-site metric spread vs the centralized oracle, retrace counters)
    land in BENCH_hetero.json (committed on full runs, scratch on --smoke).
    Smoke keeps ALL grid cells — CI asserts ≥4 scenario rows — and shrinks
    only the run scale."""
    from repro.configs.base import SwarmConfig
    from repro.experiments import scenarios

    if smoke:
        rcfg = scenarios.ScenarioRunConfig(
            n_train=96, n_test=48, feat_dim=8, hidden=8, steps=8,
            batch_size=4,
            swarm=SwarmConfig(
                n_nodes=4, sync_every=4, topology="ring", merge="fedavg",
                payload="lora", wire_dtype="int8", wire_block=128,
                val_threshold=0.0, gate_metric="auc", fairness_floor=0.05))
    else:
        rcfg = scenarios.ScenarioRunConfig()
    rows = []
    for scn in scenarios.scenario_grid():
        t0 = time.perf_counter()
        row = scenarios.run_scenario(scn, rcfg)
        row["wall_s"] = time.perf_counter() - t0
        rows.append(row)
        print(f"hetero_{row['scenario']},{row['wall_s'] * 1e6:.0f},"
              f"wire_bytes={row['wire_bytes_per_sync']:.0f};"
              f"frac_of_full={row['wire_fraction_of_full']:.5f};"
              f"retraces={row['retraces']};"
              f"auc_spread={row['site_auc_spread']:.4f};"
              f"oracle_gap={row['oracle_gap_auc']:.4f};"
              f"fair_ok={row['fairness_ok_last']}")
    data = dict(n_nodes=rcfg.n_nodes, steps=rcfg.steps,
                sync_every=rcfg.swarm.sync_every,
                schedule=rows[0]["schedule"], rows=rows)
    path = _bench_json_update("hetero_smoke" if smoke else "hetero", data,
                              smoke=smoke, filename="BENCH_hetero.json")
    print(f"hetero_swarm_json,0,{path}")


def hetero_swarm_smoke():
    hetero_swarm(smoke=True)


def merge_kernel_smoke():
    merge_kernel(1 << 14)


def overlap_roundtrip_smoke():
    overlap_roundtrip(reps=3)


ALL = [fig2_node0, fig3_node3, fig4_node2_25pct, scarcity_node3_5pct,
       tbl_dbi, tbl_minority, merge_kernel, lora_payload, gossip_spectrum,
       engine_roundtrip, overlap_roundtrip,
       dynamic_membership, spmd_parity, swarm_sync, ring_sync_parity,
       mesh_wire, hier_sync, serve, hetero_swarm, fault_matrix]

# seconds-scale subset covering every benchmark family (tier-1 smoke test)
SMOKE = [merge_kernel_smoke, gossip_spectrum, engine_roundtrip,
         overlap_roundtrip_smoke, dynamic_membership_smoke,
         spmd_parity_smoke, swarm_sync_smoke, ring_sync_parity_smoke,
         mesh_wire_smoke, hier_sync_smoke, serve_smoke, hetero_swarm_smoke,
         fault_matrix_smoke]


def roofline_table():
    """Append the §Roofline rows when a dry-run matrix is present."""
    from benchmarks.roofline import load_rows
    rows = load_rows("experiments/dryrun")
    for r in rows:
        print(f"roofline_{r['arch']}_{r['shape']},0,"
              f"compute={r['compute_s']:.3e};memory={r['memory_s']:.3e};"
              f"collective={r['collective_s']:.3e};dominant={r['dominant']};"
              f"useful={r['useful_ratio']:.3f};peakGiB={r['peak_gib']:.1f}")


# the --inner modes: each runs inside a CPU child (see _cpu_child)
INNER = {"spmd-parity": _spmd_parity_inner,
         "ring-sync": _ring_sync_parity_inner,
         "mesh-wire": _mesh_wire_inner,
         "hier-sync": _hier_sync_inner,
         "fault-gossip": _fault_matrix_gossip_inner}


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="benchmark harness")
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale subset for CI (no cached protocols)")
    ap.add_argument("--inner", nargs=2, metavar=("MODE", "ARGS"),
                    help="internal: run one forced-device mode "
                         f"({', '.join(INNER)}) with comma-separated ints")
    args = ap.parse_args(argv)

    if args.inner:
        mode, ints = args.inner
        dev = jax.devices()[0]
        print(f"{mode.replace('-', '_')}_device,0,platform={dev.platform};"
              f"kind={dev.device_kind};count={jax.device_count()}")
        INNER[mode](*map(int, ints.split(",")))
        return 0

    print("name,us_per_call,derived")
    fns = SMOKE if args.smoke else ALL + [roofline_table]
    failed = []
    for fn in fns:
        try:
            fn()
        except Exception as e:  # noqa: BLE001 — reported, then exit 1
            print(f"{fn.__name__},0,ERROR:{e!r}")
            failed.append(fn.__name__)
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
