"""SPMD gossip collectives need >1 device; all checks run in ONE subprocess
with XLA_FLAGS forcing 8 host devices (the main test process keeps seeing 1
CPU device), each printing an `OK <tag>` marker the tests assert on —
amortizing the jax import + mesh setup across the whole module."""
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.spmd

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str):
    env = dict(os.environ, PYTHONPATH=SRC)
    # append so conftest's compile-time flags survive in the subprocess
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


_CHECKS = """
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import ModelConfig, SwarmConfig, TrainConfig
from repro.core.gossip import (fedavg_gossip, fisher_gossip, matrix_gossip,
                               ring_gossip)
from repro.core.merge_impl import fisher_merge
from repro.core.engine import gate_decisions, gated_commit
from repro.core.topology import dynamic_matrix, full_matrix, ring_matrix
from repro.launch.train import (make_swarm_train_step, make_swarm_sync_step,
                                init_train_state)
from repro.models import build_model

mesh = jax.make_mesh((4, 2), ("node", "model"), devices=jax.devices())

# --- fedavg gossip == host weighted merge -------------------------------
rng = np.random.default_rng(0)
tree = {"w": jnp.asarray(rng.normal(0, 1, (4, 8, 6)), jnp.float32),
        "skip": None}
w = jnp.asarray([0.1, 0.2, 0.3, 0.4], jnp.float32)
out = jax.jit(lambda t: fedavg_gossip(t, w, mesh, "node"))(tree)
want = np.tensordot(np.asarray(w), np.asarray(tree["w"]), axes=(0, 0))
for i in range(4):
    np.testing.assert_allclose(np.asarray(out["w"][i]), want, rtol=1e-5)
assert out["skip"] is None
print("OK fedavg")

# --- ring gossip == ring mixing matrix ----------------------------------
rng = np.random.default_rng(1)
x = jnp.asarray(rng.normal(0, 1, (4, 5, 3)), jnp.float32)
out = jax.jit(lambda t: ring_gossip(t, mesh, "node", 0.5))({"x": x})["x"]
want = np.tensordot(ring_matrix(4, 0.5), np.asarray(x), axes=(1, 0))
np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5, atol=1e-6)
print("OK ring")

# --- matrix gossip with dynamic membership ------------------------------
rng = np.random.default_rng(2)
x = jnp.asarray(rng.normal(0, 1, (4, 7)), jnp.float32)
W = dynamic_matrix(full_matrix(4, [1, 3, 3, 3]), [True, True, False, True])
out = jax.jit(lambda t: matrix_gossip(t, W, mesh, "node"))({"x": x})["x"]
np.testing.assert_allclose(np.asarray(out), W @ np.asarray(x),
                           rtol=1e-5, atol=1e-6)
np.testing.assert_allclose(np.asarray(out[2]), np.asarray(x[2]))
print("OK matrix_dynamic")

# --- fisher gossip == host fisher merge ---------------------------------
rng = np.random.default_rng(3)
x = {"w": jnp.asarray(rng.normal(0, 1, (4, 6, 4)), jnp.float32)}
f = {"w": jnp.asarray(np.abs(rng.normal(1, 0.3, (4, 6, 4))), jnp.float32)}
out = jax.jit(lambda t, ff: fisher_gossip(t, ff, mesh, "node"))(x, f)["w"]
np.testing.assert_allclose(np.asarray(out), np.asarray(fisher_merge(x, f)["w"]),
                           rtol=1e-5, atol=1e-6)
print("OK fisher")

# --- ring-native topo fisher: two ppermutes, no gather, oracle parity ----
from repro.core.gossip import ring_topo_fisher_gossip, ring_rows_gossip, \
    topo_fisher_gossip
from repro.core.merge_impl import topo_weighted_merge
from repro.core.topology import ring_structured
from repro.launch import hlo_stats
rW = dynamic_matrix(ring_matrix(4, 0.5), [True, True, False, True])
assert ring_structured(rW)
ring_fn = jax.jit(lambda t, ff: ring_topo_fisher_gossip(t, ff, rW, mesh,
                                                        "node"))
want = topo_weighted_merge(x, f, rW)["w"]
np.testing.assert_allclose(np.asarray(ring_fn(x, f)["w"]), np.asarray(want),
                           rtol=1e-5, atol=1e-6)
coll = hlo_stats.collective_bytes(ring_fn.lower(x, f).compile().as_text())
d = x["w"][0].size
assert coll["all-gather"] == 0, coll
# two ppermutes of the fused (F*theta + F) payload: 4*P f32 values
assert coll["collective-permute"] == 4 * d * 4, (coll, d)
np.testing.assert_allclose(
    np.asarray(jax.jit(lambda t, ff: ring_topo_fisher_gossip(
        t, ff, rW, mesh, "node", wire_dtype="bf16"))(x, f)["w"]),
    np.asarray(want), rtol=2e-2, atol=2e-2)
print("OK ring_topo_fisher")

# --- single-gather fallback: exactly ONE all_gather of (num + mass) ------
gat_fn = jax.jit(lambda t, ff: topo_fisher_gossip(t, ff, rW, mesh, "node"))
np.testing.assert_allclose(np.asarray(gat_fn(x, f)["w"]), np.asarray(want),
                           rtol=1e-5, atol=1e-6)
coll = hlo_stats.collective_bytes(gat_fn.lower(x, f).compile().as_text())
assert coll["collective-permute"] == 0, coll
# one gather of the stacked [2N, P] payload -> 2*N*P f32 result bytes;
# two separate gathers would land 2x this from 2 ops
assert coll["all-gather"] == 2 * 4 * d * 4, (coll, d)
assert coll["count"] == 1, coll
print("OK topo_single_gather")

# --- ring rows gossip (mean/fedavg ring with a masked matrix) ------------
got = jax.jit(lambda t: ring_rows_gossip(t, rW, mesh, "node"))(x)["w"]
want_rows = np.tensordot(rW, np.asarray(x["w"]), axes=(1, 0))
np.testing.assert_allclose(np.asarray(got), want_rows, rtol=1e-5, atol=1e-6)
print("OK ring_rows")


# --- gradmatch via the engine gossip backend == host gradmatch merge -----
from repro.core.engine import SwarmEngine
from repro.core.merge_impl import gradmatch_merge
gm_mesh = jax.make_mesh((4,), ("gnode",), devices=jax.devices()[:4])  # noqa: SWL001 — off-registry on purpose: the engine's gossip backend must be axis-name-agnostic (axis is a parameter, never hardcoded)
sizes = [1.0, 3.0, 3.0, 3.0]
gcfg = SwarmConfig(n_nodes=4, topology="full", merge="gradmatch",
                   lora_only=False)
geng = SwarmEngine(gcfg, None, None, data_sizes=sizes, backend="gossip",
                   mesh=gm_mesh, axis="gnode")
cand, _, _ = jax.jit(lambda p, ff: geng.propose(p, fishers=ff))(x, f)
w = jnp.asarray(np.asarray(sizes) / np.sum(sizes), jnp.float32)
np.testing.assert_allclose(np.asarray(cand["w"]),
                           np.asarray(gradmatch_merge(x, f, w)["w"]),
                           rtol=1e-5, atol=1e-6)
print("OK gradmatch_gossip")

# --- engine gossip backend lowers ring fisher to the ppermute schedule ---
rcfg = SwarmConfig(n_nodes=4, topology="ring", merge="fisher",
                   lora_only=False)
reng = SwarmEngine(rcfg, None, None, data_sizes=[1.0] * 4, backend="gossip",
                   mesh=gm_mesh, axis="gnode")
assert reng.sync_schedule.name == "ring_topo_ppermute"
rcand_fn = jax.jit(lambda p, ff: reng.propose(p, fishers=ff)[0])
# engine applies finalize_mass (mean-1 normalization) before the merge;
# scale cancels in the ratio, so the unnormalized oracle still matches
want_eng = topo_weighted_merge(x, f, ring_matrix(4, 0.5))["w"]
np.testing.assert_allclose(np.asarray(rcand_fn(x, f)["w"]),
                           np.asarray(want_eng), rtol=1e-4, atol=1e-5)
print("OK engine_ring_schedule")

# --- full SPMD swarm step: vmapped train + gossip + gated commit --------
cfg = ModelConfig(name="t", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                  d_ff=128, vocab_size=128)
model = build_model(cfg)
tc = TrainConfig(lr=1e-3, remat=False, warmup_steps=1, max_steps=10)
keys = jax.random.split(jax.random.key(0), 4)
ps, os_ = [], []
for k in keys:
    p, o = init_train_state(model, k)
    ps.append(p); os_.append(o)
stack = lambda ts: jax.tree.map(lambda *xs: jnp.stack(xs), *ts)
params, opts = stack(ps), stack(os_)
rng = np.random.default_rng(0)
batch = {"tokens": jnp.asarray(rng.integers(0, 128, (4, 2, 16)), jnp.int32),
         "labels": jnp.asarray(rng.integers(0, 128, (4, 2, 16)), jnp.int32)}
step = jax.jit(make_swarm_train_step(model, tc))
params2, opts2, metrics = step(params, opts, batch)
assert metrics["loss"].shape == (4,)
assert np.isfinite(np.asarray(metrics["loss"])).all()

scfg = SwarmConfig(n_nodes=4, topology="ring", merge="fedavg",
                   lora_only=False, val_threshold=0.8)
propose, commit = make_swarm_sync_step(scfg, mesh, "node", [1, 3, 3, 3])
cand = jax.jit(propose)(params2)
assert all(jax.tree.leaves(
    jax.tree.map(lambda a, b: a.shape == b.shape, cand, params2)))
merged_metric = jnp.asarray([1.0, 1.0, 0.1, 1.0])
local_metric = jnp.ones(4)
final = jax.jit(commit)(cand, params2, merged_metric, local_metric)
# node 2 rejected -> keeps local
l2 = jax.tree.leaves(jax.tree.map(lambda a, b: float(jnp.abs(a[2]-b[2]).max()),
                                  final, params2))
assert max(l2) == 0.0
# node 0 accepted -> took the merge
l0 = jax.tree.leaves(jax.tree.map(lambda a, b: float(jnp.abs(a[0]-b[0]).max()),
                                  final, cand))
assert max(l0) == 0.0
print("OK swarm_step")

# --- full-topology fedavg keeps the psum schedule under a runtime mask ---
fcfg = SwarmConfig(n_nodes=4, topology="full", merge="fedavg",
                   lora_only=False)
feng = SwarmEngine(fcfg, None, None, data_sizes=[1, 3, 3, 3],
                   backend="gossip", mesh=gm_mesh, axis="gnode")
assert feng.sync_schedule.name == "fedavg_psum"
xa = {"w": jnp.asarray(np.random.default_rng(9).normal(0, 1, (4, 7)),
                       jnp.float32)}
amask = jnp.asarray([True, True, False, True])
fcand = jax.jit(lambda p, a: feng.propose(p, active=a)[0])(xa, amask)
Wdyn = dynamic_matrix(full_matrix(4, [1, 3, 3, 3]),
                      np.asarray(amask))
np.testing.assert_allclose(np.asarray(fcand["w"]),
                           Wdyn @ np.asarray(xa["w"]), rtol=1e-5, atol=1e-6)
np.testing.assert_allclose(np.asarray(fcand["w"][2]), np.asarray(xa["w"][2]))
coll = hlo_stats.collective_bytes(
    jax.jit(lambda p, a: feng.propose(p, active=a)[0])
    .lower(xa, amask).compile().as_text())
# masked fedavg stays on the psum wire: no payload-sized all_gather (XLA
# may still gather the tiny [N] weights vector)
assert coll["all-gather"] < 4 * 7 * 4, coll
# merge="mean" must stay UNIFORM under the mask (host W is uniform),
# ignoring data sizes
mcfg = SwarmConfig(n_nodes=4, topology="full", merge="mean",
                   lora_only=False)
meng = SwarmEngine(mcfg, None, None, data_sizes=[1, 3, 3, 3],
                   backend="gossip", mesh=gm_mesh, axis="gnode")
mcand = jax.jit(lambda p, a: meng.propose(p, active=a)[0])(xa, amask)
Wuni = dynamic_matrix(full_matrix(4), np.asarray(amask))
np.testing.assert_allclose(np.asarray(mcand["w"]),
                           Wuni @ np.asarray(xa["w"]), rtol=1e-5, atol=1e-6)
print("OK full_psum_masked")

# --- dynamic membership with a TRACED active mask under jit --------------
dcfg = SwarmConfig(n_nodes=4, topology="dynamic", merge="fedavg",
                   lora_only=False)
prop_dyn, _ = make_swarm_sync_step(dcfg, mesh, "node", [1, 3, 3, 3])
active = jnp.asarray([True, True, False, True])
cand2 = jax.jit(lambda p, a: prop_dyn(p, active=a))(params2, active)
l2 = jax.tree.leaves(jax.tree.map(lambda a, b: float(jnp.abs(a[2]-b[2]).max()),
                                  cand2, params2))
assert max(l2) == 0.0  # absent node keeps its params
print("OK dynamic_traced")

# --- session on a (node, model) mesh: params sharded inside each site ----
from jax.sharding import PartitionSpec as P
from repro.core.session import SwarmSession

def lin_step(p, o, b, s):
    xx, yy = b
    l, g = jax.value_and_grad(
        lambda q: jnp.mean((xx @ q["w"] + q["b"] - yy) ** 2))(p)
    return jax.tree.map(lambda a, d: a - 0.1 * d, p, g), o, {"loss": l}

def lin_eval(p, v):
    xx, yy = v
    return 1.0 / (1.0 + jnp.mean((xx @ p["w"] + p["b"] - yy) ** 2))

rng = np.random.default_rng(5)
p0 = {"w": rng.normal(0, 0.3, (4, 6, 8)).astype(np.float32),
      "b": np.zeros((4, 8), np.float32)}
rb = (rng.normal(0, 1, (2, 3, 4, 5, 6)).astype(np.float32),  # [R, T, N, B, D]
      rng.normal(0, 1, (2, 3, 4, 5, 8)).astype(np.float32))
rval = (rng.normal(0, 1, (4, 5, 6)).astype(np.float32),
        rng.normal(0, 1, (4, 5, 8)).astype(np.float32))
icfg = SwarmConfig(n_nodes=4, sync_every=3, topology="ring", merge="fedavg",
                   lora_only=False, val_threshold=0.0)
ikw = dict(params=p0, stacked=True, data_sizes=[1.0, 3.0, 3.0, 3.0])
ses = SwarmSession(icfg, lin_step, lin_eval, **ikw)
sgs = SwarmSession(icfg, lin_step, lin_eval, backend="gossip", mesh=mesh,
                   axis="node", param_specs={"w": P(None, "model"),
                                             "b": P("model")}, **ikw)
# the round program, before it runs: no site's weights gathered whole
coll = hlo_stats.collective_bytes(sgs._rounds_jit.lower(
    sgs.state, rb, rval).compile().as_text())
assert coll["all-gather"] < 6 * 8 * 4, coll
elog, glog = ses.run_rounds(rb, rval), sgs.run_rounds(rb, rval)
np.testing.assert_array_equal(np.asarray(glog["gates"]),
                              np.asarray(elog["gates"]))
for k in ("w", "b"):
    np.testing.assert_allclose(np.asarray(sgs.state.params[k]),
                               np.asarray(ses.state.params[k]),
                               rtol=1e-5, atol=1e-6)
assert sgs.state.params["w"].sharding.shard_shape((4, 6, 8)) == (1, 6, 4)
print("OK session_inner_specs")

# --- production mesh guard ----------------------------------------------
from repro.launch.mesh import make_production_mesh
try:
    make_production_mesh()
    raise SystemExit("should have raised")
except RuntimeError as e:
    assert "need" in str(e) and "XLA_FLAGS" in str(e)
print("OK mesh_guard")
"""

@pytest.fixture(scope="module")
def spmd_out():
    return _run(_CHECKS)  # module scope: the subprocess runs once


def test_fedavg_gossip_matches_host_merge(spmd_out):
    assert "OK fedavg" in spmd_out


def test_ring_gossip_matches_mixing_matrix(spmd_out):
    assert "OK ring" in spmd_out


def test_matrix_gossip_dynamic_membership(spmd_out):
    assert "OK matrix_dynamic" in spmd_out


def test_fisher_gossip_matches_host_merge(spmd_out):
    assert "OK fisher" in spmd_out


def test_gradmatch_engine_gossip_matches_host_merge(spmd_out):
    """The engine's gossip backend realizes gradmatch as the weighted-fisher
    psum ratio — must equal the host `gradmatch_merge` closed form."""
    assert "OK gradmatch_gossip" in spmd_out


def test_ring_topo_fisher_ppermute_parity_and_bytes(spmd_out):
    """Ring-native topo-fisher gossip == the host oracle, lowered to two
    ppermutes of the fused (F⊙θ ⊕ F) payload (4·P values) with ZERO
    all_gathers; bf16 wire casting stays within cast tolerance."""
    assert "OK ring_topo_fisher" in spmd_out


def test_topo_fisher_single_gather(spmd_out):
    """The general-rows fallback issues exactly ONE all_gather (the stacked
    (num ⊕ mass) payload) instead of the former two matrix_gossip passes."""
    assert "OK topo_single_gather" in spmd_out


def test_ring_rows_gossip_matches_masked_matrix(spmd_out):
    """ppermute row mixing honours a membership-masked ring matrix."""
    assert "OK ring_rows" in spmd_out


def test_engine_gossip_ring_fisher_uses_ppermute_schedule(spmd_out):
    """The comms cost model routes ring+fisher through ring_topo_ppermute
    end-to-end in the engine's gossip backend."""
    assert "OK engine_ring_schedule" in spmd_out


def test_swarm_spmd_train_and_sync_step(spmd_out):
    """Full SPMD swarm step: vmapped local training + gossip + gated commit."""
    assert "OK swarm_step" in spmd_out


def test_dynamic_membership_traced_active_mask(spmd_out):
    """Gossip propose works under jit with a traced (runtime) active mask."""
    assert "OK dynamic_traced" in spmd_out


def test_full_fedavg_mask_stays_on_psum_schedule(spmd_out):
    """A runtime membership mask must not silently demote full-topology
    fedavg from the psum schedule (2·P·(N−1)/N) to an N·P all_gather: the
    weights are active-masked in-graph and absent nodes keep their params."""
    assert "OK full_psum_masked" in spmd_out


def test_session_keeps_inner_sharded_params_sharded(spmd_out):
    """A gossip session on a (node, model) mesh with inner param specs
    leaves each site's step to the partitioner: it matches the engine
    backend, gathers no site's weights whole, and its params stay split
    over the model axis."""
    assert "OK session_inner_specs" in spmd_out


def test_production_mesh_requires_devices(spmd_out):
    assert "OK mesh_guard" in spmd_out
