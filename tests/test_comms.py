"""Wire-efficient sync layer (`core.comms`): cost model, schedule picker,
and the quantized error-feedback wire.

Pins the ISSUE 4 acceptance criteria:
  * the analytic bytes/sync model matches the schedule table (topology ×
    merge × wire dtype × N), and the picker selects the cheapest CORRECT
    schedule — including the int8-flips-the-argmin case,
  * quantized EF sync is bitwise deterministic, drifts from the f32 oracle
    by no more than the per-block quantization bound per round, and its
    residual telescopes to zero on constant inputs,
  * wire compression composes with lora_only payloads, checkpoints (the
    wire reference rides SwarmState), and the histo smoke loop (convergence
    non-regression),
  * invalid combinations fail loudly (mesh int8, bad dtypes).
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SwarmConfig
from repro.core import comms
from repro.core.session import SwarmSession

N = 4


def _cfg(**kw):
    kw.setdefault("n_nodes", N)
    kw.setdefault("sync_every", 2)
    kw.setdefault("merge", "fedavg")
    kw.setdefault("topology", "full")
    kw.setdefault("lora_only", False)
    kw.setdefault("val_threshold", 0.0)
    return SwarmConfig(**kw)


def _toy_fns():
    def train_step(params, opt_state, batch, step):
        g = params["x"] - batch
        return {"x": params["x"] - 0.1 * g}, opt_state, {"loss": jnp.sum(g * g)}

    def eval_fn(params, val):
        return 1.0 - 0.0 * jnp.sum(params["x"])

    return train_step, eval_fn


def _targets(d=4):
    return jnp.asarray([np.full((d,), t, np.float32) for t in range(N)])


def _session(cfg, d=4, **kw):
    kw.setdefault("params", {"x": jnp.zeros((d,))})
    kw.setdefault("data_sizes", [100 * (i + 1) for i in range(N)])
    return SwarmSession(cfg, *_toy_fns(), **kw)


# ---------------------------------------------------------------------------
# cost model + picker
# ---------------------------------------------------------------------------

def test_cost_model_matches_schedule_table():
    """bytes/sync formulas: the docstring table, at P=1 payload value."""
    p = 1 << 16
    rows = {
        ("full", "fedavg"): ("fedavg_psum", 2.0 * (N - 1) / N * 4),
        ("ring", "fedavg"): ("ring_ppermute", 2.0 * 4),
        ("dynamic", "fedavg"): ("gathered_rows", N * 4.0),
        ("full", "fisher"): ("fisher_psum", 4.0 * (N - 1) / N * 4),
        ("ring", "fisher"): ("ring_topo_ppermute", 4.0 * 4),
        ("dynamic", "gradmatch"): ("gathered_topo_stack", 2.0 * N * 4),
    }
    for (topo, merge), (name, bytes_per_p) in rows.items():
        s = comms.pick_schedule(_cfg(topology=topo, merge=merge))
        assert s.name == name, (topo, merge, s.name)
        assert s.bytes_per_sync(p) == pytest.approx(bytes_per_p * p)


def test_ring_schedules_beat_gather_by_n_over_constant():
    """Ring topo-fisher moves ≤ ~4·P values vs the gather form's 2·N·P —
    the headline acceptance number, straight from the estimator."""
    for n in (3, 4, 16, 64):
        cfg = _cfg(n_nodes=n, topology="ring", merge="fisher")
        ring = comms.pick_schedule(cfg)
        assert ring.name == "ring_topo_ppermute"
        assert ring.payload_factor <= 4.0
        gather = [s for s in comms.candidate_schedules(cfg)
                  if s.name == "gathered_topo_stack"][0]
        assert gather.payload_factor == 2.0 * n
        assert ring.bytes_per_sync(1 << 20) < gather.bytes_per_sync(1 << 20)


def test_int8_wire_flips_full_fisher_off_the_f32_psum():
    """Cost-model-driven choice, not a hardcoded table: the plain psum must
    reduce in f32, so an int8 wire flips full-topology fisher off it — onto
    the compression-aware ``fisher_psum_q8`` reduction (4·P int8 values),
    which also undercuts the gathered stack (2·N·P int8). The picker follows
    the bytes."""
    f32 = comms.pick_schedule(_cfg(topology="full", merge="fisher"))
    assert f32.name == "fisher_psum"
    i8 = comms.pick_schedule(
        _cfg(topology="full", merge="fisher", wire_dtype="int8"))
    assert i8.name == "fisher_psum_q8"
    p = 1 << 20
    assert i8.bytes_per_sync(p) < f32.bytes_per_sync(p)
    gathered = [s for s in comms.candidate_schedules(
        _cfg(topology="full", merge="fisher", wire_dtype="int8"))
        if s.name == "gathered_topo_stack"][0]
    assert i8.bytes_per_sync(p) < gathered.bytes_per_sync(p)


def test_int8_bytes_include_per_block_scale_overhead():
    s = comms.SyncSchedule("gathered_rows", "all_gather", float(N),
                           wire_dtype="int8", wire_block=512)
    p = 1 << 20
    vals = N * p
    assert s.bytes_per_sync(p) == pytest.approx(vals + vals / 512 * 4)


def test_model_sharded_payloads_skip_the_q8_psums():
    """The q8 psum reductions chunk the globally-flattened payload, which a
    model axis would scramble — a model-sharded layout must fall back to a
    q8 schedule that supports inner specs instead of picking one that
    raises at trace time."""
    from jax.sharding import PartitionSpec as P
    cfg = _cfg(topology="full", merge="fisher", wire_dtype="int8")
    assert comms.pick_schedule(cfg).name == "fisher_psum_q8"
    sharded = comms.pick_schedule(cfg, model_sharded=True)
    assert sharded.name == "gathered_topo_stack"
    assert comms.has_inner_sharding({"w": P("model"), "b": P()})
    assert not comms.has_inner_sharding({"w": P(None), "b": P()})
    assert not comms.has_inner_sharding(None)


def test_ring_schedule_needs_one_node_per_shard_and_n3():
    """per>1 or N<3 invalidates the ppermute schedules (gathered fallback)."""
    cfg = _cfg(topology="ring", merge="fisher")
    assert comms.pick_schedule(cfg, per=2).name == "gathered_topo_stack"
    cfg2 = _cfg(n_nodes=2, topology="ring", merge="fedavg")
    assert comms.pick_schedule(cfg2).name == "gathered_rows"


def test_ring_masking_preserves_ring_structure():
    """The ring-ppermute schedules assume membership masking never creates
    non-neighbour coupling — `topology.ring_structured` pins that."""
    from repro.core import topology as topo
    for n in (3, 4, 7):
        base = topo.ring_matrix(n)
        assert topo.ring_structured(base)
        masked = topo.dynamic_matrix(base, [i != 1 for i in range(n)])
        assert topo.ring_structured(masked)
    assert not topo.ring_structured(topo.full_matrix(4))


def test_validation_errors():
    with pytest.raises(ValueError, match="unknown wire_dtype"):
        comms.validate_wire_dtype("fp4")
    with pytest.raises(ValueError, match="multiple of 128"):
        comms.validate_wire_block(100)


# ---------------------------------------------------------------------------
# cost-model drift gate: CHANGES.md table == pick_schedule, row for row
# ---------------------------------------------------------------------------

_CHANGES_MD = os.path.join(os.path.dirname(__file__), "..", "CHANGES.md")


def _parse_schedule_table():
    """Rows of the CHANGES.md comms schedule table:
    (topology, merges, wires, schedule, values-expr, collective)."""
    lines = open(_CHANGES_MD).read().splitlines()
    start = next(i for i, l in enumerate(lines)
                 if l.startswith("## Comms schedule table"))
    rows = []
    for line in lines[start:]:
        if not line.startswith("|"):
            continue
        cells = [c.strip() for c in line.strip("|").split("|")]
        if len(cells) != 6 or cells[0] in ("topology", ""):
            continue
        if set(cells[0]) <= {"-"}:
            continue
        topo, merges, wires, sched, vals, coll = cells
        rows.append((topo, merges.split("/"),
                     ["f32", "bf16", "int8"] if wires == "any"
                     else wires.split("/"), sched, vals, coll))
    assert rows, "no schedule table found in CHANGES.md"
    return rows


def _values_per_sync(expr: str, n: int) -> float:
    """Evaluate a table values/sync expression ('2P·(N−1)/N', '2N·P', …)."""
    import re
    s = expr.replace("·", "*").replace("−", "-")
    s = re.sub(r"(?<=[0-9NP\)])(?=[NP\(])", "*", s)
    return float(eval(s, {"__builtins__": {}}, {"N": n, "P": 1.0}))


def _parse_hier_schedule_table():
    """Rows of the CHANGES.md hierarchical (two-level mesh) schedule table:
    (topology, merges, wires, schedule, intra-expr, cross-expr, collective).
    The 7-cell format is deliberately invisible to the flat-table parser."""
    lines = open(_CHANGES_MD).read().splitlines()
    start = next(i for i, l in enumerate(lines)
                 if l.startswith("## Hierarchical schedule table"))
    rows = []
    for line in lines[start:]:
        if not line.startswith("|"):
            continue
        cells = [c.strip() for c in line.strip("|").split("|")]
        if len(cells) != 7 or cells[0] in ("topology", ""):
            continue
        if set(cells[0]) <= {"-"}:
            continue
        topo, merges, wires, sched, intra, cross, coll = cells
        rows.append((topo, merges.split("/"), wires.split("/"), sched,
                     intra, cross, coll))
    assert rows, "no hierarchical schedule table found in CHANGES.md"
    return rows


def _parse_lora_schedule_table():
    """Rows of the CHANGES.md adapter-only payload schedule table:
    (topology, merges, wire, schedule, values-expr). The 5-cell format is
    deliberately invisible to the flat (6-cell) and hier (7-cell) parsers."""
    lines = open(_CHANGES_MD).read().splitlines()
    start = next(i for i, l in enumerate(lines)
                 if l.startswith("## Adapter-only payload schedule table"))
    rows = []
    for line in lines[start:]:
        if not line.startswith("|"):
            continue
        cells = [c.strip() for c in line.strip("|").split("|")]
        if len(cells) != 5 or cells[0] in ("topology", ""):
            continue
        if set(cells[0]) <= {"-"}:
            continue
        topo, merges, wire, sched, vals = cells
        rows.append((topo, merges.split("/"), wire, sched, vals))
    assert rows, "no adapter-only payload schedule table found in CHANGES.md"
    return rows


def _hier_values_per_sync(expr: str, k: int, m: int) -> float:
    """Evaluate a hierarchical-table expression ('(2(M−1)/M + 1)·P',
    'h·P/M', …): M = nodes/pod, h = cross-pod hops (1 at K=2, else 2)."""
    import re
    s = expr.replace("·", "*").replace("−", "-")
    s = re.sub(r"(?<=[0-9MPh\)])(?=[MPh\(])", "*", s)
    return float(eval(s, {"__builtins__": {}},
                      {"M": m, "P": 1.0, "h": 1.0 if k == 2 else 2.0}))


def test_cost_model_drift_gate():
    """The documented schedule table IS the cost model: re-derive every row
    (topology × merge × wire, at several N) from `comms.pick_schedule` and
    fail when code and table diverge — in either direction (a schedule the
    picker chooses that the table doesn't name also fails)."""
    rows = _parse_schedule_table()
    table = {}
    for topo, merges, wires, sched, vals, coll in rows:
        for m in merges:
            for wd in wires:
                assert (topo, m, wd) not in table, ("duplicate table row",
                                                    topo, m, wd)
                table[(topo, m, wd)] = (sched, vals, coll)
    for n in (3, 4, 16):
        for topo in ("full", "ring", "dynamic"):
            for m in ("mean", "fedavg", "fisher", "gradmatch"):
                for wd in ("f32", "bf16", "int8"):
                    got = comms.pick_schedule(
                        _cfg(n_nodes=n, topology=topo, merge=m, wire_dtype=wd))
                    key = (topo, m, wd)
                    assert key in table, f"picker chose {got.name} for " \
                        f"{key} but the CHANGES.md table has no such row"
                    sched, vals, coll = table[key]
                    assert got.name == sched, (key, n, got.name, sched)
                    assert got.collective == coll, (key, n, got.collective)
                    assert got.payload_factor == pytest.approx(
                        _values_per_sync(vals, n)), (key, n, vals)
                    # the documented scale-overhead formula: int8 moves one
                    # byte per value plus 4/wire_block bytes of f32 scales
                    p = 1 << 18
                    v = got.payload_factor * p
                    want = v * comms.WIRE_BYTES[got.wire_dtype]
                    if got.wire_dtype == "int8":
                        want += v / got.wire_block * 4.0
                    assert got.bytes_per_sync(p) == pytest.approx(want)

    # -- adapter-only payload class: the lora table ---------------------------
    # both routes into the class must produce identical tagged schedules:
    # lora_only (carve the adapters out of a full state at sync) and
    # payload="lora" (the state IS the flat adapter payload, PR 10)
    ltable = {}
    for topo, merges, wire, sched, vals in _parse_lora_schedule_table():
        for m in merges:
            assert (topo, m, wire) not in ltable, ("duplicate lora row",
                                                   topo, m, wire)
            ltable[(topo, m, wire)] = (sched, vals)
    for n in (3, 4, 16):
        for topo in ("full", "ring", "dynamic"):
            for m in ("mean", "fedavg", "fisher", "gradmatch"):
                for wd in ("f32", "int8"):
                    key = (topo, m, wd)
                    picks = [
                        comms.pick_schedule(_cfg(
                            n_nodes=n, topology=topo, merge=m, wire_dtype=wd,
                            lora_only=True)),
                        comms.pick_schedule(_cfg(
                            n_nodes=n, topology=topo, merge=m, wire_dtype=wd,
                            payload="lora")),
                    ]
                    for got in picks:
                        assert key in ltable, f"picker chose {got.name} " \
                            f"for lora {key} but the table has no such row"
                        sched, vals = ltable[key]
                        assert got.payload == "lora", (key, got.name)
                        assert "/lora" in got.describe(), got.describe()
                        assert got.name == sched, (key, n, got.name, sched)
                        assert got.payload_factor == pytest.approx(
                            _values_per_sync(vals, n)), (key, n, vals)
                    # untagged twin: same schedule/bytes, full payload class
                    plain = comms.pick_schedule(
                        _cfg(n_nodes=n, topology=topo, merge=m, wire_dtype=wd))
                    assert plain.payload == "full"
                    assert "/lora" not in plain.describe()

    # -- two-level (pod, node) meshes: the hierarchical table -----------------
    htable = {}
    for topo, merges, wires, sched, intra, cross, coll in \
            _parse_hier_schedule_table():
        for m in merges:
            for wd in wires:
                assert (topo, m, wd) not in htable, ("duplicate hier row",
                                                     topo, m, wd)
                htable[(topo, m, wd)] = (sched, intra, cross, coll)
    p = 1 << 18
    for k, mm in ((2, 2), (2, 4), (4, 4)):
        n = k * mm
        for topo in ("full", "ring", "dynamic"):
            for m in ("mean", "fedavg", "fisher", "gradmatch"):
                for wd in ("f32", "bf16", "int8"):
                    key = (topo, m, wd)
                    # dominant cross-pod cost: the hier row must win exactly
                    # where the table has one
                    got = comms.pick_schedule(
                        _cfg(n_nodes=n, topology=topo, merge=m, wire_dtype=wd,
                             cross_pod_cost=50.0), mesh_shape=(k, mm))
                    if key in htable:
                        sched, intra, cross, coll = htable[key]
                        assert got.name == sched, (key, k, mm, got.name)
                        assert got.collective == coll, (key, got.collective)
                        assert got.intra_factor == pytest.approx(
                            _hier_values_per_sync(intra, k, mm)), (key, k, mm)
                        assert got.cross_factor == pytest.approx(
                            _hier_values_per_sync(cross, k, mm)), (key, k, mm)
                        # intra legs move f32; the cross leg is the int8 EF
                        # wire with its documented per-block scale overhead
                        b = got.bytes_by_link_class(p)
                        assert b["intra"] == pytest.approx(
                            got.intra_factor * p * 4.0)
                        assert b["cross"] == pytest.approx(
                            got.cross_factor * p * (1 + 4.0 / got.wire_block))
                    else:
                        # no hierarchical form exists for this key: however
                        # costly the DCN hop, the picker stays on the flat
                        # table row — priced 100% cross-pod on the 2-D mesh
                        assert got.name == table[key][0], (key, k, mm,
                                                           got.name)
                        assert got.cross_factor == got.payload_factor
        # neutral link costs: flat wins even where a hier row is offered
        # (it moves fewer total bytes) — the other pick direction
        for m, wd in (("fedavg", "int8"), ("fisher", "int8")):
            got = comms.pick_schedule(
                _cfg(n_nodes=n, topology="ring", merge=m, wire_dtype=wd),
                mesh_shape=(k, mm))
            assert got.name == table[("ring", m, wd)][0], (k, mm, got.name)


# ---------------------------------------------------------------------------
# shared quantization core: one implementation, everywhere
# ---------------------------------------------------------------------------

def test_quant_encode_decode_bit_identical_to_round_trip():
    """decode(encode(v)) == quant_dequant_block(v) == the Pallas kernel's
    round-trip, bit for bit — the wire payload and the fused commit can
    never diverge from the EF contract."""
    from repro.kernels.fused_merge import _quant_block
    rng = np.random.default_rng(7)
    v = jnp.asarray(rng.normal(0, 2, (N, 1024)), jnp.float32)
    a = np.asarray(comms.quant_dequant_block(v, "int8", 128))
    q, s = comms.quant_encode(v, 128)
    assert q.dtype == jnp.int8 and s.shape == (N, 8)
    np.testing.assert_array_equal(np.asarray(comms.quant_decode(q, s, 128)),
                                  a)
    np.testing.assert_array_equal(np.asarray(_quant_block(v, "int8", 128)),
                                  a)
    for wd in ("f32", "bf16"):
        np.testing.assert_array_equal(
            np.asarray(comms.quant_dequant_block(v, wd, 128)),
            np.asarray(_quant_block(v, wd, 128)))


def test_quant_core_has_single_implementation():
    """The per-block scale arithmetic (the `/ 127` max-abs scale + round)
    lives ONLY in core/comms.py. Enforced by swarmlint's declarative
    sole_impl registry (SWL004) — any second implementation site under src/
    is a finding."""
    from repro.analysis.lint import run_paths
    findings = run_paths(["src"], rules=["SWL004"])
    assert findings == [], "\n".join(f.render() for f in findings)


# ---------------------------------------------------------------------------
# stateless quant + EF advance (XLA ground truth)
# ---------------------------------------------------------------------------

def test_quant_dequant_error_bound():
    """int8 per-block round-trip error ≤ max|block|/254 + float slack."""
    rng = np.random.default_rng(0)
    wb = 128
    x = jnp.asarray(rng.normal(0, 3, (N, 1000)), jnp.float32)
    deq = comms.quant_dequant_tree({"x": x}, "int8", wb)["x"]
    xe = np.pad(np.asarray(x), ((0, 0), (0, (-1000) % wb)))
    blocks = xe.reshape(N, -1, wb)
    bound = (np.abs(blocks).max(-1, keepdims=True) / 254.0 + 1e-6)
    err = np.abs(np.asarray(deq) - np.asarray(x))
    assert (err <= np.broadcast_to(bound, blocks.shape).reshape(N, -1)[:, :1000]).all()


def test_wire_effective_residual_is_quant_error():
    """θ − θ̂' == the current round's quantization error (nothing dropped)."""
    rng = np.random.default_rng(1)
    p = {"x": jnp.asarray(rng.normal(0, 1, (N, 300)), jnp.float32),
         "skip": None}
    wire = comms.init_wire(p)
    eff = comms.wire_effective(p, wire, "int8", 128)
    assert eff["skip"] is None
    res = comms.wire_residual(p, eff)
    # second advance transmits most of the residual: geometric contraction
    eff2 = comms.wire_effective(p, eff, "int8", 128)
    res2 = comms.wire_residual(p, eff2)
    r1 = float(jnp.abs(res["x"]).max())
    r2 = float(jnp.abs(res2["x"]).max())
    assert r2 <= r1 / 64 + 1e-7   # ≥127× in exact arithmetic; allow slack


# ---------------------------------------------------------------------------
# quantized EF sessions: determinism, drift, telescoping
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("merge,topo", [("fedavg", "full"),
                                        ("fisher", "ring"),
                                        ("gradmatch", "dynamic")])
def test_wire_session_bounded_drift_and_determinism(merge, topo):
    """int8 EF sync: (a) bitwise deterministic across runs, (b) committed
    params stay within a quantization-scale band of the f32 session every
    round — the parity harness vs the f32 host oracle."""
    batches = jnp.broadcast_to(_targets(), (2, N, 4))
    val = jnp.zeros((N, 1))

    def run(wd):
        cfg = _cfg(merge=merge, topology=topo, wire_dtype=wd, wire_block=128)
        sess = _session(cfg)
        drift = []
        for _ in range(4):
            sess.round(batches, val)
            drift.append(np.asarray(sess.state.params["x"]).copy())
        return drift

    a = run("int8")
    b = run("int8")
    for xa, xb in zip(a, b):
        np.testing.assert_array_equal(xa, xb)   # bitwise determinism
    f = run("f32")
    for r, (xa, xf) in enumerate(zip(a, f)):
        # params are O(node index) ≤ 3: per-block scale ≤ 3/127; EF keeps
        # the accumulated drift within a few quantization steps
        assert np.abs(xa - xf).max() < 0.1, f"round {r} drift too large"


def test_wire_residual_telescopes_on_constant_inputs():
    """Constant inputs (identity train step, every node inactive so no
    commit ever lands): the EF residual contracts geometrically to zero —
    untransmitted mass is delayed, never lost."""
    def train_step(p, o, b, s):
        return p, o, {"loss": 0.0 * jnp.sum(p["x"])}

    def eval_fn(p, v):
        return 0.0 * jnp.sum(p["x"])

    rng = np.random.default_rng(3)
    x0 = jnp.asarray(rng.normal(0, 1, (N, 64)), jnp.float32)
    cfg = _cfg(merge="fedavg", topology="dynamic", val_threshold=0.9,
               wire_dtype="int8", wire_block=128, sync_every=1)
    sess = SwarmSession(cfg, train_step, eval_fn, params={"x": x0},
                        stacked=True, data_sizes=[1.0] * N)
    sess.set_active([False] * N)   # merges rejected; wire still advances
    batches = jnp.zeros((1, N, 4))
    val = jnp.zeros((N, 1))
    prev = np.inf
    for r in range(5):
        out = sess.round(batches, val)
        assert not np.asarray(out["gates"]).any()   # params stay constant
        res = float(np.abs(np.asarray(sess.state.params["x"])
                           - np.asarray(sess.state.wire["x"])).max())
        if r >= 1:
            assert res <= prev / 32 + 1e-9, f"round {r}: {res} vs {prev}"
        prev = res
    assert prev < 1e-7   # telescoped to (float) zero


def test_wire_with_lora_only_payload():
    """Wire state mirrors the adapter payload (None base leaves); base
    params never cross the wire and stay bit-exact."""
    rng = np.random.default_rng(4)
    params = {"attn": {"w": jnp.asarray(rng.normal(0, 1, (8, 6)), jnp.float32),
                       "lora_A": jnp.asarray(rng.normal(0, 0.1, (8, 2)),
                                             jnp.float32),
                       "lora_B": jnp.zeros((2, 6)),
                       "lora_scale": jnp.asarray(2.0)}}

    def train_step(p, o, b, s):
        return jax.tree.map(lambda x: x + 0.01, p), o, {"loss": jnp.sum(b)}

    def eval_fn(p, v):
        return 1.0 - 0.0 * jnp.sum(p["attn"]["w"])

    cfg = _cfg(lora_only=True, wire_dtype="int8", wire_block=128,
               sync_every=1)
    sess = SwarmSession(cfg, train_step, eval_fn, params=params,
                        data_sizes=[1.0] * N)
    assert sess.state.wire["attn"]["w"] is None        # base: no wire state
    assert sess.state.wire["attn"]["lora_A"] is not None
    batches = jnp.zeros((1, N, 4))
    sess.round(batches, jnp.zeros((N, 1)))
    got_w = np.asarray(sess.state.params["attn"]["w"])
    want_w = np.asarray(params["attn"]["w"]) + 0.01    # local steps only
    np.testing.assert_array_equal(got_w, np.broadcast_to(want_w, got_w.shape))


def test_wire_checkpoint_resume_bit_identical(tmp_path):
    """save → restore → continue == never stopping, wire reference included."""
    cfg = _cfg(merge="fisher", topology="ring", wire_dtype="int8",
               wire_block=128)
    batches = jnp.broadcast_to(_targets(), (2, N, 4))
    val = jnp.zeros((N, 1))
    path = str(tmp_path / "wire.msgpack")

    ref = _session(cfg)
    for _ in range(4):
        ref.round(batches, val)

    sess = _session(cfg)
    for _ in range(2):
        sess.round(batches, val)
    sess.save(path)
    resumed = SwarmSession.restore(path, cfg, *_toy_fns(),
                                   params={"x": jnp.zeros((4,))},
                                   data_sizes=[100 * (i + 1)
                                               for i in range(N)])
    for _ in range(2):
        resumed.round(batches, val)
    np.testing.assert_array_equal(np.asarray(resumed.state.params["x"]),
                                  np.asarray(ref.state.params["x"]))
    np.testing.assert_array_equal(np.asarray(resumed.state.wire["x"]),
                                  np.asarray(ref.state.wire["x"]))


def test_wire_overlap_mode_runs():
    """EF wire composes with the stale-by-one overlap schedule."""
    cfg = _cfg(sync_every=1, overlap_sync=True, wire_dtype="int8",
               wire_block=128)
    sess = _session(cfg)
    batches = jnp.broadcast_to(_targets(), (6, 1, N, 4))
    logs = sess.run_rounds(batches, jnp.zeros((N, 1)))
    assert np.asarray(logs["gates"]).all()
    assert np.isfinite(np.asarray(sess.state.params["x"])).all()
    assert sess.state.wire is not None


def test_direct_engine_api_honours_wire_dtype():
    """The deprecated tuple API (no threaded SwarmState.wire) must still
    quantize — never a silent f32 no-op while reporting a compressed
    schedule. Without carried state it falls back to a zero reference per
    call (stateless quantization); the advanced reference is returned so
    callers CAN thread it."""
    from repro.core.engine import SwarmEngine
    rng = np.random.default_rng(6)
    params = {"x": jnp.asarray(rng.normal(0, 1, (N, 64)), jnp.float32)}
    _, eval_fn = _toy_fns()
    outs = {}
    for wd in ("f32", "int8"):
        eng = SwarmEngine(_cfg(wire_dtype=wd, wire_block=128), None, eval_fn,
                          data_sizes=[1.0] * N)
        committed, log = jax.jit(eng.sync)(params, jnp.zeros((N, 1)))
        outs[wd] = np.asarray(committed["x"])
        assert ("wire" in log) == (wd == "int8")
    assert np.abs(outs["int8"] - outs["f32"]).max() > 0   # quantized for real
    assert np.abs(outs["int8"] - outs["f32"]).max() < 3.0 / 127 * 4


def test_session_surfaces_schedule_and_bytes():
    """The trace-time choice and predicted bytes are session attributes —
    what the logs and benchmarks report."""
    sess = _session(_cfg(topology="ring", merge="fisher"))
    s = sess.sync_schedule
    assert s.name == "ring_topo_ppermute" and s.simulated
    assert sess.payload_params == 4
    assert sess.predicted_sync_bytes == pytest.approx(4 * 4 * 4)
    assert "ring_topo_ppermute" in s.describe(sess.payload_params)


# ---------------------------------------------------------------------------
# histo smoke: convergence non-regression under the quantized wire
# ---------------------------------------------------------------------------

def test_histo_smoke_with_int8_wire_non_regression():
    """The paper's histo swarm loop with an int8 EF wire tracks the f32 loop:
    same gates trajectory shape, merged-metric within a small band."""
    from repro.data import make_histo_dataset, paper_splits, shard_to_nodes
    from repro.experiments.histo import (HistoExperimentConfig,
                                         _make_model_fns, _train_loop)

    def run(wd):
        ecfg = HistoExperimentConfig(
            n_train=120, n_test=24, steps=4, image_size=16, batch_size=8,
            noise=0.6, growth=4, stem=8, feat_dim=32, hidden=16, n_blocks=1,
            layers_per_block=2, seed=5,
            swarm=SwarmConfig(n_nodes=4, sync_every=2, topology="full",
                              merge="fedavg", lora_only=False,
                              val_threshold=0.8, gate_metric="auc",
                              wire_dtype=wd, wire_block=128))
        images, labels = make_histo_dataset(ecfg.n_train,
                                            size=ecfg.image_size,
                                            noise=ecfg.noise, seed=ecfg.seed)
        shards = shard_to_nodes(images, labels,
                                paper_splits(ecfg.n_train, ecfg.fractions),
                                seed=ecfg.seed)
        train_step, _, _ = _make_model_fns(ecfg)
        params, sync_log = _train_loop(ecfg, train_step, shards,
                                       swarm_cfg=ecfg.swarm)
        return params, sync_log

    p8, log8 = run("int8")
    pf, logf = run("f32")
    assert len(log8) == len(logf) > 0
    for s8, sf in zip(log8, logf):
        assert all(np.isfinite(s8["metric_merged"]))
        m8 = np.mean(s8["metric_merged"])
        mf = np.mean(sf["metric_merged"])
        assert m8 >= mf - 0.05   # quantized sync must not collapse the gate
    for a, b in zip(jax.tree.leaves(p8[0]), jax.tree.leaves(pf[0])):
        assert np.isfinite(np.asarray(a)).all()
