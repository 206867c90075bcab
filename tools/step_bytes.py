#!/usr/bin/env python3
"""XLA's count of the histo swarm's local step, gate forward and round,
vmapped against site-folded, compiled for a described TPU v5e (nothing
runs; no chip).

    JAX_PLATFORMS=cpu PYTHONPATH=src python tools/step_bytes.py \
        [--only step.vmap,step.folded,forward.vmap,forward.folded] [--top K]

At the benchmark's shapes (4 sites x batch 32 x 224 px at the paper's
widths; the gate at 38 validation rows a site) it compiles the train step
``jax.vmap``-ed over sites and its stacked, site-folded form
(`histo._make_model_fns`), and the gate's forward both ways; and, when
named with ``--only``, the whole round of a benchmark traffic mix
(``round.<mix>.vmap``, ``round.<mix>.folded``, the mix read from
``swarmbench/traffic/<mix>.json``). One JSON line per program: XLA's
``bytes accessed`` and ``flops`` (`Compiled.cost_analysis`), the
program's temporary and code bytes (`Compiled.memory_analysis`; the
device holds the code in its memory, so a round's code counts in the
benchmark's ``peak_device_bytes``), and the bytes of the operands and
results of its unfused instructions, as stored in their tiles and as
logical (`launch.hlo_stats.tile_padding`). With ``--top K``, each
program's line is followed by one line for each of its K instructions
that the compiler estimates at the most cycles
(`launch.hlo_stats.estimated_cycles`): its name, the cycles, its round
stage and model part (`repro.tracing` scopes, from its ``op_name``; a
backward pass's under the forward's) and, for a convolution, the emitter
the compiler picked. A compile for a described v5e names its fusions as
the chip's trace does, so the instructions of a ledger line's
``breakdown.device_ops`` can be looked up here. These are compiler counts
and estimates, not times (a loop's body counts once). A step or round
compile takes one to three minutes on a CPU.
"""
from __future__ import annotations

import argparse
import json
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

from repro import tracing
from repro.configs.base import SwarmConfig
from repro.configs.paper_histo import PAPER_FULL
from repro.core import engine
from repro.core.session import SwarmSession
from repro.experiments.histo import (HistoExperimentConfig, _init_params,
                                     _make_model_fns, _swarm_session)
from repro.launch.hlo_stats import estimated_cycles, tile_padding
from repro.models.cnn import forward_cnn, forward_cnn_sites
from repro.optim import adamw_init

ROOT = Path(__file__).resolve().parents[1]
SITES, BATCH, VAL_ROWS = 4, 32, 38
SHARD_SIZES = [51, 153, 154, 154]  # the benchmark's 10/30/30/30 of 512


def _ecfg(sync_every=20):
    c = PAPER_FULL
    return HistoExperimentConfig(
        image_size=c.image_size, growth=c.growth, stem=c.stem,
        feat_dim=c.feat_dim, hidden=c.hidden, n_blocks=c.n_blocks,
        layers_per_block=c.layers_per_block, batch_size=BATCH,
        sync_every=sync_every)


def _stack(tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((SITES,) + s.shape, s.dtype), tree)


def _images(*lead):
    hw = (PAPER_FULL.image_size,) * 2 + (3,)
    return jax.ShapeDtypeStruct(lead + hw, jnp.float32)


def _step_or_forward(name):
    ecfg = _ecfg()
    step = _make_model_fns(ecfg)[0]
    params = _stack(jax.eval_shape(
        lambda: _init_params(ecfg, jax.random.key(0))))
    batch = (_images(SITES, BATCH),
             jax.ShapeDtypeStruct((SITES, BATCH), jnp.int32))
    args = (params, _stack(jax.eval_shape(adamw_init, params)), batch,
            jax.ShapeDtypeStruct((), jnp.int32))
    val = (params, _images(SITES, VAL_ROWS))
    return {
        "step.vmap": (jax.vmap(step, in_axes=(0, 0, 0, None)), args),
        "step.folded": (step.stacked, args),
        "forward.vmap": (jax.vmap(forward_cnn), val),
        "forward.folded": (forward_cnn_sites, val),
    }[name]


def _round(mix, form):
    """The session's round at a traffic mix; ``vmap``: the same step and
    eval behind wrappers that offer no stacked form."""
    t = json.loads((ROOT / "swarmbench/traffic" / f"{mix}.json").read_text())
    swarm = SwarmConfig(
        n_nodes=SITES, sync_every=t["sync_every"], topology=t["topology"],
        merge=t["merge"], lora_only=False, val_threshold=t["gate_threshold"],
        gate_metric="auc", self_weight=t["self_weight"],
        wire_dtype=t["wire"], wire_block=t["wire_block"])
    ecfg = _ecfg(t["sync_every"])
    step = _make_model_fns(ecfg)[0]
    shards = [(None, np.zeros(s)) for s in SHARD_SIZES]
    session = _swarm_session(ecfg, step, shards, swarm, interpret=False)
    if form == "vmap":
        ev = session.eval_fn
        session = SwarmSession(
            swarm, lambda p, o, b, s: step(p, o, b, s),
            lambda p, v: ev(p, v), params=session.state.params,
            opt_state=session.state.opt_state, stacked=True,
            data_sizes=SHARD_SIZES, interpret=False)
    sds = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype), tree)
    batches = (_images(t["sync_every"], SITES, BATCH),
               jax.ShapeDtypeStruct((t["sync_every"], SITES, BATCH),
                                    jnp.int32))
    val = (_images(SITES, VAL_ROWS),
           jax.ShapeDtypeStruct((SITES, VAL_ROWS), jnp.int32),
           jax.ShapeDtypeStruct((SITES, VAL_ROWS), jnp.bool_))
    return session._round_jit, (sds(session.state), batches, val, None)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--only", default="step.vmap,step.folded,forward.vmap,"
                    "forward.folded", help="comma-separated program names")
    ap.add_argument("--top", type=int, default=0, metavar="K",
                    help="also print each program's K instructions with "
                    "the most estimated cycles")
    opts = ap.parse_args(argv)
    names = [n for n in opts.only.split(",") if n]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    jax.config.update("jax_enable_compilation_cache", False)
    # the programs are compiled for a TPU: the engine takes the forms it
    # takes there, though JAX runs on the CPU here
    engine.lanes_tiled = lambda: True
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    place = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
        tree)
    for name in names:
        kind, *rest = name.split(".")
        fn, args = (_round(*rest) if kind == "round"
                    else _step_or_forward(name))
        compiled = jax.jit(fn).lower(*place(args)).compile()
        cost = compiled.cost_analysis()
        cost = cost[0] if isinstance(cost, list) else cost
        mem = compiled.memory_analysis()
        text = compiled.as_text()
        stored, logical = tile_padding(text)
        print(json.dumps({"program": name,
                          "bytes_accessed": cost["bytes accessed"],
                          "flops": cost["flops"],
                          "temp_bytes": mem.temp_size_in_bytes,
                          "code_bytes": mem.generated_code_size_in_bytes,
                          "unfused_bytes_stored": stored,
                          "unfused_bytes_logical": logical}), flush=True)
        if opts.top:
            stage = tracing.scope_of_ops(text)
            part = tracing.scope_of_ops(
                text, tracing.CNN_SCOPES + tracing.LM_SCOPES)
            for i in estimated_cycles(text)[:opts.top]:
                print(json.dumps({"program": name, "instruction": i["name"],
                                  "estimated_cycles": i["cycles"],
                                  "stage": stage.get(i["name"]),
                                  "part": part.get(i["name"]),
                                  "emitter": i["emitter"]}), flush=True)


if __name__ == "__main__":
    main()
