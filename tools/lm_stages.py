#!/usr/bin/env python3
"""Where a swarm cell's round goes: device time per traced round under
each of the model's own scopes and each of the round's stages.

    python3 tools/lm_stages.py --workload <cell> --seed <n> --seconds <s>

Runs the cell as ``swarmbench/run.py --trace 1`` does (set-up, the check
rounds, then the window with the profiler on over its last
`run.TRACE_S` seconds), with the program's span recorder on, and prints
one JSON line:

- ``ssd_ms``, ``mixer_ms``, ``attn_ms``, ``mlp_ms``, ``head_ms``: device
  milliseconds per traced round under `repro.tracing.LM_SCOPES` (each op
  under its innermost scope: the Mamba-2 scan under ``lm.ssd`` is not in
  ``mixer_ms``), forward and backward alike, and ``lm_other_ms`` under
  none of them (embedding, AdamW, proposal, commit), in an LM cell;
- ``stem_ms``: the same under `repro.tracing.CNN_SCOPES`, the DenseNet's
  stem convolution in the gate and the local steps together, in a
  DenseNet cell;
- ``local_step_ms``, ``propose_ms``, ``gate_ms``, ``commit_ms``,
  ``other_ms``: the same split by the round's stages
  (`swarmbench/stages.py`);
- ``busy_ms``, the device's busy time per round, and the program's
  ``session.build`` attributes (an LM's model, its layer kinds, the
  frozen base's bytes, the adapters a site; the forms of the local step
  and the gate).

Op names map to scopes through the compiled round's text
(`repro.tracing.scope_of_ops`), which is read after the window from the
program the window ran (a cache hit). Exits 2 without a TPU.
"""
from __future__ import annotations

import argparse
import importlib
import json
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from repro import tracing  # noqa: E402

from swarmbench import run, stages, trace as tr  # noqa: E402

LM_METRIC = dict(zip(tracing.LM_SCOPES + (stages.OTHER,),
                     ("mixer_ms", "ssd_ms", "attn_ms", "mlp_ms", "head_ms",
                      "lm_other_ms")))
CNN_METRIC = {"cnn.stem": "stem_ms"}


def measure(cell_spec, config, traffic, *, seed, seconds, clock,
            trace_dir) -> dict:
    family = importlib.import_module(
        f"swarmbench.families.{config['family']}")
    cell = family.Cell(config, traffic, seed)
    cell.check_rounds(run.CHECK_ROUNDS)
    w = run.window(cell, seconds, traffic["in_flight"], clock, trace_dir)
    traced = tr.load(tr.find(trace_dir))
    traced.spans = sorted(w["spans"])
    lo, hi = tr.window(traced) or (0, 0)
    build = [sp for sp in tracing.drain() if sp.name == "session.build"]
    out = {"cell": cell_spec["name"], "seed": seed,
           "traced_rounds": w["traced_rounds"],
           "build": build[0].attrs if build else None}
    if traced.ops and w["traced_rounds"]:
        session = cell.session
        text = session._round_jit.lower(
            session._state, cell.inputs["pool"][0], cell.val, None,
            session.shared).compile().as_text()
        per_round = 1e3 / w["traced_rounds"]
        for scopes, names in ((tracing.LM_SCOPES, LM_METRIC),
                              (tracing.CNN_SCOPES, CNN_METRIC),
                              (tracing.ROUND_SCOPES, stages.METRIC)):
            scope_of = tracing.scope_of_ops(text, scopes)
            if scope_of:
                secs = stages.scope_seconds(traced, scope_of, lo, hi)
                out.update({names[k]: v * per_round
                            for k, v in secs.items() if k in names})
        out["busy_ms"] = tr.summarize(traced)["busy_s"] * per_round
    cell.free()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    tracing.enable()
    _, cell_spec, config, traffic, _ = run.load(args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell_spec["chips"]:
        print(f"lm_stages: {args.workload} needs {cell_spec['chips']} TPU "
              f"chip(s), JAX found {devices}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import use_compile_cache

    from swarmbench.clock import SetupClock

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    clock = SetupClock()
    tdir = tempfile.mkdtemp(prefix="lm-stages-")
    try:
        out = measure(cell_spec, config, traffic, seed=args.seed,
                      seconds=args.seconds, clock=clock, trace_dir=tdir)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    out["device"] = {"kind": devices[0].device_kind, "count": len(devices)}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
