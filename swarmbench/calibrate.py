#!/usr/bin/env python3
"""Readings the correctness limits are set from, for one cell, in one
process on the chip (PERF.md gives the readings and the limits).

    python3 swarmbench/calibrate.py --workload <cell> --seeds 1,2,... \
        [--control-seeds 1,2,3] [--fault-seeds 1,2,3] [--out file.jsonl]

For every seed of ``--seeds``: the program's first rounds, through the
cell's own session and feed at the cell's size, against the plain
reference (the lower readings). For ``--control-seeds``: the reference
with every convolution's and product's operands rounded to float8 (e4m3),
float32 accumulation, put in the program's place (the control: the
program multiplies bfloat16 operands, and float8 is the next step down).
For ``--fault-seeds``: the reference trained on half of each batch. Only
``--seeds`` needs a TPU: the other readings are the reference's own and
run on any backend. One JSON line
per reading; a state left unchanged reads ``update_gap`` 1 by the
measure's definition and needs no run. The benchmark's runs never run
this.
"""
from __future__ import annotations

import argparse
import importlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


# the control's operand type (PERF.md section 2)
CONTROL = "float8_e4m3fn"


def _seeds(text):
    return [int(s) for s in text.split(",") if s]


def readings(cell, seeds, control_seeds, fault_seeds, rounds, emit):
    """Emit one dict per reading: kind (program, control, half_batch),
    seed, the check's numbers, both sides' gates and AUCs, and
    how many gates the reference took from the compared run (ties within
    ``check.GATE_BAND``)."""
    from swarmbench import check

    def compare(kind, seed, got):
        ref = cell.reference(rounds, follow=got["gates"],
                             band=check.GATE_BAND)
        emit(kind, seed, check.numbers(got, ref),
             gates=got["gates"].tolist(), reference_gates=ref["gates"].tolist(),
             followed=ref["followed"], auc=got["auc"].tolist(),
             reference_auc=ref["auc"].tolist())

    for seed in sorted(set(seeds) | set(control_seeds) | set(fault_seeds)):
        t = time.perf_counter()
        cell.reset(seed)
        if seed in seeds:
            compare("program", seed, cell.check_rounds(rounds))
        if seed in control_seeds:
            compare("control", seed,
                    cell.reference(rounds, operands=CONTROL))
        if seed in fault_seeds:
            compare("half_batch", seed,
                    cell.reference(rounds, half_batch=True))
        print(f"seed {seed} done in {time.perf_counter() - t:.1f} s",
              file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, default=[])
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--fault-seeds", type=_seeds, default=[])
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax

    from repro.launch.compile_cache import use_compile_cache

    from swarmbench import run

    if args.seeds and jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    _, _, config, traffic, _ = run.load(args.workload)
    family = importlib.import_module(
        f"swarmbench.families.{config['family']}")
    out = open(args.out, "a") if args.out else None

    def emit(kind, seed, nums, **extra):
        line = json.dumps(dict(workload=args.workload, kind=kind, seed=seed,
                               **nums, **extra))
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    cell = family.Cell(config, traffic, 0)
    if not args.seeds:
        cell.free()
    readings(cell, args.seeds, args.control_seeds, args.fault_seeds,
             run.CHECK_ROUNDS, emit)
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
