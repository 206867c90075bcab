#!/usr/bin/env python3
"""Where a cell's round goes: device time per traced round in each of the
round's stages, and the program's own host spans and set-up count.

    python3 swarmbench/stages.py --workload <cell> --seed <n> --seconds <s>

Runs the cell as ``run.py --trace 1`` does (set-up, the check rounds, then
the window with the profiler on over its last `run.TRACE_S` seconds), with
the program's span recorder (`repro.tracing`) on from the start, and
prints one JSON line:

- ``local_step_ms``, ``propose_ms``, ``gate_ms``, ``commit_ms``: the
  device milliseconds per traced round under each stage of
  `repro.tracing.ROUND_SCOPES`, and ``other_ms``, under none
  (`scope_seconds`); ``busy_ms``, the device's busy time per round;
- ``round_host_cpu_ms``: the mean thread CPU time of the program's
  ``round`` spans (`SwarmSession.round`) in the traced slice, beside
  ``round_host_wall_ms``, their wall time;
- ``setup_session_s``: the wall time of the ``session.build`` span
  (`histo._swarm_session`: weights, AdamW state, the session);
- ``setup_programs``: programs compiled or loaded from the cache from the
  start of the clock until the check rounds have run (`clock.SetupClock`).

A traced round is one that `run.window` reads in the traced slice, as
``commit_roofline`` counts them; with many rounds in flight the reads
lag the device and the count runs a few rounds high, so the shares of
``busy_ms`` are the steadier reading (PERF.md section 7).

Op names map to stages through the round program's compiled text
(`repro.tracing.scope_of_ops`): a chip trace's events carry the HLO name
and not the ``op_name`` metadata that holds the scope. The text is read
after the window, from the compiled round the window ran (a cache hit).
Exits 2 without a TPU, as ``run.py`` does.
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

from swarmbench import run, trace as tr  # noqa: E402

OTHER = "other"
# what each stage's device time per round is printed as: the names of the
# per-layer metrics that are to read them
METRIC = dict(zip(tracing.ROUND_SCOPES + (OTHER,),
                  ("local_step_ms", "propose_ms", "gate_ms", "commit_ms",
                   "other_ms")))


def scope_seconds(trace: tr.Trace, scope_of: dict, lo: int, hi: int) -> dict:
    """Device seconds in [lo, hi] under each scope, mean over the trace's
    devices: per scope the union of the intervals of the ops that
    ``scope_of`` maps to it, so a control op (a ``while``) and the ops of
    its body count once; under `OTHER`, the busy time that no op of a
    scope covers."""
    scopes = sorted(set(scope_of.values()))
    out = dict.fromkeys(scopes + [OTHER], 0)
    for ops in trace.ops.values():
        scoped = []
        for s in scopes:
            evs = [ev for ev in ops if scope_of.get(ev[2]) == s]
            out[s] += sum(e - b for b, e in tr.union(evs, lo, hi))
            scoped += evs
        busy = sum(e - b for b, e in tr.union(ops, lo, hi))
        out[OTHER] += busy - sum(e - b for b, e in tr.union(scoped, lo, hi))
    nd = max(len(trace.ops), 1)
    return {k: v / nd * 1e-9 for k, v in out.items()}


def host_spans(spans, lo: int, hi: int) -> dict:
    """The program's spans: ``round`` spans that open in [lo, hi] (their
    mean CPU and wall milliseconds) and the ``session.build`` span."""
    rounds = [sp for sp in spans if sp.name == "round" and lo <= sp.start_ns
              and sp.end_ns <= hi]
    build = [sp for sp in spans if sp.name == "session.build"]
    out = {"round_spans": len(rounds),
           "setup_session_s": ((build[0].end_ns - build[0].start_ns) * 1e-9
                               if build else None)}
    if rounds:
        out["round_host_cpu_ms"] = (sum(sp.cpu_ns for sp in rounds)
                                    / len(rounds) * 1e-6)
        out["round_host_wall_ms"] = (sum(sp.end_ns - sp.start_ns
                                         for sp in rounds)
                                     / len(rounds) * 1e-6)
    return out


def round_scopes(cell) -> dict:
    """``{HLO name: stage}`` of the compiled round that ``cell.round``
    runs."""
    session = cell.session
    compiled = session._round_jit.lower(
        session._state, cell.inputs["pool"][0], cell.val, None).compile()
    return tracing.scope_of_ops(compiled.as_text())


def measure(cell_spec, config, traffic, *, seed, seconds, clock,
            trace_dir) -> dict:
    """One run of the cell; the recorder must be on from before the
    cell is built. Without a TPU plane in the trace, the device numbers
    are left out."""
    family = importlib.import_module(
        f"swarmbench.families.{config['family']}")
    cell = family.Cell(config, traffic, seed)
    cell.check_rounds(run.CHECK_ROUNDS)
    out = {"cell": cell_spec["name"], "seed": seed,
           "setup_programs": clock.programs}
    w = run.window(cell, seconds, traffic["in_flight"], clock, trace_dir)
    traced = tr.load(tr.find(trace_dir))
    traced.spans = sorted(w["spans"])
    lo, hi = tr.window(traced) or (0, 0)
    out.update(host_spans(tracing.drain(), lo, hi),
               traced_rounds=w["traced_rounds"],
               window_programs=w["window_programs"])
    if traced.ops and w["traced_rounds"]:
        scope_of = round_scopes(cell)
        per_round = 1e3 / w["traced_rounds"]
        secs = scope_seconds(traced, scope_of, lo, hi)
        busy = tr.summarize(traced)["busy_s"]
        out.update({METRIC[k]: v * per_round for k, v in secs.items()},
                   busy_ms=busy * per_round,
                   other_share=secs[OTHER] / busy,
                   scoped_ops=len(scope_of))
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
        print(f"stages: {args.workload} needs {cell_spec['chips']} TPU "
              f"chip(s), JAX found {devices}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import use_compile_cache

    from swarmbench.clock import SetupClock

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    clock = SetupClock()
    tdir = tempfile.mkdtemp(prefix="swarmbench-stages-")
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
