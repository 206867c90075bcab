#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 swarmbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (``workloads`` in BENCHMARK.json) names a configuration file, a
traffic mix file and a chip count. Set-up builds the family's session from
the seed, runs the first rounds through the window's own call and feed
(they warm every program and are the rounds the correctness check
compares), then the window runs rounds with one in flight ahead: dispatch
round r+1, then read round r's gates, for ``--seconds``. With ``--trace 1``
the profiler records the device in the window's last ``TRACE_S`` seconds
(see `window`). After the window the session is freed and the plain
reference runs the first rounds again; ``correct`` says whether the
program's rounds agree with it within the cell's limits
(``swarmbench/limits/<cell>.json``).

The last line of stdout is one JSON object: ``correct``, ``attempted``
(rounds completed in the window), ``failed`` (of those, rounds with a
non-finite loss), ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics, each read by
``swarmbench/metrics/<name>.py``), ``device`` and, traced, ``breakdown``;
its last key, ``checks``, holds each compared number beside its limit, as
do the last lines of stderr. Exits 2, printing no result, when JAX finds
no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

# rounds that set-up runs first: warm-up and the correctness check's rounds
CHECK_ROUNDS = 3
# seconds at the window's end that a traced run records: a profile of the
# whole window holds too many events to keep on the host
TRACE_S = 5.0


def load(workload: str):
    """(BENCHMARK.json, the cell, its configuration, its traffic mix, its
    correctness limits)."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"cells: {sorted(cells)}")
    cell = cells[workload]
    cfg = next(c for c in bench["configs"] if c["name"] == cell["config"])
    read = lambda p: json.loads((ROOT / p).read_text())
    return (bench, cell, read(cfg["file"]),
            read(f"swarmbench/traffic/{cell['traffic']}.json"),
            read(f"swarmbench/limits/{workload}.json"))


def p90(values):
    """90th percentile (inclusive quantiles); one value is its own."""
    if len(values) < 2:
        return max(values)
    return statistics.quantiles(values, n=10, method="inclusive")[8]


def host_memory() -> int | None:
    """This process's resident bytes (Linux)."""
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS:"):
                return int(line.split()[1]) * 1024
    return None


def applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def profile_options():
    """Device operations only. The host tracer at level 1 records millions
    of events a second from the TPU runtime's threads, gigabytes of memory
    in a few seconds, so the harness times its own spans instead."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 0
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


def window(cell, seconds: float, in_flight: int, clock, trace_dir=None):
    """Rounds for ``seconds``, ``in_flight`` dispatched before the oldest is
    read. Returns what the metrics read.

    With ``trace_dir`` the profiler records the window's last ``TRACE_S``
    seconds there, and the harness keeps its spans (``dispatch``,
    ``wait_gates``) on the trace's clock. The traced slice opens when the
    first round read in the trace has been read, so the rounds that run
    in it are those read after that, each whole; it closes when the
    window does, every round in flight read inside it. The clock is read,
    and only then the trace stops."""
    import collections

    import jax

    r = CHECK_ROUNDS
    reads, dispatch, spans, bad = [], [], [], 0
    pending = collections.deque()
    programs0 = clock.programs
    tracing, trace_s = False, {}

    def span(name, fn, *args):
        t = time.time_ns()
        out = fn(*args)
        if tracing:
            spans.append((t, time.time_ns(), name))
        return out

    def read_oldest():
        nonlocal bad
        _, loss = span("wait_gates", cell.read, pending.popleft())
        reads.append(time.perf_counter())
        bad += not all(math.isfinite(v) for v in loss.ravel())

    try:
        t_start = time.perf_counter()
        end = t_start + seconds
        trace_at = end - min(TRACE_S, seconds) if trace_dir else math.inf
        while True:
            if not tracing and time.perf_counter() >= trace_at:
                t = time.perf_counter()
                jax.profiler.start_trace(trace_dir,
                                         profiler_options=profile_options())
                trace_s["start"] = time.perf_counter() - t
                tracing = True
            t = time.perf_counter()
            pending.append(span("dispatch", cell.round, r))
            dispatch.append(time.perf_counter() - t)
            r += 1
            if len(pending) < in_flight:
                continue
            read_oldest()
            if reads[-1] >= end:
                break
        while pending:
            read_oldest()
        t_end = reads[-1]
    finally:
        if tracing:
            t = time.perf_counter()
            jax.profiler.stop_trace()
            trace_s["stop"] = time.perf_counter() - t
    if spans:
        opened = next(e for _, e, n in spans if n == "wait_gates")
        spans = [sp for sp in spans if sp[0] >= opened]
    steps = [b - a for a, b in zip([t_start] + reads, reads)]
    return {"rounds": len(reads), "rounds_dispatched": r - CHECK_ROUNDS,
            "traced_rounds": (sum(n == "wait_gates" for *_, n in spans)
                              if tracing else None),
            "spans": spans, "trace_s": trace_s,
            "window_s": t_end - t_start, "round_s": steps,
            "dispatch_s": dispatch, "failed": bad,
            "window_programs": clock.programs - programs0}


def run(bench, cell_spec, config, traffic, limits, *, seed, seconds, trace,
        devices, clock):
    """One run of a cell on ``devices``; returns the result line's dict."""
    import jax

    from swarmbench import check, trace as tr

    family = importlib.import_module(
        f"swarmbench.families.{config['family']}")
    mem = {"start": host_memory()}
    cell = family.Cell(config, traffic, seed)
    mem["built"] = host_memory()
    prog = cell.check_rounds(CHECK_ROUNDS)
    setup_s = time.perf_counter() - T0
    mem["setup"] = host_memory()
    tdir = tempfile.mkdtemp(prefix="swarmbench-trace-") if trace else None
    summary, trace_bytes = None, None
    try:
        w = window(cell, seconds, traffic["in_flight"], clock, tdir)
        mem["window"] = host_memory()
        if tdir:
            path = tr.find(tdir)
            trace_bytes = os.path.getsize(path)
            traced = tr.load(path)
            # the harness's spans, timed on the trace's clock
            traced.spans = sorted(w["spans"])
            summary = tr.summarize(traced)
            mem["trace_read"] = host_memory()
    finally:
        if tdir:
            shutil.rmtree(tdir, ignore_errors=True)
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peak = max(peaks) if None not in peaks else None
    ctx = dict(w, trace=summary, chips=len(devices),
               device_kind=devices[0].device_kind,
               model_flops_per_round=cell.model_flops_per_round,
               commit=cell.commit)
    samples = cell.samples_per_round
    described = cell.describe()
    cell.free()
    gc.collect()

    ref = cell.reference(CHECK_ROUNDS, follow=prog["gates"],
                         band=check.GATE_BAND)
    mem["reference"] = host_memory()
    nums = check.numbers(prog, ref)
    correct, rows = check.judge(nums, limits)
    correct = correct and w["failed"] == 0

    name = cell_spec["name"]
    if trace:
        metrics = {}
        for m in bench["per_layer"]:
            if not applies(m, name):
                continue
            mod = importlib.import_module(f"swarmbench.metrics.{m['name']}")
            value = mod.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = {"site_samples_per_s": w["rounds"] * samples / w["window_s"],
               "round_ms_p90": p90(w["round_s"]) * 1e3,
               "peak_device_bytes": peak, "setup_s": setup_s}
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in bench["end_to_end"] if applies(m, name)}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": w["rounds"],
           "failed": w["failed"], "metrics": metrics, "device": device}
    if summary is not None:
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        out["breakdown"] = {"device_ops": summary["device_ops"],
                            "idle_gaps": summary["idle_gaps"]}
    print(json.dumps({
        "cell": name, "seed": seed, "setup_s": setup_s,
        "compile_s": clock.seconds, "cache": clock.cache,
        "window": {k: w[k] for k in ("rounds", "rounds_dispatched",
                                     "traced_rounds", "window_s",
                                     "window_programs", "trace_s")},
        "trace_bytes": trace_bytes,
        "host_memory": mem,
        "peaks": peaks, "program_gates": prog["gates"].tolist(),
        "reference_gates": ref["gates"].tolist(),
        "gates_followed": ref["followed"],
        "program_auc": prog["auc"].tolist(),
        "reference_auc": ref["auc"].tolist(),
        "numbers": nums, **described,
        **({"idle_by_label_s": summary["idle_by_label_s"]}
           if summary else {})}), file=sys.stderr)
    out["checks"] = {k: {"value": v, "limit": lim} for k, v, lim in rows}
    for k, v, lim in rows:
        print(f"check {k} {v!r} limit {lim!r}", file=sys.stderr)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench, cell_spec, config, traffic, limits = load(args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"swarmbench: needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 2
    chips = cell_spec["chips"]
    if len(devices) < chips:
        print(f"swarmbench: {args.workload} needs {chips} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import use_compile_cache

    from swarmbench.clock import SetupClock

    use_compile_cache()
    # every program, however quick to compile, is kept: set-up stays steady
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    clock = SetupClock()
    out = run(bench, cell_spec, config, traffic, limits, seed=args.seed,
              seconds=args.seconds, trace=bool(args.trace),
              devices=devices[:chips], clock=clock)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
