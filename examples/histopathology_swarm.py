"""End-to-end reproduction of the paper's cancer-histopathology experiments.

Runs the full §4 protocol: 4 nodes, unbalanced 10/30/30/30 shards, P2P-SL with
validation-gated FedAvg merging every `sync_every` steps, against centralized
and standalone baselines; then the 25% and 5% scarcity trials. Writes JSON
results into experiments/histo/ (consumed by benchmarks/run.py and
EXPERIMENTS.md).

Run:  PYTHONPATH=src python examples/histopathology_swarm.py [--steps 400]
"""
import argparse
import json
import os

from repro.experiments.histo import (HistoExperimentConfig, run_experiment,
                                     summarize)
from repro.launch.compile_cache import use_compile_cache

OUT = "experiments/histo"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=400)
    ap.add_argument("--n-train", type=int, default=2000)
    ap.add_argument("--seeds", type=int, default=1,
                    help="paper repeats 5 seeds; default 1 for CPU speed")
    args = ap.parse_args()
    use_compile_cache()
    os.makedirs(OUT, exist_ok=True)

    scenarios = {
        "unbalanced": {},
        "scarcity25": {"scarcity": {2: 0.25}},
        "scarcity5": {"scarcity": {3: 0.05}},
    }
    for tag, extra in scenarios.items():
        for seed in range(args.seeds):
            cfg = HistoExperimentConfig(
                steps=args.steps, n_train=args.n_train, noise=0.8,
                seed=seed, **extra)
            print(f"\n=== scenario {tag} (seed {seed}) "
                  f"steps={cfg.steps} ===")
            r = run_experiment(cfg)
            print(summarize(r))
            print("recovery of centralized AUC:",
                  [round(x, 2) for x in r["recovery"]])
            name = tag if seed == 0 else f"{tag}_seed{seed}"
            with open(os.path.join(OUT, f"{name}.json"), "w") as f:
                json.dump(r, f, indent=2, default=float)
    print(f"\nresults written to {OUT}/")


if __name__ == "__main__":
    main()
