"""The paper's DenseNet swarm: the program's session, its round blocks and
the work one round does.

The session is the program's own: its train step from
`repro.experiments.histo._make_model_fns` and the session (weights, AdamW
state, AUC gate) from `repro.experiments.histo._swarm_session`. The window
drives `SwarmSession.round` on ``backend="engine"``: every site vmapped on
one chip.
"""
from __future__ import annotations

import jax
import numpy as np

from repro.configs.base import SwarmConfig
from repro.experiments import histo

from swarmbench import flops, reference, traffic as gen


def program_train_step(ecfg):
    """The program's jitted local step (the one the session vmaps)."""
    return histo._make_model_fns(ecfg)[0]


def _host(tree):
    return [np.asarray(a) for a in jax.tree.leaves(tree)]


class Cell:
    """One configuration under one traffic mix, built from ``seed``."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config, self.traffic = config, traffic
        if traffic["merge"] not in ("fedavg", "mean"):
            raise ValueError(f"no reference for merge {traffic['merge']!r}")
        if config["backend"] != "engine":
            raise ValueError(f"no cell runs backend {config['backend']!r}")
        self.swarm = SwarmConfig(
            n_nodes=config["n_sites"], sync_every=traffic["sync_every"],
            topology=traffic["topology"], merge=traffic["merge"],
            lora_only=False, val_threshold=traffic["gate_threshold"],
            gate_metric="auc", self_weight=traffic["self_weight"],
            wire_dtype=traffic["wire"], wire_block=traffic["wire_block"])
        self.session = None
        self.reset(seed)
        n, t, b = config["n_sites"], traffic["sync_every"], traffic["batch"]
        self.samples_per_round = n * t * b
        self.model_flops_per_round = (
            self.samples_per_round * flops.train_image(config)
            + 2 * self.inputs["valid_val"] * flops.forward_image(config))
        sizes = [a.size // n for a in jax.tree.leaves(self.session.state.params)]
        self.commit = flops.commit(sizes, n, traffic["wire"])

    def _ecfg(self, seed32: int):
        c = self.config
        return histo.HistoExperimentConfig(
            image_size=c["image_size"], growth=c["growth"], stem=c["stem"],
            feat_dim=c["feat_dim"], hidden=c["hidden"],
            n_blocks=c["n_blocks"], layers_per_block=c["layers_per_block"],
            lr=c["train"]["lr"], steps=c["train"]["schedule_steps"],
            batch_size=self.traffic["batch"],
            sync_every=self.traffic["sync_every"], seed=seed32,
            swarm=self.swarm)

    def reset(self, seed: int) -> None:
        """Inputs and a fresh swarm state from ``seed``. The first call
        builds the session; later calls load the new state into it, so the
        compiled round is reused."""
        self.seed32 = gen.seed32(seed)
        self.inputs = gen.build(self.config, self.traffic, seed)
        ecfg = self._ecfg(self.seed32)
        sizes = [(None, np.empty(s)) for s in self.inputs["data_sizes"]]
        fresh = histo._swarm_session(ecfg, program_train_step(ecfg), sizes,
                                     self.swarm)
        if self.session is None:
            self.session = fresh
        else:
            self.session.load_state(fresh.state)
        self.val = jax.device_put(self.inputs["val"])

    def round(self, r: int):
        """Dispatch round ``r`` on pool block ``r``; returns its log."""
        pool = self.inputs["pool"]
        return self.session.round(pool[r % len(pool)], self.val)

    @staticmethod
    def read(log):
        """(gates [N], losses [T, N]) of a dispatched round, on the host:
        what a trainer that logs each commit reads."""
        gates, loss = jax.device_get((log["gates"], log["train"]["loss"]))
        return np.asarray(gates), np.asarray(loss)

    def check_rounds(self, rounds: int) -> dict:
        """Rounds 0..rounds-1 through the window's own call and feed, with
        what the correctness check reads: the params before and after, the
        first moment after round 1, every step's loss, the gates and the
        AUCs the gates were decided on."""
        out = {"params0": _host(self.session.state.params)}
        logs = []
        for r in range(rounds):
            logs.append(self.round(r))
            if r == 0:
                out["mu1"] = _host(self.session.state.opt_state["mu"])
        out["params"] = _host(self.session.state.params)
        out["losses"] = np.stack([np.asarray(g["train"]["loss"])
                                  for g in logs])
        out["gates"] = np.stack([self.read(g)[0] for g in logs])
        out["auc"] = np.stack([np.stack(jax.device_get(
            (g["metric_local"], g["metric_merged"])), -1) for g in logs])
        return out

    def free(self) -> None:
        """Drop the session and its device state."""
        self.session = self.val = None

    def reference(self, rounds: int, **kw) -> dict:
        """The plain reference over the same inputs (see reference.py)."""
        return reference.run_rounds(self.config, self.traffic, self.inputs,
                                    self.seed32, rounds, **kw)

    def describe(self) -> dict:
        return {"schedule": self.session.sync_schedule.name,
                "interpret": self.session.engine.interpret,
                "data_sizes": self.inputs["data_sizes"]}

