"""A LoRA swarm of Granite-4.0-H: the program's session, its round blocks
and the work one round does.

The session is the program's own (`repro.experiments.lm_swarm`): the
registered ``granite-4.0-h-micro`` at the widths of the configuration
file, its train step and gate, the frozen base held once and passed to
each round beside the sites' stacked adapters. The window drives
`SwarmSession.round` on ``backend="engine"``: four sites on one chip.

The gate is checked by its numbers as well as by its decisions. The
harness's ``check.GATE_BAND`` is a band of AUC; the gates here score
next-token accuracy, which the program (bfloat16) and the reference
(float32) compute to within ``ACC_LIMIT``. A gate counts as flipped where
either accuracy it was decided on lies more than ``ACC_LIMIT`` from the
reference's, or where the decisions differ outside ``(1 + threshold) x
ACC_LIMIT`` of the threshold, the most by which accuracies that agree can
move the margin (PERF.md section 2 gives the readings).
"""
from __future__ import annotations

import jax
import numpy as np

from repro.configs import get_config
from repro.configs.base import SwarmConfig, TrainConfig
from repro.experiments import lm_swarm

from swarmbench import flops, flops_lm, reference_hybrid, text, traffic as gen

# the program's configuration fields, from the file's (HF's) keys
FIELDS = {"n_layers": "num_hidden_layers", "d_model": "hidden_size",
          "n_heads": "num_attention_heads",
          "n_kv_heads": "num_key_value_heads",
          "d_ff": "shared_intermediate_size", "vocab_size": "vocab_size",
          "norm_eps": "rms_norm_eps", "ssm_state": "mamba_d_state",
          "ssm_heads": "mamba_n_heads", "ssm_head_dim": "mamba_d_head",
          "ssm_expand": "mamba_expand", "ssm_chunk": "mamba_chunk_size",
          "conv_width": "mamba_d_conv", "ssm_groups": "mamba_n_groups",
          "embedding_multiplier": "embedding_multiplier",
          "residual_multiplier": "residual_multiplier",
          "logits_scaling": "logits_scaling",
          "attention_multiplier": "attention_multiplier",
          "tie_embeddings": "tie_word_embeddings",
          "param_dtype": "dtype", "compute_dtype": "dtype"}
# largest gap of a gate's accuracy, local or merged, between the program
# and the reference (PERF.md section 2)
ACC_LIMIT = 0.015
# what the program's mixers and MLP compute; anything else is refused
FIXED = {"hidden_act": "silu", "position_embedding_type": "nope",
         "mamba_conv_bias": True, "mamba_proj_bias": False,
         "attention_bias": False, "num_local_experts": 0,
         "normalization_function": "rmsnorm", "tie_word_embeddings": True}


def model_config(config: dict):
    """The registered model at the file's sizes and layer pattern."""
    for key, value in FIXED.items():
        if config[key] != value:
            raise ValueError(f"{key}={config[key]!r}: the program runs "
                             f"{value!r}")
    return get_config("granite-4.0-h-micro").replace(
        layer_kinds=tuple(config["layer_types"]),
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        **{f: config[k] for f, k in FIELDS.items()})


def train_config(config: dict) -> TrainConfig:
    t = config["train"]
    return TrainConfig(lr=t["lr"], warmup_steps=t["warmup_steps"],
                       max_steps=t["schedule_steps"],
                       weight_decay=t["weight_decay"], b1=t["b1"],
                       b2=t["b2"], eps=t["eps"], grad_clip=t["grad_clip"],
                       schedule="cosine")


def _host(tree):
    """Copies on the host: on the CPU a view would share the buffer that
    the next round's donation reuses."""
    return [np.array(a) for a in jax.tree.leaves(tree)]


class Cell:
    """One configuration under one traffic mix, built from ``seed``."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config, self.traffic = config, traffic
        if traffic["merge"] not in ("fedavg", "mean"):
            raise ValueError(f"no reference for merge {traffic['merge']!r}")
        if config["backend"] != "engine":
            raise ValueError(f"no cell runs backend {config['backend']!r}")
        self.model = model_config(config)
        self.train = train_config(config)
        self.swarm = SwarmConfig(
            n_nodes=config["n_sites"], sync_every=traffic["sync_every"],
            topology=traffic["topology"], merge=traffic["merge"],
            payload="lora", lora_rank=config["lora"]["rank"],
            lora_alpha=config["lora"]["alpha"],
            val_threshold=traffic["gate_threshold"], gate_metric="accuracy",
            self_weight=traffic["self_weight"], wire_dtype=traffic["wire"],
            wire_block=traffic["wire_block"])
        self.session = self.scored = None
        self.reset(seed)
        n, t, b = config["n_sites"], traffic["sync_every"], traffic["batch"]
        self.samples_per_round = n * t * b
        self.model_flops_per_round = flops_lm.round_flops(config, traffic)
        sizes = [a.size // n for a in jax.tree.leaves(self.session.state.params)]
        self.commit = flops.commit(sizes, n, traffic["wire"])

    def reset(self, seed: int) -> None:
        """Inputs, base and a fresh swarm state from ``seed``. The first
        call builds the session; later calls free the old base, then load
        the new base and state into it, so the compiled round is reused."""
        self.seed32 = gen.seed32(seed)
        self.inputs = text.build(self.config, self.traffic, seed)
        if self.session is not None:
            self.session.shared = None
        fresh = lm_swarm.lm_swarm_session(
            self.model, self.train, self.swarm, self.inputs["data_sizes"],
            seed=self.seed32)
        if self.session is None:
            self.session = fresh
        else:
            self.session.load_state(fresh.state)
            self.session.shared = fresh.shared
        self.val = jax.device_put(self.inputs["val"])

    def round(self, r: int):
        """Dispatch round ``r`` on pool block ``r``; returns its log."""
        pool = self.inputs["pool"]
        return self.session.round(pool[r % len(pool)], self.val)

    @staticmethod
    def read(log):
        """(gates [N], losses [T, N]) of a dispatched round, on the host."""
        gates, loss = jax.device_get((log["gates"], log["train"]["loss"]))
        return np.asarray(gates), np.asarray(loss)

    def check_rounds(self, rounds: int) -> dict:
        """Rounds 0..rounds-1 through the window's own call and feed, with
        what the correctness check reads: the adapters before and after,
        their first moment after round 1, every step's loss, the gates and
        the accuracies the gates were decided on (under ``auc``, the
        harness's name)."""
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
        self.scored = out["auc"]
        return out

    def free(self) -> None:
        """Drop the session, its base and its device state."""
        self.session = self.val = None

    def reference(self, rounds: int, *, follow=None, band=None,
                  **kw) -> dict:
        """The plain reference over the same inputs (reference_hybrid.py).
        With ``follow`` it is the check's reference for the run last
        compared (`check_rounds`, or a reference run without ``follow``):
        the gates' tie band is this cell's, not the harness's AUC ``band``,
        and a gate scored apart counts as flipped."""
        ref = lambda **more: reference_hybrid.run_rounds(  # noqa: E731
            self.config, self.traffic, self.inputs, self.seed32, rounds,
            **kw, **more)
        if follow is None:
            out = ref()
            self.scored = out["auc"]
            return out
        tie = (1 + self.traffic["gate_threshold"]) * ACC_LIMIT
        return ref(follow=follow, band=tie, scored=self.scored,
                   acc_limit=ACC_LIMIT)

    def describe(self) -> dict:
        return {"schedule": self.session.sync_schedule.name,
                "interpret": self.session.engine.interpret,
                "data_sizes": self.inputs["data_sizes"]}
