"""Quickstart: the P2P-SL framework in ~60 lines.

Builds a reduced LM, trains a 4-node swarm on heterogeneous token streams
with LoRA-only peer exchanges, and prints per-round gates. Each round —
``sync_every`` local steps, the in-graph validation gate and the fused
commit — runs as one compiled `SwarmSession.round` call, so the train step
and the eval are traceable and the batches are stacked ``[T, N, ...]``
arrays. engine_swarm.py scans several rounds per call.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import SwarmConfig, TrainConfig, get_config, smoke_variant
from repro.core.lora import inject_lora
from repro.core.session import SwarmSession
from repro.data import make_lm_stream
from repro.launch.train import make_train_step
from repro.models import build_model
from repro.optim import adamw_init


def main():
    # 1. pick an assigned architecture, reduced for CPU
    cfg = smoke_variant(get_config("minicpm-2b"))
    model = build_model(cfg)
    tc = TrainConfig(lr=3e-3, remat=False, warmup_steps=5, max_steps=200)
    base_step = make_train_step(model, tc)

    def train_step(params, opt_state, batch, step):
        return base_step(params, opt_state, batch)

    def eval_fn(params, val):
        loss, _ = model.loss_fn(params, val, remat=False)
        return 1.0 / (1.0 + loss)  # higher = better, in-graph

    # 2. four nodes, shared pre-trained-style init, LoRA adapters injected
    base = model.init(jax.random.key(0))
    node_params = [inject_lora(base, jax.random.key(i + 1), rank=8)
                   for i in range(4)]

    swarm = SwarmSession(
        SwarmConfig(n_nodes=4, sync_every=10, topology="ring",
                    merge="fedavg", lora_only=True, val_threshold=0.8),
        train_step, eval_fn,
        params=node_params, opt_state=[adamw_init(p) for p in node_params],
        data_sizes=[100, 300, 300, 300])

    # 3. heterogeneous local data (topic-biased token streams)
    streams = [make_lm_stream(64, 32, cfg.vocab_size, seed=i, topic_bias=1.0)
               for i in range(4)]
    rng = np.random.default_rng(0)
    vals = {k: jnp.asarray(np.stack([s[k][:8] for s in streams]))
            for k in streams[0]}                          # [N, 8, S]

    def draw(steps):  # [T, N, 8, S]: one index draw per node and step
        idx = [rng.integers(0, 64, (steps, 8)) for _ in streams]
        return {k: jnp.asarray(np.stack([s[k][i] for s, i
                                         in zip(streams, idx)], axis=1))
                for k in streams[0]}

    # 4. train + gossip: each round = sync_every local steps + gated merge
    for r in range(5):
        log = swarm.round(draw(10), vals)
        merged = np.asarray(log["metric_merged"])
        print(f"round {r}: gates={np.asarray(log['gates']).tolist()} "
              f"merged-metric={[round(float(m), 4) for m in merged]}")

    for i, p in enumerate(swarm.node_params):
        loss, _ = model.loss_fn(p, jax.tree.map(lambda v: v[i], vals),
                                remat=False)
        print(f"node {i}: final val loss = {float(loss):.3f}")
    print("OK — swarm training with LoRA-only P2P sync complete.")


if __name__ == "__main__":
    main()
