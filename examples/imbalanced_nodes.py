"""Merge-strategy & topology ablation under node imbalance (beyond-paper).

The paper uses FedAvg-weighted full merging. Its §2 survey *cites* Fisher and
gradient-matching merging as principled upgrades but never builds them — this
example does, comparing on the same biased-shard setup:

  fedavg/full    the paper's mechanism (faithful baseline)
  mean/full      unweighted averaging (the paper's strawman)
  fedavg/ring    sparse P2P gossip (TPU-native ppermute schedule)
  fisher/full    diagonal-Fisher-weighted merging
  gradmatch/full uncertainty-based gradient matching [Daheim et al., cited]

Also demonstrates DYNAMIC MEMBERSHIP: node 3 leaves the swarm mid-training
via ``session.leave(3)`` and re-joins later via ``session.join(3)`` (the
paper's §3.1 join/leave semantics — runtime state, not reconfiguration).

Each round — local steps, the in-graph AUC gate, the fused commit — runs as
one compiled `SwarmSession.round` call over stacked ``[T, N, ...]`` batches;
while node 3 is away it gets padding that its step leaves unapplied.

Note on fisher/gradmatch here: importance mass comes from the strategy's
in-graph Δθ² accumulation (no host-side Fisher loop). Because this example
trains with AdamW — whose step sizes are ~lr regardless of gradient scale —
that proxy is closer to update-activity weighting than exact curvature, so
fisher/gradmatch land nearer fedavg than they would with true squared-grad
Fishers (a train step returning its grads as a fourth value supplies those;
see `SwarmEngine.local_steps`).

Run:  PYTHONPATH=src python examples/imbalanced_nodes.py [--steps 150]
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import SwarmConfig, TrainConfig
from repro.core.session import SwarmSession
from repro.data import batches, make_histo_dataset, shard_to_nodes
from repro.metrics import gate_metric_fn
from repro.models.cnn import bce_loss, forward_cnn, init_cnn
from repro.optim import adamw_init, adamw_update


def run(swarm_cfg, steps, dynamic=False, seed=0):
    imgs, labels = make_histo_dataset(1200, size=24, noise=0.8,
                                      class_probs=(0.5, 0.3, 0.2), seed=seed)
    test_x, test_y = make_histo_dataset(400, size=24, noise=0.8,
                                        class_probs=(0.5, 0.3, 0.2),
                                        seed=seed + 99)
    # class-biased shards: each node sees a skewed class mix
    shards = shard_to_nodes(imgs, labels, [120, 360, 360, 360], seed=seed,
                            class_bias=[[5, 1, 1], [1, 5, 1], [1, 1, 5],
                                        [1, 1, 1]])
    tc = TrainConfig(lr=1e-3, weight_decay=1e-4)

    def loss(params, x, y):
        return bce_loss(forward_cnn(params, x), jax.nn.one_hot(y, 3))

    def train_step(params, opt, batch, step):
        x, y, live = batch   # live False: padding, the step is not applied
        l, g = jax.value_and_grad(loss)(params, x, y)
        new_params, new_opt = adamw_update(params, g, opt, tc, 1e-3)
        keep = lambda new, old: jnp.where(live, new, old)
        return (jax.tree.map(keep, new_params, params),
                jax.tree.map(keep, new_opt, opt), {"loss": l})

    auc = gate_metric_fn("auc")

    def eval_fn(params, val):
        x, y = val
        return auc(jax.nn.sigmoid(forward_cnn(params, x)), y)

    params = init_cnn(jax.random.key(42), None, growth=8, stem=16,
                      feat_dim=96, hidden=32)
    sw = SwarmSession(swarm_cfg, train_step, eval_fn,
                      params=params, opt_state=adamw_init(params),
                      data_sizes=[len(s[1]) for s in shards])

    rngs = [np.random.default_rng(seed * 10 + i) for i in range(4)]
    iters = [iter(()) for _ in range(4)]
    vals = tuple(jnp.asarray(np.stack([s[k][:48] for s in shards]))
                 for k in (0, 1))
    pad = (np.zeros_like(shards[0][0][:16]), np.zeros_like(shards[0][1][:16]))
    t = swarm_cfg.sync_every
    for round_start in range(0, steps, t):
        if dynamic:  # node 3 leaves at 1/3, rejoins at 2/3 of the run
            if steps // 3 <= round_start < 2 * steps // 3:
                sw.leave(3)
            else:
                sw.join(3)
        active = sw.active
        round_batches = []
        for _ in range(min(t, steps - round_start)):
            bs = []
            for i, s in enumerate(shards):
                if not active[i]:
                    bs.append(pad + (False,))
                    continue
                try:
                    b = next(iters[i])
                except StopIteration:
                    iters[i] = batches(s[0], s[1], 16, rngs[i])
                    b = next(iters[i])
                bs.append(b + (True,))
            round_batches.append(bs)
        # [T][N] (x, y, live) -> [T, N, ...] per field; fisher/gradmatch
        # importance mass accumulates inside the round via the configured
        # MergeStrategy
        sw.round(tuple(jnp.asarray(np.array([[b[k] for b in bs]
                                             for bs in round_batches]))
                       for k in range(3)), vals)

    score = jax.jit(lambda p, x, y: auc(jax.nn.sigmoid(forward_cnn(p, x)), y))
    return [float(score(p, test_x, test_y)) for p in sw.node_params]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=150)
    args = ap.parse_args()

    settings = [
        ("fedavg/full (paper)", SwarmConfig(n_nodes=4, sync_every=15,
         topology="full", merge="fedavg", lora_only=False)),
        ("mean/full", SwarmConfig(n_nodes=4, sync_every=15, topology="full",
         merge="mean", lora_only=False)),
        ("fedavg/ring (P2P)", SwarmConfig(n_nodes=4, sync_every=15,
         topology="ring", merge="fedavg", lora_only=False)),
        ("fisher/full", SwarmConfig(n_nodes=4, sync_every=15, topology="full",
         merge="fisher", lora_only=False)),
        ("gradmatch/full", SwarmConfig(n_nodes=4, sync_every=15,
         topology="full", merge="gradmatch", lora_only=False)),
    ]
    print(f"{'setting':22s}  node AUCs (scarce node first)        mean")
    for name, cfg in settings:
        aucs = run(cfg, args.steps)
        print(f"{name:22s}  {[round(a, 3) for a in aucs]}  {np.mean(aucs):.3f}")

    aucs = run(SwarmConfig(n_nodes=4, sync_every=15, topology="dynamic",
                           merge="fedavg", lora_only=False),
               args.steps, dynamic=True)
    print(f"{'dynamic membership':22s}  {[round(a, 3) for a in aucs]}  "
          f"{np.mean(aucs):.3f}   (node 3 left & re-joined)")


if __name__ == "__main__":
    main()
