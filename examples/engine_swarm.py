"""The compiled swarm session in ~50 lines.

Where quickstart.py calls `SwarmSession.round` once a round, this example
hands the whole P2P-SL schedule to `SwarmSession.run_rounds` on the default
engine backend: every round — `sync_every` vmapped local steps, in-graph
validation of local and merged params, the 80% gate, and the fused Pallas
commit — is part of ONE compiled program; rounds are scanned with zero
host round-trips. Mid-run membership changes
(`session.leave` / `session.join`) are pure state updates: the second
`run_rounds` call below reuses the already-compiled round.

Run:  PYTHONPATH=src python examples/engine_swarm.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, SwarmConfig, TrainConfig
from repro.core.session import SwarmSession
from repro.data import make_lm_stream
from repro.launch.train import make_train_step
from repro.models import build_model
from repro.optim import adamw_init


def main():
    n_nodes, rounds, sync_every, batch, seq = 4, 3, 5, 8, 32
    cfg = ModelConfig(name="tiny", n_layers=2, d_model=64, n_heads=4,
                      n_kv_heads=2, d_ff=128, vocab_size=256)
    model = build_model(cfg)
    base_step = make_train_step(model, TrainConfig(lr=3e-3, remat=False,
                                                   warmup_steps=2,
                                                   max_steps=rounds * sync_every))

    # heterogeneous local shards: topic-biased token streams per node
    streams = [make_lm_stream(128, seq, cfg.vocab_size, seed=i, topic_bias=1.0)
               for i in range(n_nodes)]
    rng = np.random.default_rng(0)

    def block(count):  # [rounds, T, N, B, S] stacked batch schedule
        # one index draw per node, shared by every key (tokens/labels pair up)
        idx = [rng.integers(0, len(s["tokens"]), (rounds, count, batch))
               for s in streams]
        return {k: jnp.asarray(np.stack([s[k][i] for s, i
                                         in zip(streams, idx)], axis=2))
                for k in streams[0]}

    vals = {k: jnp.asarray(np.stack([s[k][:8] for s in streams]))
            for k in streams[0]}
    params = model.init(jax.random.key(0))

    session = SwarmSession(
        SwarmConfig(n_nodes=n_nodes, sync_every=sync_every, topology="full",
                    merge="fedavg", lora_only=False, val_threshold=0.8),
        lambda p, o, b, s: base_step(p, o, b),
        lambda p, v: 1.0 / (1.0 + model.loss_fn(p, v, remat=False)[0]),
        params=params, opt_state=adamw_init(params),
        data_sizes=[len(s["tokens"]) for s in streams])

    logs = session.run_rounds(block(sync_every), vals)
    losses = np.asarray(logs["train"]["loss"])     # [rounds, T, N]
    for r in range(rounds):
        print(f"round {r}: loss={[f'{l:.3f}' for l in losses[r, -1]]} "
              f"gates={np.asarray(logs['gates'][r]).astype(bool).tolist()}")

    # dynamic membership: node 3 drops out; the SAME compiled round serves
    # the new configuration (active mask is runtime data, zero retraces)
    session.leave(3)
    logs = session.run_rounds(block(sync_every), vals)
    print(f"node 3 left: gates={np.asarray(logs['gates'][-1]).tolist()} "
          f"(round {int(session.state.round)}, step {int(session.state.step)})")
    session.join(3)
    print("OK — every round above ran as one compiled session call.")


if __name__ == "__main__":
    main()
