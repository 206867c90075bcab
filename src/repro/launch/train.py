"""Train-step builders: standard synchronous data-parallel (the centralized
baseline) and the swarm-parallel variant (the paper's technique as SPMD).

Swarm-parallel = ``jax.vmap`` of the local step over a leading node axis
(sharded over the mesh's gossip axis) — gradients never cross node slices —
plus a periodic gossip sync step built from `repro.core`.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import SwarmConfig, TrainConfig
from repro.core.engine import SwarmEngine, gate_decisions, gated_commit
from repro.models import Model
from repro.optim import adamw_init, adamw_update, make_schedule


def make_train_step(model: Model, tc: TrainConfig,
                    grad_shardings=None) -> Callable:
    """(params, opt_state, batch) -> (params, opt_state, metrics).

    grad_shardings: optional pytree of NamedShardings matching params. Without
    it GSPMD leaves large gradient accumulators (e.g. the [V, d] embedding
    grad) replicated over the model axis — pinning grads to the param sharding
    removed ~25 GiB/device of f32 temp on command-r-104B (§Perf iteration 2).
    """
    schedule = make_schedule(tc)

    def grads_of(params, batch):
        def loss(p):
            return model.loss_fn(p, batch, remat=tc.remat)
        return jax.value_and_grad(loss, has_aux=True)(params)

    def train_step(params, opt_state, batch):
        if tc.accum_steps > 1:
            # microbatching: scan over [A, B/A, ...] slices accumulating f32
            # grads — live activation memory scales with B/A, not B
            a = tc.accum_steps
            micro = jax.tree.map(
                lambda x: x.reshape((a, x.shape[0] // a) + x.shape[1:]), batch)

            def body(carry, mb):
                acc, lsum = carry
                (l, _), g = grads_of(params, mb)
                acc = jax.tree.map(
                    lambda A, G: A + G.astype(jnp.float32) / a, acc, g)
                return (acc, lsum + l / a), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, l), _ = jax.lax.scan(body, (zeros, jnp.float32(0.0)), micro)
            metrics = {"xent": l, "aux": jnp.float32(0.0)}
        else:
            (l, metrics), grads = grads_of(params, batch)
        if grad_shardings is not None:
            grads = jax.lax.with_sharding_constraint(grads, grad_shardings)
        lr = schedule(opt_state["count"])
        params, opt_state = adamw_update(params, grads, opt_state, tc, lr)
        metrics = dict(metrics, loss=l, lr=lr)
        return params, opt_state, metrics

    return train_step


def make_eval_step(model: Model) -> Callable:
    def eval_step(params, batch):
        loss, metrics = model.loss_fn(params, batch, remat=False)
        return dict(metrics, loss=loss)

    return eval_step


def init_train_state(model: Model, key):
    params = model.init(key)
    return params, adamw_init(params)


# ---------------------------------------------------------------------------
# swarm-parallel (SPMD) — the paper's technique on the mesh
# ---------------------------------------------------------------------------

def make_swarm_train_step(model: Model, tc: TrainConfig) -> Callable:
    """vmapped local step: stacked (params, opt_state) with leading node axis,
    batch [N, local_B, ...]. Gradient reduction stays within each node slice."""
    local = make_train_step(model, tc)
    return jax.vmap(local, in_axes=(0, 0, 0), out_axes=(0, 0, 0))


def make_swarm_sync_step(swarm_cfg: SwarmConfig, mesh, axis: str,
                         data_sizes, param_specs=None) -> Callable:
    """Gossip sync: propose (collective merge) + commit (validation-gated
    select), both delegating to the shared `SwarmEngine` gossip backend.

    Returns propose_fn(stacked_params) -> candidate. Ring topology uses
    ppermute (sparse P2P, the TPU-native schedule); full/fedavg uses psum;
    dynamic uses the all_gather mixing matrix with a runtime membership mask.
    """
    engine = SwarmEngine(swarm_cfg, None, None, data_sizes=data_sizes,
                         backend="gossip", mesh=mesh, axis=axis,
                         param_specs=param_specs)

    def propose(stacked_params, active=None, fishers=None, stats=None):
        candidate, _, _ = engine.propose(stacked_params, active=active,
                                         fishers=fishers, stats=stats)
        return candidate

    def commit(candidate, local_params, metric_merged, metric_local):
        gates = gate_decisions(metric_merged, metric_local,
                               swarm_cfg.val_threshold)
        return gated_commit(candidate, local_params, gates)

    return propose, commit


# ---------------------------------------------------------------------------
# CLI launcher:  python -m repro.launch.train --arch minicpm-2b --smoke ...
# ---------------------------------------------------------------------------

def main():
    import argparse
    import time

    from repro.checkpointing import save_json, save_pytree
    from repro.configs import get_config, smoke_variant
    from repro.core.lora import inject_lora
    from repro.data import make_lm_stream
    from repro.launch.compile_cache import use_compile_cache
    from repro.models import build_model
    from repro.optim import EarlyStopper

    ap = argparse.ArgumentParser(description="P2P-SL trainer")
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family variant (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--swarm-nodes", type=int, default=0,
                    help="0 = plain training; N = P2P-SL with N nodes")
    ap.add_argument("--sync-every", type=int, default=10)
    ap.add_argument("--topology", default="ring",
                    choices=["ring", "full", "dynamic"])
    ap.add_argument("--merge", default="fedavg",
                    choices=["mean", "fedavg", "fisher", "gradmatch"])
    ap.add_argument("--lora", action="store_true",
                    help="LoRA-adapter-only peer payloads (paper §3.2)")
    ap.add_argument("--wire-dtype", default="f32",
                    choices=["f32", "bf16", "int8"],
                    help="sync wire compression (core.comms): int8 = "
                         "error-feedback quantized deltas")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--resume", default="",
                    help="resume a swarm run from a session checkpoint "
                         "(session.msgpack written by --ckpt-dir)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)
    if cfg.is_encdec or cfg.family == "vlm":
        raise SystemExit("CLI LM trainer supports decoder-only families; "
                         "use examples/ for vlm/audio drivers")
    model = build_model(cfg)
    tc = TrainConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                     max_steps=args.steps, remat=False)
    base_step = make_train_step(model, tc)
    n_nodes = max(args.swarm_nodes, 1)
    streams = [make_lm_stream(256, args.seq, cfg.vocab_size,
                              seed=args.seed + i, topic_bias=1.0)
               for i in range(n_nodes)]
    stopper = EarlyStopper(patience=5, mode="min")
    rng = np.random.default_rng(args.seed)
    t0 = time.time()
    final_step, sync_log = 0, []

    if not args.swarm_nodes:  # plain single-learner training
        jit_step = jax.jit(base_step)
        p = model.init(jax.random.key(args.seed))
        o = adamw_init(p)
        s = streams[0]
        for step in range(args.steps):
            idx = rng.integers(0, len(s["tokens"]), args.batch)
            p, o, m = jit_step(p, o, {k: jnp.asarray(v[idx])
                                      for k, v in s.items()})
            final_step = step + 1
            if step % 20 == 0 or step == args.steps - 1:
                loss = float(m["loss"])
                print(f"step {final_step:4d} loss={loss:.3f} "
                      f"({(time.time()-t0)/final_step:.2f}s/step)")
                if stopper.update(loss):
                    print("early stop (patience exhausted)")
                    break
        node_params = [p]
    else:  # P2P-SL: one SwarmSession, one compiled call per round
        from repro.core.session import SwarmSession

        ps = []
        for i in range(n_nodes):
            p = model.init(jax.random.key(args.seed))
            if args.lora:
                p = inject_lora(p, jax.random.key(args.seed + 1 + i), rank=8)
            ps.append(p)

        def train_step(params, opt_state, batch, step):
            return base_step(params, opt_state, batch)

        def eval_fn(params, val):
            loss, _ = model.loss_fn(params, val, remat=False)
            return 1.0 / (1.0 + loss)

        scfg = SwarmConfig(n_nodes=n_nodes, sync_every=args.sync_every,
                           topology=args.topology, merge=args.merge,
                           lora_only=args.lora, wire_dtype=args.wire_dtype)
        # fisher/gradmatch importance accumulators live inside the session's
        # SwarmState — estimation is in-graph, no host-side Fisher loop
        sess = SwarmSession(scfg, train_step, eval_fn, params=ps,
                            opt_state=[adamw_init(p) for p in ps],
                            seed=args.seed,
                            data_sizes=[len(s["tokens"]) for s in streams])
        print(f"sync schedule: "
              f"{sess.sync_schedule.describe(sess.payload_params)}")
        if args.resume:
            sess.load(args.resume)
            final_step = int(sess.state.step)
            print(f"resumed from {args.resume} at step {final_step} "
                  f"(round {int(sess.state.round)})")
        vals = {k: jnp.asarray(np.stack([s[k][:8] for s in streams]))
                for k in streams[0]}

        def draw(count):  # [count, N, B, S] stacked batch block
            # one index draw per node, shared by every key — tokens and
            # labels rows are paired within a sequence
            idx = [rng.integers(0, len(s["tokens"]), (count, args.batch))
                   for s in streams]
            return {k: jnp.asarray(np.stack([s[k][i] for s, i
                                             in zip(streams, idx)], axis=1))
                    for k in streams[0]}

        last_check = 0  # keep the old loop's every-20-steps stopper cadence
        while final_step < args.steps:
            t = min(max(args.sync_every, 1), args.steps - final_step)
            block = draw(t)
            if t == args.sync_every:  # full round: local steps + gated sync
                out = sess.round(block, vals)
                losses = np.asarray(out["train"]["loss"])[-1]
                gates = np.asarray(out["gates"]).astype(bool).tolist()
                sync_log.append({
                    "step": final_step + t, "gates": gates,
                    "metric_local": np.asarray(out["metric_local"]).tolist(),
                    "metric_merged": np.asarray(out["metric_merged"]).tolist()})
                extra = f" sync gates={gates}"
            else:  # remainder steps, no sync
                tm = sess.run_local(block)
                losses = np.asarray(tm["loss"])[-1]
                extra = ""
            final_step += t
            print(f"step {final_step:4d} loss={['%.3f' % l for l in losses]} "
                  f"({(time.time()-t0)/final_step:.2f}s/step){extra}")
            if final_step - last_check >= 20 or final_step >= args.steps:
                last_check = final_step
                if stopper.update(float(np.mean(losses))):
                    print("early stop (patience exhausted)")
                    break
        node_params = sess.node_params
        if args.ckpt_dir:  # full session state: checkpoint/resume round-trip
            sess.save(f"{args.ckpt_dir}/session.msgpack")

    if args.ckpt_dir:
        for i, p in enumerate(node_params):
            save_pytree(f"{args.ckpt_dir}/node{i}.msgpack", p,
                        metadata={"arch": cfg.name, "step": final_step})
        save_json(f"{args.ckpt_dir}/sync_log.json", sync_log)
        print(f"checkpoints -> {args.ckpt_dir}")


if __name__ == "__main__":
    main()
