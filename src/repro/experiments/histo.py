"""The paper's experimental protocol (§4), reproducible end-to-end.

Builds the 4-node P2P-SL swarm over synthetic histopathology shards and
compares, exactly as the paper does:
  * centralized "full-data" baseline,
  * standalone (local-only) per-node models,
  * P2P-SL swarm-trained per-node models,
under the unbalanced 10/30/30/30 split and the 25%/5% scarcity trials,
reporting AUC / sensitivity / specificity / F1 on a shared held-out test set,
plus the embedding-quality (Davies-Bouldin) and minority-recall claims.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.configs.base import SwarmConfig, TrainConfig
from repro.core.session import SwarmSession
from repro.data import (augment, batches, make_histo_dataset, paper_splits,
                        shard_to_nodes)
from repro.metrics import classify_report, davies_bouldin, gate_metric_fn
from repro.models.cnn import (TRAIN_FOLD_SHARE, bce_loss, forward_cnn,
                              forward_cnn_sites, init_cnn)
from repro.optim import EarlyStopper, adamw_init, adamw_update, make_schedule


@dataclass
class HistoExperimentConfig:
    n_train: int = 2000
    n_test: int = 500
    image_size: int = 24
    noise: float = 1.1               # tuned so AUCs land in the paper's band
    class_probs: tuple = (0.5, 0.3, 0.2)  # imbalanced classes (minority = 2)
    fractions: tuple = (0.10, 0.30, 0.30, 0.30)
    scarcity: Optional[Dict[int, float]] = None  # e.g. {2: 0.25} / {3: 0.05}
    steps: int = 240
    batch_size: int = 16
    lr: float = 1e-3
    sync_every: int = 20             # ≈ paper's every-3-epochs cadence
    val_frac: float = 0.25
    seed: int = 0
    swarm: SwarmConfig = field(default_factory=lambda: SwarmConfig(
        n_nodes=4, sync_every=20, topology="full", merge="fedavg",
        lora_only=False, val_threshold=0.8, gate_metric="auc"))
    # small CNN (paper arch scaled to 24px inputs for CPU)
    growth: int = 8
    stem: int = 16
    feat_dim: int = 96
    hidden: int = 32
    n_blocks: int = 4           # paper: 4 encoder modules × 4 layers; tests
    layers_per_block: int = 4   # shrink these to bound XLA compile time


def _make_model_fns(ecfg: HistoExperimentConfig):
    tc = TrainConfig(lr=ecfg.lr, warmup_steps=20, max_steps=ecfg.steps,
                     weight_decay=1e-4, schedule="cosine")
    sched = make_schedule(tc)

    def loss(params, x, y):
        return bce_loss(forward_cnn(params, x), jax.nn.one_hot(y, 3))

    def site_losses(params, x, y):
        logits = forward_cnn_sites(params, x, fold_share=TRAIN_FOLD_SHARE)
        losses = jax.vmap(bce_loss)(logits, jax.nn.one_hot(y, 3))
        return losses.sum(), losses

    def update(params, g, opt_state):
        return adamw_update(params, g, opt_state, tc,
                            sched(opt_state["count"]))

    @jax.jit
    def train_step(params, opt_state, batch, step):
        x, y = batch
        l, g = jax.value_and_grad(loss)(params, jnp.asarray(x), jnp.asarray(y))
        params, opt_state = update(params, g, opt_state)
        return params, opt_state, {"loss": l}

    @jax.jit
    def train_step_sites(params, opt_state, batch, step):
        """`train_step` of N sites stacked on a leading axis, on the
        site-folded forward: the gradient of the sum of the sites' losses
        is each site's own gradient, and AdamW (its clipping included) runs
        per site."""
        x, y = batch
        (_, l), g = jax.value_and_grad(site_losses, has_aux=True)(
            params, jnp.asarray(x), jnp.asarray(y))
        params, opt_state = jax.vmap(update)(params, g, opt_state)
        return params, opt_state, {"loss": l}

    # the engine runs this in place of vmap(train_step) where one device
    # holds several sites (`SwarmEngine`); alone, train_step stays per site
    train_step.stacked = train_step_sites

    @jax.jit
    def predict(params, x):
        return jax.nn.sigmoid(forward_cnn(params, jnp.asarray(x)))

    @jax.jit
    def features(params, x):
        _, f = forward_cnn(params, jnp.asarray(x), return_features=True)
        return f

    return train_step, predict, features


def _init_params(ecfg, key):
    return init_cnn(key, None, growth=ecfg.growth, stem=ecfg.stem,
                    feat_dim=ecfg.feat_dim, hidden=ecfg.hidden,
                    n_blocks=ecfg.n_blocks,
                    layers_per_block=ecfg.layers_per_block)


def _batch_stream(ecfg, trains):
    """Precompute the per-node minibatch stream as stacked arrays.

    Returns (xs [steps, N, B, H, W, C], ys [steps, N, B]). Nodes whose shard
    can serve a full batch keep the exact per-node epoch iterators the host
    loop used (identical data order); a node with fewer than B samples — the
    extreme-scarcity trials — draws B samples with replacement per step
    instead of shrinking every other node's batch (vmap needs one B).
    """
    n = len(trains)
    bs = min(ecfg.batch_size, max(len(y) for _, y in trains))
    rngs = [np.random.default_rng(ecfg.seed * 100 + i) for i in range(n)]
    iters = [iter(()) for _ in range(n)]
    h = trains[0][0].shape[1]
    xs = np.empty((ecfg.steps, n, bs, h, h, 3), np.float32)
    ys = np.empty((ecfg.steps, n, bs), np.int32)
    for s in range(ecfg.steps):
        for i, (x, y) in enumerate(trains):
            if len(y) < bs:  # tiny shard: resample with replacement
                idx = rngs[i].integers(0, len(y), bs)
                xs[s, i], ys[s, i] = augment(x[idx], rngs[i]), y[idx]
                continue
            try:
                b = next(iters[i])
            except StopIteration:
                iters[i] = batches(x, y, bs, rngs[i])
                b = next(iters[i])
            xs[s, i], ys[s, i] = b
    return xs, ys


def _stack_vals(vals):
    """Pad per-node validation sets to a common length + validity mask."""
    n = len(vals)
    vmax = max(len(y) for _, y in vals)
    h = vals[0][0].shape[1]
    vx = np.zeros((n, vmax, h, h, 3), np.float32)
    vy = np.zeros((n, vmax), np.int32)
    vm = np.zeros((n, vmax), bool)
    for i, (x, y) in enumerate(vals):
        vx[i, :len(y)], vy[i, :len(y)], vm[i, :len(y)] = x, y, True
    return vx, vy, vm


def _swarm_session(ecfg, train_step, shards, swarm_cfg=None, **session_kw):
    """The `SwarmSession` that `_train_loop` trains: every site starts from
    the same init (warm-start effect) with fresh AdamW state, and its gate
    scores ``cfg.gate_metric`` on the site's validation split. Engine
    backend unless ``session_kw`` names another, e.g.
    ``backend="gossip", mesh=mesh, axis="node"`` for one site per device.
    Without ``swarm_cfg`` the sites never sync (isolated local learners)."""
    cfg = swarm_cfg or SwarmConfig(n_nodes=len(shards), sync_every=10**9,
                                   gate_metric="auc")
    metric = gate_metric_fn(cfg.gate_metric)

    def eval_fn(p, v):
        x, y, m = v
        return metric(jax.nn.sigmoid(forward_cnn(p, x)), y, m)

    def eval_sites(p, v):  # every site at once, as train_step.stacked
        x, y, m = v
        return jax.vmap(metric)(jax.nn.sigmoid(forward_cnn_sites(p, x)), y, m)

    eval_fn.stacked = eval_sites

    with tracing.span("session.build"):
        params = _init_params(ecfg, jax.random.key(ecfg.seed + 42))
        return SwarmSession(cfg, train_step, eval_fn, params=params,
                            opt_state=adamw_init(params), seed=ecfg.seed,
                            data_sizes=[len(y) for _, y in shards],
                            **session_kw)


def _train_loop(ecfg, train_step, shards, *, swarm_cfg=None, log=None,
                session=None):
    """Train nodes (swarm if swarm_cfg else isolated). Returns node params.
    ``log``, when given, is a list the sync records are appended to.
    ``session``, when given, is a `_swarm_session` to train in place of a
    new engine-backend one (its config then stands for ``swarm_cfg``).

    Runs on `SwarmSession`: the whole sync round —
    `sync_every` vmapped local steps, the in-graph gate metric selected by
    ``swarm.gate_metric`` (sort-based AUC by default), fused Pallas commit —
    is one compiled program; `run_rounds` scans over rounds with zero host
    round-trips. The swarm config's merge method (including fisher/gradmatch
    with in-graph importance accumulation) and `overlap_sync` double-buffered
    rounds are handled entirely inside the session's compiled drivers.
    """
    sess = session or _swarm_session(ecfg, train_step, shards, swarm_cfg)
    cfg = sess.cfg

    vals, trains = [], []
    for x, y in shards:
        n_val = max(8, int(len(y) * ecfg.val_frac))
        vals.append((x[:n_val], y[:n_val]))
        trains.append((x[n_val:], y[n_val:]))

    xs, ys = _batch_stream(ecfg, trains)
    val = _stack_vals(vals)

    sync_log = []
    if cfg.sync_every > ecfg.steps:
        sess.run_local((xs, ys))
    else:
        t = cfg.sync_every
        rounds = ecfg.steps // t
        # host arrays: the session places them (one site per device on the
        # gossip backend) without staging the whole stream on one device
        head = (xs[:rounds * t].reshape((rounds, t) + xs.shape[1:]),
                ys[:rounds * t].reshape((rounds, t) + ys.shape[1:]))
        logs = sess.run_rounds(head, val)
        if ecfg.steps % t:
            sess.run_local((xs[rounds * t:], ys[rounds * t:]))
        gates = np.asarray(logs["gates"])
        ml = np.asarray(logs["metric_local"])
        mm = np.asarray(logs["metric_merged"])
        loss = np.asarray(logs["train"]["loss"]).mean(axis=(1, 2))
        sync_log = [{"step": (r + 1) * t, "gates": gates[r].tolist(),
                     "loss": float(loss[r]),
                     "metric_local": ml[r].tolist(),
                     "metric_merged": mm[r].tolist(),
                     "spectral_gap": sess.engine.spectral_gap}
                    for r in range(rounds)]
        if log is not None:
            log.extend(sync_log)
    return sess.node_params, sync_log


def run_experiment(ecfg: HistoExperimentConfig) -> dict:
    """Full §4 protocol. Returns nested report dict."""
    images, labels = make_histo_dataset(
        ecfg.n_train, size=ecfg.image_size, noise=ecfg.noise,
        class_probs=ecfg.class_probs, seed=ecfg.seed)
    test_x, test_y = make_histo_dataset(
        ecfg.n_test, size=ecfg.image_size, noise=ecfg.noise,
        class_probs=ecfg.class_probs, seed=ecfg.seed + 999)

    sizes = paper_splits(ecfg.n_train, ecfg.fractions)
    shards = shard_to_nodes(images, labels, sizes, seed=ecfg.seed)
    if ecfg.scarcity:  # down-sample chosen nodes (the 25% / 5% trials)
        shards = [
            (x[: max(16, int(len(y) * ecfg.scarcity.get(i, 1.0)))],
             y[: max(16, int(len(y) * ecfg.scarcity.get(i, 1.0)))])
            for i, (x, y) in enumerate(shards)
        ]

    train_step, predict, features = _make_model_fns(ecfg)

    def report(params):
        probs = np.asarray(predict(params, test_x))
        rep = classify_report(probs, test_y)
        rep["dbi"] = davies_bouldin(np.asarray(features(params, test_x)), test_y)
        return rep

    # centralized full-data baseline
    key = jax.random.key(ecfg.seed + 42)
    params = _init_params(ecfg, key)
    opt = adamw_init(params)
    rng = np.random.default_rng(ecfg.seed)
    it = iter(())
    for step in range(ecfg.steps):
        try:
            b = next(it)
        except StopIteration:
            it = batches(images, labels, 32, rng)
            b = next(it)
        params, opt, _ = train_step(params, opt, b, step)
    central = report(params)

    # standalone local learners
    local_params, _ = _train_loop(ecfg, train_step, shards, swarm_cfg=None)
    local = [report(p) for p in local_params]

    # P2P-SL swarm
    swarm_params, sync_log = _train_loop(ecfg, train_step, shards,
                                         swarm_cfg=ecfg.swarm)
    swarm = [report(p) for p in swarm_params]

    out = {
        "config": {"sizes": [len(s[1]) for s in shards], "steps": ecfg.steps,
                   "sync_every": ecfg.swarm.sync_every,
                   "merge": ecfg.swarm.merge, "topology": ecfg.swarm.topology},
        "centralized": central,
        "local": local,
        "swarm": swarm,
        "sync_log": sync_log[-3:],
        "recovery": [  # fraction of centralized AUC recovered by swarm
            (s["auc"] - 0.5) / max(central["auc"] - 0.5, 1e-9) for s in swarm
        ],
    }
    return out


def summarize(result: dict) -> str:
    lines = ["node,setting,auc,sensitivity,specificity,f1,dbi"]
    c = result["centralized"]
    lines.append(f"-,centralized,{c['auc']:.4f},{c['sensitivity']:.2f},"
                 f"{c['specificity']:.2f},{c['f1']:.2f},{c['dbi']:.3f}")
    for i, (l, s) in enumerate(zip(result["local"], result["swarm"])):
        lines.append(f"{i},local,{l['auc']:.4f},{l['sensitivity']:.2f},"
                     f"{l['specificity']:.2f},{l['f1']:.2f},{l['dbi']:.3f}")
        lines.append(f"{i},swarm,{s['auc']:.4f},{s['sensitivity']:.2f},"
                     f"{s['specificity']:.2f},{s['f1']:.2f},{s['dbi']:.3f}")
    return "\n".join(lines)
