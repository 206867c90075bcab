"""Plain reference of the paper's swarm round, for the check that decides
``correct``. It imports nothing of the program and takes nothing it made:
weights come from the seed through this file's own copy of the network's
initialisation, and inputs from `swarmbench.traffic`.

One round at every site, one site at a time: ``sync_every`` local steps
(DenseNet forward and backward, sigmoid cross-entropy, AdamW with global
norm clipping on a cosine schedule), then the gate (macro one-vs-rest AUC of
the local and of the merged model on the site's padded validation set, in
float64 on the host), the merge (fedavg or ring mixing of the sites'
parameters, or of their int8 error-feedback wire reconstructions) and the
commit of accepted sites.

Computed in float32, every convolution and matrix product of the network
at the precision the configuration states (``matmul_precision``: the
program runs float32 at the TPU's default, one bfloat16 pass into a float32
accumulator), the commit's mixing at HIGHEST as the program's commit
kernels compute it. ``operands="float8_e4m3fn"`` rounds both operands of
every convolution and product to float8 and accumulates in float32,
forward and backward (the control, see PERF.md), and ``half_batch=True``
trains on the first half of each batch (a fault the check has to catch).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
PRECISION = {"default": jax.lax.Precision.DEFAULT,
             "high": jax.lax.Precision.HIGH, "highest": HI}
BN_EPS = 1e-5


def init_params(key, *, stem, growth, n_blocks, layers_per_block, feat_dim,
                hidden, n_classes):
    """He-normal convolutions and head, unit/zero batch norms, drawn from
    ``key`` in the network's published order."""
    ks = iter(jax.random.split(key, 2 + n_blocks * (layers_per_block + 1)
                               + 4))

    def conv(k, kh, cin, cout):
        return (jax.random.normal(k, (kh, kh, cin, cout))
                * jnp.sqrt(2.0 / (kh * kh * cin)))

    def bn(c):
        return {"scale": jnp.ones((c,)), "bias": jnp.zeros((c,))}

    params = {"stem": {"w": conv(next(ks), 7, 3, stem), "bn": bn(stem)}}
    c, blocks = stem, []
    for b in range(n_blocks):
        layers = []
        for _ in range(layers_per_block):
            layers.append({"bn": bn(c), "w": conv(next(ks), 3, c, growth)})
            c += growth
        out = c // 2 if b < n_blocks - 1 else feat_dim
        blocks.append({"layers": layers,
                       "trans": {"bn": bn(c),
                                 "w": conv(next(ks), 1, c, out)}})
        c = out
    params["blocks"] = blocks
    params["head"] = {
        "fc1": {"w": jax.random.normal(next(ks), (feat_dim, hidden))
                * jnp.sqrt(2.0 / feat_dim),
                "b": jnp.zeros((hidden,)), "bn": bn(hidden)},
        "fc2": {"w": jax.random.normal(next(ks), (hidden, n_classes))
                * jnp.sqrt(2.0 / hidden),
                "b": jnp.zeros((n_classes,)), "bn": bn(n_classes)},
    }
    return params


def _bn(p, x):
    axes = tuple(range(x.ndim - 1))
    mu = jnp.mean(x, axes, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axes, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + BN_EPS) * p["scale"] + p["bias"]


def forward(params, x, precision=HI, operands=None):
    """images [B, H, W, 3] -> logits [B, C]; batch norm on batch
    statistics. ``operands``: a dtype both operands of every convolution
    and product are rounded to first."""
    def rnd(a):
        return a if operands is None else a.astype(operands).astype(a.dtype)

    def conv(w, h, stride=1):
        return jax.lax.conv_general_dilated(
            rnd(h), rnd(w), (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)

    def dot(a, b):
        return jnp.dot(rnd(a), rnd(b), precision=precision)

    x = jax.nn.relu(_bn(params["stem"]["bn"], conv(params["stem"]["w"], x,
                                                   2)))
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
    for block in params["blocks"]:
        for layer in block["layers"]:
            h = conv(layer["w"], jax.nn.relu(_bn(layer["bn"], x)))
            x = jnp.concatenate([x, h], axis=-1)
        x = conv(block["trans"]["w"],
                 jax.nn.relu(_bn(block["trans"]["bn"], x)))
        if min(x.shape[1], x.shape[2]) >= 2:
            x = jax.lax.reduce_window(x, 0.0, jax.lax.add, (1, 2, 2, 1),
                                      (1, 2, 2, 1), "VALID") / 4.0
    z = jnp.mean(x, axis=(1, 2))
    fc1, fc2 = params["head"]["fc1"], params["head"]["fc2"]
    z = jax.nn.relu(_bn(fc1["bn"], dot(z, fc1["w"]) + fc1["b"]))
    return _bn(fc2["bn"], dot(z, fc2["w"]) + fc2["b"])


def make_fns(train: dict, operands=None, prec=HI):
    """Jitted (step, probs) for one site, convolutions and products at
    ``prec``. ``train`` holds the recipe: lr, warmup_steps, schedule_steps,
    weight_decay, b1, b2, eps, grad_clip."""

    def loss_fn(params, x, y):
        logits = forward(params, x, prec, operands)
        onehot = jax.nn.one_hot(y, logits.shape[-1])
        return -jnp.mean(onehot * jax.nn.log_sigmoid(logits)
                         + (1 - onehot) * jax.nn.log_sigmoid(-logits))

    def lr_at(count):
        step = count.astype(jnp.float32)
        base, warm = train["lr"], train["warmup_steps"]
        total = train["schedule_steps"]
        prog = jnp.clip((step - warm) / max(total - warm, 1), 0.0, 1.0)
        cos = base * (0.1 + 0.9 * 0.5 * (1 + jnp.cos(jnp.pi * prog)))
        return jnp.where(step < warm, base * step / max(warm, 1), cos)

    @jax.jit
    def step(params, opt, x, y):
        mu, nu, count = opt
        loss, g = jax.value_and_grad(loss_fn)(params, x, y)
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(a))
                            for a in jax.tree.leaves(g)))
        g = jax.tree.map(lambda a: a * jnp.minimum(
            1.0, train["grad_clip"] / jnp.maximum(norm, 1e-9)), g)
        lr = lr_at(count)
        count = count + 1
        b1, b2 = train["b1"], train["b2"]
        c = count.astype(jnp.float32)
        mu = jax.tree.map(lambda m, a: b1 * m + (1 - b1) * a, mu, g)
        nu = jax.tree.map(lambda v, a: b2 * v + (1 - b2) * a * a, nu, g)
        params = jax.tree.map(
            lambda p, m, v: p - lr * ((m / (1 - b1 ** c))
                                      / (jnp.sqrt(v / (1 - b2 ** c))
                                         + train["eps"])
                                      + train["weight_decay"] * p),
            params, mu, nu)
        return params, (mu, nu, count), loss

    @jax.jit
    def probs(params, x):
        return jax.nn.sigmoid(forward(params, x, prec, operands))

    return step, probs


def macro_auc(probs, labels):
    """One-vs-rest macro AUC (rank sums, ties averaged) over the classes
    present, in float64."""
    probs = np.asarray(probs, np.float64)
    aucs = []
    for c in range(probs.shape[1]):
        pos = labels == c
        n_pos, n_neg = pos.sum(), (~pos).sum()
        if n_pos == 0:
            continue
        if n_neg == 0:
            aucs.append(0.5)
            continue
        s = probs[:, c]
        order = np.argsort(s, kind="mergesort")
        uniq, inv, counts = np.unique(s[order], return_inverse=True,
                                      return_counts=True)
        ranks = np.empty(len(s))
        ranks[order] = (np.cumsum(counts) - (counts - 1) / 2.0)[inv]
        aucs.append((ranks[pos].sum() - n_pos * (n_pos + 1) / 2.0)
                    / (n_pos * n_neg))
    return float(np.mean(aucs)) if aucs else 0.5


def mixing_matrix(traffic: dict, data_sizes) -> np.ndarray:
    """Row i: the weights site i gives every site's parameters."""
    n = len(data_sizes)
    if traffic["topology"] == "full":
        w = (np.asarray(data_sizes, np.float64) if traffic["merge"] == "fedavg"
             else np.ones(n))
        return np.tile(w / w.sum(), (n, 1))
    if traffic["topology"] == "ring":
        s = traffic["self_weight"]
        W = np.zeros((n, n))
        for i in range(n):
            W[i, i] += s
            W[i, (i - 1) % n] += (1 - s) / 2
            W[i, (i + 1) % n] += (1 - s) / 2
        return W
    raise ValueError(f"no reference for topology {traffic['topology']!r}")


def _int8_round_trip(v, block):
    """Per-site int8 quantize and dequantize of a stacked leaf [N, ...]:
    flattened per site, zero-padded to whole blocks, one scale (max |v| /
    127) per block, round half to even, clipped to +-127."""
    n = v.shape[0]
    flat = v.reshape(n, -1)
    d = flat.shape[1]
    flat = jnp.pad(flat, ((0, 0), (0, (-d) % block)))
    blocks = flat.reshape(n, -1, block)
    scale = jnp.max(jnp.abs(blocks), axis=-1, keepdims=True) / 127.0
    q = jnp.clip(jnp.round(blocks / jnp.where(scale > 0, scale, 1.0)),
                 -127.0, 127.0)
    return (q * scale).reshape(n, -1)[:, :d].reshape(v.shape)


@jax.jit
def _mix(W, leaf):
    return jnp.tensordot(W, leaf, axes=1, precision=HI)


_wire_advance = jax.jit(lambda x, r, block: r + _int8_round_trip(x - r,
                                                                 block),
                        static_argnums=2)


def run_rounds(config: dict, traffic: dict, inputs: dict, seed32: int,
               rounds: int, *, operands=None,
               half_batch: bool = False, follow=None, band: float = 0.0):
    """The first ``rounds`` rounds of a cell. Returns per-round losses
    [R, T, N], the stacked first moment after round 1, the stacked params
    before round 1 and after round R (numpy, one array per leaf), the gates
    [R, N] and the AUCs [R, N, (local, merged)].

    A gate whose merged AUC lies within ``band`` of the threshold times the
    local AUC is a tie that rounding may decide either way: there the
    reference takes the decision given in ``follow`` [R, N] (the compared
    run's gates), so that both go on from the same branch. ``followed``
    counts those gates."""
    widths = {k: config[k] for k in ("stem", "growth", "n_blocks",
                                      "layers_per_block", "feat_dim",
                                      "hidden", "n_classes")}
    p0 = jax.jit(lambda k: init_params(k, **widths))(
        jax.random.key(seed32 + 42))
    n = config["n_sites"]
    step, probs = make_fns(config["train"], operands,
                           PRECISION[config["matmul_precision"]])
    sites = [p0] * n
    zeros = jax.tree.map(jnp.zeros_like, p0)
    opts = [(zeros, zeros, jnp.zeros((), jnp.int32))] * n
    W = jnp.asarray(mixing_matrix(traffic, inputs["data_sizes"]),
                    jnp.float32)
    int8 = traffic["wire"] == "int8"
    wire = jax.tree.map(lambda a: jnp.zeros((n,) + a.shape), p0)
    vx, vy, vm = inputs["val"]
    thr = traffic["gate_threshold"]
    stack = lambda trees: jax.tree.map(lambda *a: jnp.stack(a), *trees)
    host = lambda tree: [np.asarray(a) for a in jax.tree.leaves(tree)]

    def score(params, i):
        return macro_auc(np.asarray(probs(params, vx[i]))[vm[i]],
                         vy[i][vm[i]])

    out = {"params0": host(stack(sites)), "losses": [], "gates": [],
           "auc": [], "followed": 0}
    for r in range(rounds):
        xs, ys = inputs["pool"][r % len(inputs["pool"])]
        b = xs.shape[2] // 2 if half_batch else xs.shape[2]
        losses = np.zeros(xs.shape[:2])
        for t in range(xs.shape[0]):
            for i in range(n):
                sites[i], opts[i], loss = step(sites[i], opts[i],
                                               xs[t, i, :b], ys[t, i, :b])
                losses[t, i] = float(loss)
        out["losses"].append(losses)
        if r == 0:
            out["mu1"] = host(stack([o[0] for o in opts]))
        stacked = stack(sites)
        if int8:
            wire = jax.tree.map(
                lambda x, w: _wire_advance(x, w, traffic["wire_block"]),
                stacked, wire)
            sent = wire
        else:
            sent = stacked
        cand = jax.tree.map(lambda a: _mix(W, a), sent)
        gates, aucs = [], []
        for i in range(n):
            mine = jax.tree.map(lambda a, i=i: a[i], cand)
            aucs.append((score(sites[i], i), score(mine, i)))
            margin = aucs[-1][1] - thr * aucs[-1][0]
            if follow is not None and abs(margin) <= band:
                gates.append(bool(follow[r][i]))
                out["followed"] += 1
            else:
                gates.append(bool(margin >= 0))
            if gates[-1]:
                sites[i] = mine
        out["gates"].append(gates)
        out["auc"].append(aucs)
    out["params"] = host(stack(sites))
    out["losses"] = np.stack(out["losses"])
    out["gates"] = np.asarray(out["gates"])
    out["auc"] = np.asarray(out["auc"])
    return out

