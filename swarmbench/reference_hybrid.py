"""Plain reference of a LoRA swarm round of an interleaved Mamba-2 /
attention LM (Granite-4.0-H), for the check that decides ``correct``. It
imports nothing of the program and takes nothing it made: the base and
the adapters come from the seed through this file's own copy of the
program's initialisation, the text from `swarmbench.text`.

One site and one report at a time, in float32, every product at HIGHEST:

  x = E[tokens] * embedding_multiplier
  each layer: x = x + r * mixer(rmsnorm(x)); x = x + r * mlp(rmsnorm(x)),
      mlp(h) = W_down(silu(a) * b), [a, b] = W_gate_up(h)
  Mamba-2: [z, xBC, dt] = in_proj(h); xBC = silu(conv1d_causal(xBC) + bias)
      split into x, B, C; dt = softplus(dt + dt_bias); A = -exp(A_log);
      S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;  y_t = C_t S_t + D x_t;
      out_proj(rmsnorm(y * silu(z)))
  attention: causal GQA, softmax(q k^T * attention_multiplier), no RoPE
  logits = rmsnorm(x) E^T / logits_scaling

where every projection W carries its site's adapters, W x + s B(A x). The
SSD runs step by step (segments of ``SEGMENT`` steps under
`jax.checkpoint`, so that the backward pass keeps one state a segment).
A round: ``sync_every`` local steps (mean next-token cross-entropy, its
gradient with respect to the adapters only, AdamW with global-norm
clipping on a cosine schedule), the gate (next-token accuracy of the local
and of the fedavg-merged adapters on the site's validation report,
relative threshold), and the commit of accepted sites.

``operands="float8_e4m3fn"`` rounds both operands of every product to
float8 and accumulates in float32, forward and backward (the control, see
PERF.md), and ``half_batch=True`` trains on the first half of each
report's tokens (a fault the check has to catch).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from swarmbench.reference import mixing_matrix

HI = jax.lax.Precision.HIGHEST
SEGMENT = 128
f32 = jnp.float32
# each kind's adapted projections, in the order the program draws their
# adapters (its parameter paths, sorted)
TARGETS = {"mamba": ("mlp/down", "mlp/gate_up", "ssm/in_proj",
                     "ssm/out_proj"),
           "attention": ("attn/k", "attn/o", "attn/q", "attn/v", "mlp/down",
                         "mlp/gate_up")}


def dims(c: dict) -> dict:
    d = c["hidden_size"]
    di = c["mamba_expand"] * d
    g, n = c["mamba_n_groups"], c["mamba_d_state"]
    return dict(d=d, f=c["shared_intermediate_size"], di=di, g=g, n=n,
                heads=c["mamba_n_heads"], p=c["mamba_d_head"],
                conv=di + 2 * g * n, width=c["mamba_d_conv"],
                nh=c["num_attention_heads"], nkv=c["num_key_value_heads"],
                hd=d // c["num_attention_heads"], v=c["vocab_size"])


def runs(c: dict):
    """``(kind, length)`` of each run of consecutive layers of one kind."""
    out = []
    for kind in c["layer_types"]:
        if out and out[-1][0] == kind:
            out[-1][1] += 1
        else:
            out.append([kind, 1])
    return out


def _dense(key, i, o, dtype):
    return (jax.random.normal(key, (i, o)) * (1.0 / jnp.sqrt(i))).astype(dtype)


def _layer(key, c, kind, dtype):
    """One layer's weights, drawn as the program draws them."""
    m = dims(c)
    ks = jax.random.split(key, 2)
    ones = lambda k: jnp.ones((k,), dtype)   # noqa: E731
    if kind == "mamba":
        k3 = jax.random.split(ks[0], 5)
        mixer = {"ssm_norm": {"scale": ones(m["d"])}, "ssm": {
            "in_proj": {"w": _dense(k3[0], m["d"], m["di"] + m["conv"]
                                    + m["heads"], dtype)},
            "conv": {"w": (jax.random.normal(k3[1], (m["width"], m["conv"]))
                           * 0.1).astype(dtype),
                     "b": jnp.zeros((m["conv"],), dtype)},
            "A_log": jnp.log(jnp.linspace(1.0, 16.0, m["heads"])).astype(f32),
            "D": jnp.ones((m["heads"],), f32),
            "dt_bias": jnp.zeros((m["heads"],), f32),
            "norm_scale": ones(m["di"]),
            "out_proj": {"w": _dense(k3[2], m["di"], m["d"], dtype)}}}
    else:
        k4 = jax.random.split(ks[0], 4)
        q, kv = m["nh"] * m["hd"], m["nkv"] * m["hd"]
        mixer = {"attn_norm": {"scale": ones(m["d"])}, "attn": {
            "q": {"w": _dense(k4[0], m["d"], q, dtype)},
            "k": {"w": _dense(k4[1], m["d"], kv, dtype)},
            "v": {"w": _dense(k4[2], m["d"], kv, dtype)},
            "o": {"w": _dense(k4[3], q, m["d"], dtype)}}}
    k3 = jax.random.split(ks[1], 3)
    return dict(mixer, mlp_norm={"scale": ones(m["d"])}, mlp={
        "gate_up": {"w": _dense(k3[0], m["d"], 2 * m["f"], dtype)},
        "down": {"w": _dense(k3[1], m["f"], m["d"], dtype)}})


def init(c: dict, seed32: int):
    """(base, adapters): the weights, each run of layers stacked, in the
    configuration's dtype, and ``{path: float32}`` adapter factors, from the
    seed as the program draws them (`experiments.lm_swarm`)."""
    dtype = jnp.dtype(c["dtype"])
    k_model, k_lora = jax.random.split(jax.random.key(seed32))
    ks = jax.random.split(k_model, 4)
    lkeys = jax.random.split(ks[0], len(c["layer_types"]))
    layers, first = [], 0
    for kind, n in runs(c):
        layers.append(jax.vmap(lambda k, kind=kind: _layer(k, c, kind, dtype))(
            lkeys[first:first + n]))
        first += n
    m = dims(c)
    base = {"embed": (jax.random.normal(ks[1], (m["v"], m["d"])) * 0.02
                      ).astype(dtype),
            "final_norm": jnp.ones((m["d"],), dtype), "layers": layers}
    rank, alpha = c["lora"]["rank"], c["lora"]["alpha"]
    keys = iter(jax.random.split(k_lora, 4096))
    adapters = {}
    for r, (kind, n) in enumerate(runs(c)):
        for target in TARGETS[kind]:
            node = layers[r]
            for k in target.split("/"):
                node = node[k]
            _, i, o = node["w"].shape
            a = jax.random.normal(next(keys), (n, i, rank)) / jnp.sqrt(rank)
            adapters[f"layers/{r}/{target}/lora_A"] = a.astype(dtype).astype(
                f32)
            adapters[f"layers/{r}/{target}/lora_B"] = jnp.zeros((n, rank, o),
                                                                f32)
    base["lora_scale"] = alpha / rank
    return base, dict(sorted(adapters.items()))


def _rounding(operands):
    if operands is None:
        return lambda a: a
    return lambda a: a.astype(operands).astype(f32)


def forward_hidden(base, adapters, c, tokens, operands=None):
    """tokens [S] -> final-normed hidden [S, D], one report."""
    m, rnd = dims(c), _rounding(operands)
    eps, res = c["rms_norm_eps"], c["residual_multiplier"]
    scale = base["lora_scale"]

    def dot(a, b):
        return jnp.matmul(rnd(a), rnd(b), precision=HI)

    def ein(spec, a, b):
        return jnp.einsum(spec, rnd(a), rnd(b), precision=HI)

    def lin(w, ad, x):
        y = dot(x, w.astype(f32))
        return y + dot(dot(x, ad["lora_A"]), ad["lora_B"]) * scale

    def rms(s, x):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * s.astype(f32)

    def ssd(x, dt, a_log, b, cm):       # x [S, H, P], dt [S, H], b [S, N]
        s, h = dt.shape
        a = -jnp.exp(a_log)
        pad = (-s) % SEGMENT
        if pad:                          # zero dt leaves the state alone
            x, dt, b, cm = (jnp.pad(t, [(0, pad)] + [(0, 0)] * (t.ndim - 1))
                            for t in (x, dt, b, cm))
        group = jnp.arange(h) // (h // m["g"])

        def step(st, inp):               # st [H, P, N]
            x_t, dt_t, b_t, c_t = inp
            upd = ein("hp,hn->hpn", dt_t[:, None] * x_t, b_t[group])
            st = jnp.exp(dt_t * a)[:, None, None] * st + upd
            return st, ein("hpn,hn->hp", st, c_t[group])

        seg = jax.checkpoint(lambda st, inp: jax.lax.scan(step, st, inp))
        rows = lambda t: t.reshape(-1, SEGMENT, *t.shape[1:])  # noqa: E731
        st0 = jnp.zeros((h, m["p"], m["n"]), f32)
        _, y = jax.lax.scan(seg, st0, tuple(map(rows, (x, dt, b, cm))))
        return y.reshape(-1, h, m["p"])[:s]

    def mamba(p, ad, h):
        di, g, n, heads = m["di"], m["g"], m["n"], m["heads"]
        z, xbc, dt = jnp.split(lin(p["in_proj"]["w"], ad["in_proj"], h),
                               [di, di + m["conv"]], axis=-1)
        w = p["conv"]["w"].astype(f32)
        full = jnp.pad(xbc, ((m["width"] - 1, 0), (0, 0)))
        conv = sum(full[i:i + xbc.shape[0]] * w[i] for i in range(m["width"]))
        xbc = jax.nn.silu(conv + p["conv"]["b"].astype(f32))
        x, b, cm = jnp.split(xbc, [di, di + g * n], axis=-1)
        s = x.shape[0]
        x = x.reshape(s, heads, m["p"])
        dt = jax.nn.softplus(dt + p["dt_bias"])
        y = ssd(x, dt, p["A_log"], b.reshape(s, g, n), cm.reshape(s, g, n))
        y = (y + x * p["D"][:, None]).reshape(s, di)
        y = rms(p["norm_scale"], y * jax.nn.silu(z))
        return lin(p["out_proj"]["w"], ad["out_proj"], y)

    def attention(p, ad, h):
        s, nh, nkv, hd = h.shape[0], m["nh"], m["nkv"], m["hd"]
        q = lin(p["q"]["w"], ad["q"], h).reshape(s, nh, hd)
        k = lin(p["k"]["w"], ad["k"], h).reshape(s, nkv, hd)
        v = lin(p["v"]["w"], ad["v"], h).reshape(s, nkv, hd)
        kv = jnp.arange(nh) // (nh // nkv)
        sc = ein("qhd,khd->hqk", q, k[:, kv]) * c["attention_multiplier"]
        sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
        out = ein("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v[:, kv])
        return lin(p["o"]["w"], ad["o"], out.reshape(s, nh * hd))

    def layer(x, p, ad, kind):
        if kind == "mamba":
            y = mamba(p["ssm"], ad["ssm"], rms(p["ssm_norm"]["scale"], x))
        else:
            y = attention(p["attn"], ad["attn"],
                          rms(p["attn_norm"]["scale"], x))
        x = x + res * y
        h = rms(p["mlp_norm"]["scale"], x)
        a, b = jnp.split(lin(p["mlp"]["gate_up"]["w"], ad["mlp"]["gate_up"],
                             h), 2, axis=-1)
        return x + res * lin(p["mlp"]["down"]["w"], ad["mlp"]["down"],
                             jax.nn.silu(a) * b)

    x = base["embed"].astype(f32)[tokens] * c["embedding_multiplier"]
    for r, (kind, n) in enumerate(runs(c)):
        ads = {}
        for path, leaf in adapters.items():
            parts = path.split("/")
            if parts[1] == str(r):
                node = ads
                for k in parts[2:-1]:
                    node = node.setdefault(k, {})
                node[parts[-1]] = leaf
        for i in range(n):
            one = lambda t, i=i: jax.tree.map(lambda a: a[i], t)  # noqa: E731
            x = jax.checkpoint(layer, static_argnums=3)(
                x, one(base["layers"][r]), one(ads), kind)
    return rms(base["final_norm"], x)


def _logits(base, c, x, operands):
    rnd = _rounding(operands)
    table = base["embed"].astype(f32)
    return jnp.matmul(rnd(x), rnd(table).T, precision=HI) \
        / c["logits_scaling"]


def make_fns(c: dict, operands=None):
    """Jitted (step, accuracy) of one site: a step over a batch of reports
    [B, S], the accuracy of one report [S]."""
    train = c["train"]

    def loss_fn(adapters, base, tokens, labels):
        """Mean over the batch's reports [B, S], one report at a time."""
        def one(total, report):
            x = forward_hidden(base, adapters, c, report[0], operands)
            logits = _logits(base, c, x, operands)
            gold = jnp.take_along_axis(logits, report[1][:, None],
                                       axis=-1)[:, 0]
            return total + jnp.mean(jax.nn.logsumexp(logits, -1) - gold), None

        total, _ = jax.lax.scan(one, jnp.zeros((), f32), (tokens, labels))
        return total / tokens.shape[0]

    def lr_at(count):
        step = count.astype(f32)
        base_lr, warm = train["lr"], train["warmup_steps"]
        total = train["schedule_steps"]
        prog = jnp.clip((step - warm) / max(total - warm, 1), 0.0, 1.0)
        cos = base_lr * (0.1 + 0.9 * 0.5 * (1 + jnp.cos(jnp.pi * prog)))
        return jnp.where(step < warm, base_lr * step / max(warm, 1), cos)

    @jax.jit
    def step(adapters, opt, base, tokens, labels):
        mu, nu, count = opt
        loss, g = jax.value_and_grad(loss_fn)(adapters, base, tokens, labels)
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(a)) for a in g.values()))
        g = {k: a * jnp.minimum(1.0, train["grad_clip"]
                                / jnp.maximum(norm, 1e-9))
             for k, a in g.items()}
        lr = lr_at(count)
        count = count + 1
        b1, b2 = train["b1"], train["b2"]
        t = count.astype(f32)
        mu = {k: b1 * mu[k] + (1 - b1) * g[k] for k in g}
        nu = {k: b2 * nu[k] + (1 - b2) * g[k] * g[k] for k in g}
        adapters = {k: p - lr * ((mu[k] / (1 - b1 ** t))
                                 / (jnp.sqrt(nu[k] / (1 - b2 ** t))
                                    + train["eps"])
                                 + train["weight_decay"] * p)
                    for k, p in adapters.items()}
        return adapters, (mu, nu, count), loss

    @jax.jit
    def accuracy(adapters, base, tokens, labels):
        x = forward_hidden(base, adapters, c, tokens, operands)
        pred = jnp.argmax(_logits(base, c, x, operands), axis=-1)
        return jnp.mean((pred == labels).astype(f32))

    return step, accuracy


@jax.jit
def _mix(W, leaf):
    return jnp.tensordot(W, leaf, axes=1, precision=HI)


def run_rounds(config: dict, traffic: dict, inputs: dict, seed32: int,
               rounds: int, *, operands=None, half_batch: bool = False,
               follow=None, band: float = 0.0, scored=None,
               acc_limit: float = 0.0):
    """The first ``rounds`` rounds of a cell, as `reference.run_rounds`
    gives them: per-round losses [R, T, N], the stacked adapters' first
    moment after round 1, the stacked adapters before round 1 and after
    round R (numpy, one array per leaf in the program's order), the gates
    [R, N] and the accuracies [R, N, (local, merged)] (under ``auc``, the
    harness's name). A gate whose merged accuracy lies within ``band`` of
    the threshold times the local one takes the decision in ``follow``.

    ``scored`` [R, N, 2] are the accuracies of the compared run, whose
    gates are ``follow``. Where either lies more than ``acc_limit`` from
    the reference's, the gate was decided on wrong numbers: its entry in
    ``gates`` is the opposite of ``follow``'s, so that the check counts it
    as flipped (``misscored`` counts them), and the reference commits by
    its own decision."""
    base, a0 = init(config, seed32)
    n = config["n_sites"]
    step, accuracy = make_fns(config, operands)
    sites = [a0] * n
    zeros = {k: jnp.zeros_like(v) for k, v in a0.items()}
    opts = [(zeros, zeros, jnp.zeros((), jnp.int32))] * n
    W = jnp.asarray(mixing_matrix(traffic, inputs["data_sizes"]), f32)
    vt, vl = inputs["val"]
    thr = traffic["gate_threshold"]
    stack = lambda trees: {k: jnp.stack([t[k] for t in trees])  # noqa: E731
                           for k in trees[0]}
    host = lambda tree: [np.asarray(tree[k]) for k in sorted(tree)]  # noqa

    def score(adapters, i):
        return float(np.mean([float(accuracy(adapters, base, vt[i, j],
                                             vl[i, j]))
                              for j in range(vt.shape[1])]))

    out = {"params0": host(stack(sites)), "losses": [], "gates": [],
           "auc": [], "followed": 0, "misscored": 0}
    for r in range(rounds):
        tokens, labels = inputs["pool"][r % len(inputs["pool"])]
        s = tokens.shape[-1] // 2 if half_batch else tokens.shape[-1]
        losses = np.zeros(tokens.shape[:2])
        for t in range(tokens.shape[0]):
            for i in range(n):
                sites[i], opts[i], loss = step(sites[i], opts[i], base,
                                               tokens[t, i, :, :s],
                                               labels[t, i, :, :s])
                losses[t, i] = float(loss)
        out["losses"].append(losses)
        if r == 0:
            out["mu1"] = host(stack([o[0] for o in opts]))
        cand = {k: _mix(W, v) for k, v in stack(sites).items()}
        gates, accs = [], []
        for i in range(n):
            mine = {k: v[i] for k, v in cand.items()}
            accs.append((score(sites[i], i), score(mine, i)))
            margin = accs[-1][1] - thr * accs[-1][0]
            if follow is not None and abs(margin) <= band:
                accept = bool(follow[r][i])
                out["followed"] += 1
            else:
                accept = bool(margin >= 0)
            gates.append(accept)
            if scored is not None and np.max(np.abs(
                    np.subtract(scored[r][i], accs[-1]))) > acc_limit:
                gates[-1] = not bool(follow[r][i])
                out["misscored"] += 1
            if accept:
                sites[i] = mine
        out["gates"].append(gates)
        out["auc"].append(accs)
    out["params"] = host(stack(sites))
    out["losses"] = np.stack(out["losses"])
    out["gates"] = np.asarray(out["gates"])
    out["auc"] = np.asarray(out["auc"])
    return out
