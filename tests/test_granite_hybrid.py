"""Granite-4.0-H's interleaved Mamba-2 / attention stack and its LoRA swarm
against the plain float32 reference (`models.reference_hybrid`), on the
CPU at a small size: d_model 64, two periods of a 3-layer pattern
(mamba, attention, mamba), sequences of 80 tokens, SSD chunks of 16.

Every tolerance below sits at a few hundred times float32's resolution:
the program and the reference compute the same products in another
order (the chunked SSD against the step-by-step recurrence, the logits in
token chunks against all at once), so they agree to rounding and no
further. A missing term (the D skip, the conv bias, a multiplier) moves
the numbers by far more.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import SwarmConfig, TrainConfig
from repro.configs.granite_4_0_h_micro import CONFIG, period_cut
from repro.core.lora import attach_payload
from repro.experiments import lm_swarm
from repro.models import build_model, reference_hybrid as ref
from repro.models.ssm import init_ssm, ssd_chunked, ssm_block
from repro.models.transformer import forward_lm

# float32 rounding, carried through a few hundred sums
RTOL, ATOL = 2e-4, 2e-5

SMALL = CONFIG.replace(
    n_layers=6, layer_kinds=("mamba", "attention", "mamba") * 2, d_model=64,
    n_heads=4, n_kv_heads=2, head_dim=16, d_ff=96, vocab_size=300,
    vocab_pad_to=0, ssm_state=16, ssm_heads=8, ssm_head_dim=16,
    ssm_chunk=16, attn_chunk=16, loss_chunk=32,
    param_dtype="float32", compute_dtype="float32")
S = 80
# the reference, compiled once per shape (the config is a static argument)
REF_FORWARD = jax.jit(ref.forward, static_argnums=1)
REF_GRAD = jax.jit(ref.adapter_grad, static_argnums=1)


def _tokens(seed, shape):
    return jax.random.randint(jax.random.key(seed), shape, 0,
                              SMALL.vocab_size)


def _leaf(tree, path):
    """The leaf of a nested params tree at a flat payload path."""
    for k in path.split("/"):
        tree = tree[int(k)] if isinstance(tree, list) else tree[k]
    return tree


def _with_adapters(seed=0):
    """Base and adapters with every LoRA factor drawn, so that ``lora_B``
    is not zero and both factors get a gradient."""
    base, adapters = lm_swarm.init_base_and_adapters(SMALL,
                                                     jax.random.key(seed))
    keys = iter(jax.random.split(jax.random.key(seed + 1), len(adapters)))
    adapters = {k: 0.1 * jax.random.normal(next(keys), v.shape)
                for k, v in adapters.items()}
    return base, adapters


def test_chunked_ssd_matches_the_recurrence():
    h, p, n = 8, 16, 16
    ks = jax.random.split(jax.random.key(0), 4)
    x = jax.random.normal(ks[0], (1, S, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, S, h)))
    b = jax.random.normal(ks[2], (1, S, 1, n))
    c = jax.random.normal(ks[3], (1, S, 1, n))
    a_log = jnp.log(jnp.linspace(1.0, 16.0, h))
    y, _ = ssd_chunked(x, dt, a_log, b, c, chunk=16)
    want = ref.ssd_sequential(x[0], dt[0], a_log, b[0], c[0])
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want),
                               rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("s", [S, 75], ids=["whole_chunks", "ragged"])
def test_mamba_mixer_matches_the_reference(s):
    """The whole mixer: conv with bias, D skip, gated norm; 75 tokens pad
    the last chunk of 16."""
    p = init_ssm(jax.random.key(1), SMALL)
    p = dict(p, conv=dict(p["conv"], b=0.1 * jnp.ones_like(p["conv"]["b"])),
             dt_bias=0.3 * jnp.ones_like(p["dt_bias"]))
    x = jax.random.normal(jax.random.key(2), (1, s, SMALL.d_model))
    y, _ = ssm_block(p, x, SMALL)
    want = ref._mamba(p, x[0], SMALL)
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want),
                               rtol=RTOL, atol=ATOL)


def test_logits_loss_and_adapter_gradients_match_the_reference():
    base, adapters = _with_adapters()
    model = build_model(SMALL)
    params = attach_payload(adapters, base)
    toks = _tokens(3, (1, S + 1))
    x, y = toks[:, :-1], toks[:, 1:]

    logits, _, _ = jax.jit(forward_lm, static_argnums=1)(params, SMALL, x)
    want = REF_FORWARD(params, SMALL, x[0])
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(want),
                               rtol=RTOL, atol=ATOL)

    def loss(a):
        return model.loss_fn(attach_payload(a, base),
                             {"tokens": x, "labels": y})[0]

    value, grads = jax.jit(jax.value_and_grad(loss))(adapters)
    want_value, want_grads = REF_GRAD(params, SMALL, x[0], y[0])
    np.testing.assert_allclose(float(value), float(want_value), rtol=RTOL)
    for path, g in grads.items():
        node = _leaf(want_grads, path)
        scale = float(jnp.max(jnp.abs(node)))
        assert scale > 0, path
        np.testing.assert_allclose(np.asarray(g), np.asarray(node),
                                   rtol=RTOL, atol=ATOL * scale, err_msg=path)


def _adamw(p, g, mu, nu, count, tc):
    """Plain AdamW of one site (global-norm clipping, bias correction,
    decoupled weight decay, a constant rate)."""
    norm = np.sqrt(sum(float(jnp.sum(v * v)) for v in g.values()))
    clip = min(1.0, tc.grad_clip / max(norm, 1e-9))
    count += 1
    out = {}
    for k in p:
        gk = g[k] * clip
        mu[k] = tc.b1 * mu[k] + (1 - tc.b1) * gk
        nu[k] = tc.b2 * nu[k] + (1 - tc.b2) * gk * gk
        step = ((mu[k] / (1 - tc.b1 ** count))
                / (jnp.sqrt(nu[k] / (1 - tc.b2 ** count)) + tc.eps))
        out[k] = p[k] - tc.lr * (step + tc.weight_decay * p[k])
    return out, count


def test_a_two_site_round_with_a_shared_base_matches_a_reference_round():
    """Two sites, two local steps each, a fedavg candidate, the relative
    accuracy gate and the commit, against the same done by hand on the
    reference. AdamW's eps is large so that its update is smooth in the
    gradient (near-zero gradients of either sign then move alike)."""
    tc = TrainConfig(lr=1e-2, warmup_steps=0, max_steps=100,
                     schedule="const", eps=1e-3)
    sw = SwarmConfig(n_nodes=2, sync_every=2, topology="full",
                     merge="fedavg", payload="lora", val_threshold=0.8,
                     gate_metric="accuracy")
    sizes = [10, 30]
    session = lm_swarm.lm_swarm_session(SMALL, tc, sw, sizes, seed=5)
    base = session.shared
    adapters0 = {k: np.asarray(v[0]) for k, v in session.state.params.items()}
    toks = _tokens(6, (2, 2, 1, S + 1))
    val = _tokens(7, (2, 1, S + 1))
    log = session.round((toks[..., :-1], toks[..., 1:]),
                        (val[..., :-1], val[..., 1:]))

    sites, losses = [], np.zeros((2, 2))
    for i in range(2):
        p = {k: jnp.asarray(v) for k, v in adapters0.items()}
        mu = {k: jnp.zeros_like(v) for k, v in p.items()}
        nu = dict(mu)
        count = 0
        for t in range(2):
            x, y = toks[t, i, 0, :-1], toks[t, i, 0, 1:]
            value, g = REF_GRAD(attach_payload(p, base), SMALL, x, y)
            g = {k: _leaf(g, k) for k in p}
            losses[t, i] = float(value)
            p, count = _adamw(p, g, mu, nu, count, tc)
        sites.append(p)
    w = np.asarray(sizes, np.float64) / sum(sizes)
    merged = {k: sum(w[i] * sites[i][k] for i in range(2)) for k in sites[0]}

    def accuracy(p, i):
        logits = REF_FORWARD(attach_payload(p, base), SMALL,
                             val[i, 0, :-1])
        return float(jnp.mean(jnp.argmax(logits, -1) == val[i, 0, 1:]))

    np.testing.assert_allclose(np.asarray(log["train"]["loss"]), losses,
                               rtol=RTOL)
    for i in range(2):
        local, cand = accuracy(sites[i], i), accuracy(merged, i)
        assert float(log["metric_local"][i]) == pytest.approx(local)
        assert float(log["metric_merged"][i]) == pytest.approx(cand)
        gate = cand >= 0.8 * local
        assert bool(log["gates"][i]) == gate
        want = merged if gate else sites[i]
        for k, v in session.state.params.items():
            np.testing.assert_allclose(np.asarray(v[i]), np.asarray(want[k]),
                                       rtol=RTOL, atol=ATOL, err_msg=k)


@pytest.fixture(scope="module")
def two_site_round():
    """(session, lowered round, compiled text) of a two-site swarm."""
    sw = SwarmConfig(n_nodes=2, sync_every=1, topology="full",
                     merge="fedavg", payload="lora", gate_metric="accuracy")
    session = lm_swarm.lm_swarm_session(
        SMALL, TrainConfig(warmup_steps=0), sw, [1, 1], seed=0)
    toks = _tokens(1, (1, 2, 1, 17))
    val = _tokens(2, (2, 1, 17))
    lowered = session._round_jit.lower(
        session._state, (toks[..., :-1], toks[..., 1:]),
        (val[..., :-1], val[..., 1:]), None, session.shared)
    return session, lowered, lowered.compile().as_text()


def test_the_round_takes_the_base_once_as_an_argument(two_site_round):
    """The compiled round takes every leaf of the frozen base as one
    unstacked parameter, holds no constant as large as its smallest matrix,
    and never lays a copy of a base matrix out per site."""
    session, lowered, text = two_site_round
    base_leaves = jax.tree.leaves(session.shared)
    shared_arg = lowered.args_info[0][4]
    assert [a.shape for a in jax.tree.leaves(shared_arg)] == \
        [a.shape for a in base_leaves]
    smallest = min(a.size for a in base_leaves if a.ndim >= 2)
    shapes = [tuple(int(d) for d in dims.split(",") if d)
              for dims in re.findall(r"\[([\d,]*)\]\{?[\d,]*\}? constant\(",
                                     text)]
    assert all(int(np.prod(s)) < smallest for s in shapes), shapes
    per_site = {f"[2,{','.join(map(str, a.shape))}]" for a in base_leaves
                if a.ndim >= 2}
    assert not [s for s in per_site if s in text], per_site


def test_the_round_names_the_models_parts(two_site_round):
    """Every part of the model has ops under its scope in the compiled
    round, those of the backward pass too (inside ``transpose(...)``)."""
    from repro import tracing

    _, _, text = two_site_round
    scope_of = tracing.scope_of_ops(text, tracing.LM_SCOPES)
    assert set(scope_of.values()) == set(tracing.LM_SCOPES)
    backward = re.findall(r'op_name="[^"]*transpose\([^"]*lm\.mlp', text)
    assert backward
    # the round's own stages are read as before
    assert set(tracing.scope_of_ops(text).values()) == set(
        tracing.ROUND_SCOPES)


def test_param_counts():
    """3.19 B published; the first period, 951.9 M (746.4 M in the layers,
    205.5 M in the tied embedding)."""
    assert get_config("granite-4.0-h-micro") is CONFIG
    assert CONFIG.param_count() == pytest.approx(3_191e6, rel=5e-3)
    cut = period_cut()
    assert cut.layer_kinds == ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    assert cut.param_count() == pytest.approx(951.9e6, rel=5e-3)
    emb = cut.vocab_size * cut.d_model
    assert cut.param_count() - emb == pytest.approx(746.4e6, rel=5e-3)
    params = jax.eval_shape(build_model(cut).init, jax.random.key(0))
    held = sum(a.size for a in jax.tree.leaves(params))
    # the analytic count leaves out only the final norm
    assert held == cut.param_count() + cut.d_model


def test_lora_adapters_number_7_2_million_a_site():
    _, adapters = jax.eval_shape(
        lambda k: lm_swarm.init_base_and_adapters(period_cut(), k),
        jax.random.key(0))
    assert sum(a.size for a in adapters.values()) == pytest.approx(
        7.2e6, rel=5e-3)
    assert {a.dtype for a in adapters.values()} == {jnp.dtype("float32")}


@pytest.mark.parametrize("backend, zoo", [("gossip", False), ("engine", True)])
def test_a_shared_base_needs_one_step_on_the_engine_backend(backend, zoo):
    from repro.core.session import SwarmSession

    def step(p, o, b, s, base):
        return p, o, {}

    def ev(p, v, base):
        return jnp.float32(1.0)

    steps = [step, step] if zoo else step
    with pytest.raises(ValueError, match="shared frozen base"):
        SwarmSession(SwarmConfig(n_nodes=2), steps, ev,
                     params={"w": jnp.zeros(3)}, backend=backend,
                     shared={"w": jnp.zeros(3)})
