"""The site-folded DenseNet (`models.cnn.forward_cnn_sites`) and the stacked
forms of the histo train step and gate eval do the vmapped per-site work,
and the engine takes them only where one TPU holds several sites (the
tests that need it stand the engine on tiled lanes).

Tiny widths (24 px; growth 8, stem 16, 2 blocks x 2 layers): the forward
alone runs every stage folded; the train step's forward the stem and the
first block, the second per site, as at 224 px. The folded
forms accumulate in another order, so float32 results agree to rounding:
tolerances are about a thousand float32 ulps of each leaf's scale.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.configs.base import SwarmConfig
from repro.core import engine as engine_mod
from repro.core.engine import GATE, LOCAL_STEPS, SwarmEngine
from repro.core.session import SwarmSession
from repro.experiments import histo
from repro.models import cnn
from repro.models.cnn import (bce_loss, conv2d, conv2d_sites, forward_cnn,
                              forward_cnn_sites, stem_sites)
from repro.optim import adamw_init

RTOL = 1e-4
ECFG = histo.HistoExperimentConfig(
    image_size=24, growth=8, stem=16, n_blocks=2, layers_per_block=2,
    feat_dim=32, hidden=16, batch_size=6, steps=8)


def _stacked_params(n):
    ps = [histo._init_params(ECFG, jax.random.key(i)) for i in range(n)]
    return jax.tree.map(lambda *a: jnp.stack(a), *ps)


# leaves whose gradient at the initial weights is 0 up to rounding: the
# head's biases sit before a batch norm, and the stem's batch-norm scale
# before a ReLU (its bias 0) whose every consumer is a batch norm
ROUNDING_ONLY = ("['stem']['bn']['scale']", "['head']['fc1']['b']",
                 "['head']['fc2']['b']")


def _images(n, rows, key=1, size=24):
    return jax.random.normal(jax.random.key(key), (n, rows, size, size, 3))


def _close(got, want, rtol=RTOL, where=None):
    """Leaf by leaf, within ``rtol`` of the leaf's largest magnitude, or of
    a thousandth of the tree's; a `ROUNDING_ONLY` leaf within ``rtol`` of
    the tree's largest magnitude. ``where``: a tree of masks of the
    elements to compare."""
    assert jax.tree.structure(got) == jax.tree.structure(want)
    top = max(float(jnp.max(jnp.abs(w))) for w in jax.tree.leaves(want))
    masks = (jax.tree.leaves(where) if where is not None
             else [True] * len(jax.tree.leaves(want)))
    for (path, w), g, m in zip(jax.tree_util.tree_leaves_with_path(want),
                               jax.tree.leaves(got), masks):
        name = jax.tree_util.keystr(path)
        assert g.shape == w.shape, name
        scale = (top if name.endswith(ROUNDING_ONLY)
                 else max(float(jnp.max(jnp.abs(w))), 1e-3 * top))
        np.testing.assert_allclose(np.where(m, g, 0), np.where(m, w, 0),
                                   rtol=0, atol=rtol * scale, err_msg=name)


def _settled(mu):
    """Masks of the elements whose first moment exceeds 1e-4 of the
    largest. AdamW steps an element by about the learning rate times the
    sign of its gradient, and where that gradient is near rounding, or
    near AdamW's epsilon, the rounding decides the step."""
    top = max(float(jnp.max(jnp.abs(m))) for m in jax.tree.leaves(mu))
    return jax.tree.map(lambda m: jnp.abs(m) > 1e-4 * top, mu)


@pytest.mark.parametrize("n,cin,cout", [(4, 12, 8), (3, 5, 7)])
def test_conv_sites_is_the_conv_of_each_site(n, cin, cout):
    w = jax.random.normal(jax.random.key(0), (n, 3, 3, cin, cout))
    x = jax.random.normal(jax.random.key(1), (n, 2, 6, 6, cin))
    fold = cnn.fold_sites

    def per_site(w, x):
        return fold(jax.vmap(conv2d)(w, x))

    def folded(w, x):
        return conv2d_sites(w, fold(x))

    _close(folded(w, x), per_site(w, x))
    probe = jax.random.normal(jax.random.key(2), per_site(w, x).shape)
    grads = [jax.grad(lambda w, x: jnp.sum(f(w, x) * probe), (0, 1))(w, x)
             for f in (folded, per_site)]
    _close(grads[0], grads[1])


@pytest.mark.parametrize("n", [1, 2, 4])
@pytest.mark.parametrize("size", [16, 17, 33])
def test_space_to_depth_stem_is_the_strided_folded_conv(size, n):
    """Width one more than height, so each size meets the other parity:
    SAME pads an odd size evenly and an even one a pixel more below."""
    w = jax.random.normal(jax.random.key(0), (n, 7, 7, 3, 8))
    x = jax.random.normal(jax.random.key(1), (n, 3, size, size + 1, 3))
    with jax.default_matmul_precision("highest"):
        got = jax.jit(stem_sites)(w, x)
        want = jax.jit(lambda w, x: conv2d_sites(w, cnn.fold_sites(x),
                                                 stride=2))(w, x)
    _close(got, want, rtol=1e-5)


ALL, TRAIN = None, cnn.TRAIN_FOLD_SHARE


@pytest.mark.parametrize("n,fold_share,size", [
    (1, ALL, 24), (1, TRAIN, 24), (4, ALL, 24), (4, TRAIN, 24),
    (4, ALL, 17), (4, TRAIN, 17)],
    ids=["1-all", "1-train", "4-all", "4-train", "4-all-17px",
         "4-train-17px"])
def test_folded_forward_matches_vmap(n, fold_share, size):
    params = _stacked_params(n)
    x = _images(n, 6, size=size)
    folded = jax.jit(lambda p, x: forward_cnn_sites(p, x, fold_share))
    _close(folded(params, x), jax.jit(jax.vmap(forward_cnn))(params, x))


def _convs(images, fold_share, **widths):
    """The equations of the folded forward's convolutions, in order."""
    params = jax.eval_shape(lambda: jax.vmap(
        lambda k: cnn.init_cnn(k, None, **widths))(
            jax.random.split(jax.random.key(0), images.shape[0])))
    jaxpr = jax.make_jaxpr(
        lambda p, x: forward_cnn_sites(p, x, fold_share))(params, images)
    return [e for e in jaxpr.eqns
            if e.primitive.name == "conv_general_dilated"]


def _conv_groups(images, fold_share, **widths):
    """``feature_group_count`` of each convolution of the folded forward:
    1 for a folded convolution, N for a vmapped per-site one."""
    return [c.params["feature_group_count"]
            for c in _convs(images, fold_share, **widths)]


@pytest.mark.parametrize("size,widths,convs", [
    (24, dict(growth=8, stem=16, n_blocks=2, layers_per_block=2,
              feat_dim=32, hidden=16), (1 + 3, 3)),
    (224, dict(), (1 + 5, 15))], ids=["tiny", "paper"])
def test_a_train_step_folds_the_stem_and_the_large_blocks(size, widths,
                                                          convs):
    images = jax.ShapeDtypeStruct((4, 2, size, size, 3), jnp.float32)
    folded, per_site = convs
    assert _conv_groups(images, None, **widths) == [1] * (folded + per_site)
    assert (_conv_groups(images, cnn.TRAIN_FOLD_SHARE, **widths)
            == [1] * folded + [4] * per_site)


@pytest.mark.parametrize("fold_share,window,stride", [
    (ALL, 4, 1), (TRAIN, 7, 2)], ids=["gate", "train"])
def test_the_gate_takes_the_space_to_depth_stem_and_a_step_the_strided(
        fold_share, window, stride):
    """A forward alone (the gate) runs the stem as `stem_sites`, a 4x4
    convolution of 2x2 pixel blocks; a training step keeps the 7x7
    stride-2 one, as XLA estimates the space-to-depth form's transpose
    and convolutions, its weight gradient included, at more cycles than
    the strided form's at the step's shape."""
    images = jax.ShapeDtypeStruct((4, 2, 224, 224, 3), jnp.float32)
    stem = _convs(images, fold_share)[0]
    assert stem.params["window_strides"] == (stride, stride)
    assert stem.invars[1].aval.shape[:2] == (window, window)


@pytest.mark.parametrize("n", [1, 4])
def test_folded_loss_and_gradients_match_vmap(n):
    params = _stacked_params(n)
    x = _images(n, 6)
    y = jax.nn.one_hot(jnp.arange(n * 6).reshape(n, 6) % 3, 3)

    def site_loss(p, x, y):
        return bce_loss(forward_cnn(p, x), y)

    def total(p):
        losses = jax.vmap(bce_loss)(forward_cnn_sites(p, x), y)
        return losses.sum(), losses

    want_l, want_g = jax.jit(jax.vmap(jax.value_and_grad(site_loss)))(
        params, x, y)
    (_, got_l), got_g = jax.jit(jax.value_and_grad(total, has_aux=True))(
        params)
    _close(got_l, want_l)
    _close(got_g, want_g)


@pytest.mark.parametrize("n", [1, 4])
def test_stacked_train_step_matches_vmap(n):
    step = histo._make_model_fns(ECFG)[0]
    params = _stacked_params(n)
    opt = jax.vmap(adamw_init)(params)
    rng = np.random.default_rng(0)
    batch = (np.asarray(_images(n, 6)),
             rng.integers(0, 3, size=(n, 6)).astype(np.int32))
    opt["count"] = opt["count"] + 10           # mid-warmup: lr > 0
    want = jax.vmap(step, in_axes=(0, 0, 0, None))(params, opt, batch, 0)
    got = step.stacked(params, opt, batch, 0)
    _close(got[2], want[2])                    # per-site losses
    _close(got[1]["mu"], want[1]["mu"])        # the clipped gradients
    _close(got[1]["nu"], want[1]["nu"])
    np.testing.assert_array_equal(got[1]["count"], want[1]["count"])
    _close(got[0], want[0], where=_settled(want[1]["mu"]))


@pytest.mark.parametrize("n", [1, 4])
def test_stacked_eval_matches_vmap_on_padded_rows(n):
    session = _session(n)
    eval_fn = session.eval_fn
    params = session.state.params
    rng = np.random.default_rng(1)
    x = np.array(_images(n, 10, key=3))
    valid = np.arange(10)[None, :] < rng.integers(4, 10, size=(n, 1))
    x[~valid] = 0.0                            # padded rows, as _stack_vals
    val = (x, rng.integers(0, 3, size=(n, 10)).astype(np.int32), valid)
    _close(jax.jit(forward_cnn_sites)(params, x),
           jax.jit(jax.vmap(forward_cnn))(params, x))
    _close(jax.jit(eval_fn.stacked)(params, val),
           jax.jit(jax.vmap(eval_fn))(params, val))


# -- engine dispatch ---------------------------------------------------------

def _swarm(n):
    return SwarmConfig(n_nodes=n, sync_every=2, topology="full",
                       merge="fedavg", lora_only=False, val_threshold=0.5,
                       gate_metric="auc")


def _session(n, plain=False, **kw):
    """The histo session at tiny widths; ``plain`` wraps the step and the
    eval in functions that offer no stacked form."""
    step = histo._make_model_fns(ECFG)[0]
    shards = [(None, np.zeros(10 + 5 * i)) for i in range(n)]
    session = histo._swarm_session(ECFG, step, shards, _swarm(n), **kw)
    if not plain:
        return session
    ev = session.eval_fn
    return SwarmSession(session.cfg, lambda p, o, b, s: step(p, o, b, s),
                        lambda p, v: ev(p, v), params=session.state.params,
                        opt_state=session.state.opt_state, stacked=True,
                        data_sizes=[10 + 5 * i for i in range(n)], **kw)


@pytest.fixture
def on_tiled_lanes(monkeypatch):
    """The engine as on the TPU, where it takes the stacked forms; on the
    CPU it keeps vmap."""
    monkeypatch.setattr(engine_mod, "lanes_tiled", lambda: True)


def test_engine_takes_the_stacked_forms_and_says_so(on_tiled_lanes):
    tracing.drain()
    tracing.enable()
    try:
        engine = _session(4).engine
        spans = tracing.drain()
    finally:
        tracing.disable()
    want = {LOCAL_STEPS: "stacked", GATE: "stacked"}
    assert engine.forms == want
    build = [sp for sp in spans if sp.name == "session.build"]
    assert build[0].attrs == {"forms": want, "folded_sites": 4}


def test_engine_keeps_vmap_where_no_stacked_form_fits(monkeypatch):
    step = histo._make_model_fns(ECFG)[0]
    ev = _session(2).eval_fn
    assert hasattr(step, "stacked") and hasattr(ev, "stacked")
    vmapped = {LOCAL_STEPS: "vmap", GATE: "vmap"}
    # a wrapper offers no stacked form
    plain = SwarmEngine(_swarm(4), lambda p, o, b, s: step(p, o, b, s),
                        lambda p, v: ev(p, v))
    assert plain.forms == vmapped
    # not on tiled lanes (this CPU): the block-diagonal weights would only
    # multiply the work
    assert not engine_mod.lanes_tiled()
    assert SwarmEngine(_swarm(4), step, ev).forms == vmapped
    monkeypatch.setattr(engine_mod, "lanes_tiled", lambda: True)
    # one site on the device: nothing to fold
    assert SwarmEngine(_swarm(1), step, ev).forms == vmapped
    # per-site closures (model zoo)
    zoo = SwarmEngine(_swarm(2), [step, step], [ev, ev])
    assert zoo.forms == {LOCAL_STEPS: "zoo", GATE: "zoo"}


@pytest.mark.spmd
def test_the_gossip_backend_keeps_vmap(on_tiled_lanes):
    """Its devices step their own sites (`gossip.per_shard`), vmapped."""
    step = histo._make_model_fns(ECFG)[0]
    mesh = jax.make_mesh((1,), ("node",))
    engine = SwarmEngine(_swarm(4), step, _session(2).eval_fn,
                         backend="gossip", mesh=mesh, axis="node")
    assert engine.forms == {LOCAL_STEPS: "vmap", GATE: "vmap"}


def _round_inputs(n, rounds=2):
    rng = np.random.default_rng(2)
    xs = rng.normal(size=(rounds, 2, n, 6, 24, 24, 3)).astype(np.float32)
    ys = rng.integers(0, 3, size=(rounds, 2, n, 6)).astype(np.int32)
    valid = np.arange(8)[None, :] < np.asarray([5, 8, 6, 7])[:n, None]
    vx = rng.normal(size=(n, 8, 24, 24, 3)).astype(np.float32)
    vx[~valid] = 0.0
    val = (vx, rng.integers(0, 3, size=(n, 8)).astype(np.int32), valid)
    return xs, ys, val


def test_a_folded_session_round_is_the_vmapped_one(on_tiled_lanes):
    """Same gates, AUCs and losses; the same parameters, each leaf's change
    within 5% of its size by the benchmark's measure (`ROUNDING_ONLY`
    leaves aside): AdamW steps an element whose gradient is rounding alone
    by about the learning rate, of either sign, and later steps carry that
    on (a few elements of the stem's batch-norm bias)."""
    n = 4
    folded, plain = _session(n), _session(n, plain=True)
    assert folded.engine.forms[LOCAL_STEPS] == "stacked"
    assert plain.engine.forms[LOCAL_STEPS] == "vmap"
    start = jax.tree.map(np.array, plain.state.params)
    xs, ys, val = _round_inputs(n)
    for r in range(xs.shape[0]):
        logs = [s.round((xs[r], ys[r]), val) for s in (folded, plain)]
        np.testing.assert_array_equal(np.asarray(logs[0]["gates"]),
                                      np.asarray(logs[1]["gates"]))
        for key in ("metric_local", "metric_merged"):
            np.testing.assert_allclose(np.asarray(logs[0][key]),
                                       np.asarray(logs[1][key]), atol=1e-6)
        _close(logs[0]["train"], logs[1]["train"])
    for (path, w), g, w0 in zip(
            jax.tree_util.tree_leaves_with_path(plain.state.params),
            jax.tree.leaves(folded.state.params), jax.tree.leaves(start)):
        name = jax.tree_util.keystr(path)
        if not name.endswith(ROUNDING_ONLY):
            gap = np.linalg.norm(g - w) / np.linalg.norm(w - w0)
            assert gap < 0.05, (name, gap)


def test_the_stem_scope_names_ops_of_the_gate_and_of_the_step(
        on_tiled_lanes):
    session = _session(4)
    xs, ys, val = _round_inputs(4, rounds=1)
    text = session._round_jit.lower(session._state, (xs[0], ys[0]), val,
                                    None).compile().as_text()
    stem = tracing.scope_of_ops(text, tracing.CNN_SCOPES)
    stage = tracing.scope_of_ops(text)
    # (a reducer's instructions carry the scope outside any stage)
    assert {GATE, LOCAL_STEPS} <= {stage.get(n) for n in stem}
