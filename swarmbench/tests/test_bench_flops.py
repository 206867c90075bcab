"""The benchmark's operation and byte counts against XLA's cost analysis on
the CPU, at a small size of the paper's network, and its table of peaks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.cnn import bce_loss, forward_cnn, init_cnn
from swarmbench import flops

SMALL = dict(image_size=32, stem=32, growth=32, n_blocks=2,
             layers_per_block=2, feat_dim=128, hidden=64, n_classes=3)


def _cost(fn, *args):
    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    return cost[0] if isinstance(cost, list) else cost


@pytest.fixture(scope="module")
def small():
    w = {k: SMALL[k] for k in ("stem", "growth", "n_blocks",
                               "layers_per_block", "feat_dim", "hidden")}
    params = init_cnn(jax.random.key(0), None, **w)
    x = jnp.ones((4, 32, 32, 3))
    y = jax.nn.one_hot(jnp.arange(4) % 3, 3)
    return params, x, y


def test_forward_flops_are_the_products_xla_counts(small):
    params, x, _ = small
    xla = _cost(forward_cnn, params, x)["flops"] / 4
    ours = flops.forward_image(SMALL)
    # XLA adds batch norm, activations and pooling; the convolutions and
    # the head dominate at these widths
    assert 0.9 * xla <= ours <= xla


def test_train_flops_leave_out_only_the_images_gradient(small):
    params, x, y = small

    def step(p, x, y):
        return jax.grad(lambda p: bce_loss(forward_cnn(p, x), y))(p)

    xla = _cost(step, params, x, y)["flops"] / 4
    ours = flops.train_image(SMALL)
    assert 0.85 * xla <= ours <= xla
    assert ours < 3 * flops.forward_image(SMALL)


@pytest.mark.parametrize("n,d", [(4, 4096), (8, 1000)])
def test_commit_counts_against_the_xla_form(n, d):
    x = jnp.ones((n, d))
    W = jnp.full((n, n), 1.0 / n)
    g = jnp.arange(n) % 2 == 0

    def commit(x, W, g):
        merged = jnp.dot(W, x, precision=jax.lax.Precision.HIGHEST)
        return jnp.where(g[:, None], merged, x)

    cost = _cost(commit, x, W, g)
    ours = flops.commit([d], n, "f32")
    # the fused kernel's bytes are its operands and result, once each:
    # the least any form of the commit can move
    assert ours["bytes"] == (2 * n * d + n * n + n) * 4
    assert ours["bytes"] <= cost["bytes accessed"]
    assert ours["flops"] == 2 * n * n * d
    assert ours["flops"] <= cost["flops"] <= ours["flops"] + 4 * n * d
    q = flops.commit([d, 7], n, "int8")
    assert q["bytes"] == sum((4 * n * k + n * n + n) * 4 for k in (d, 7))
    assert q["launches"] == 2


def test_least_seconds_takes_the_binding_roof():
    v5e = flops.peak("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    mem = {"flops": 1e9, "bytes": 819e9}
    assert flops.least_seconds(mem, v5e) == pytest.approx(1.0)
    comp = {"flops": 197e12 * 2, "bytes": 1.0}
    assert flops.least_seconds(comp, v5e) == pytest.approx(2.0)


def test_an_unknown_chip_is_an_error():
    with pytest.raises(ValueError, match="no published peaks"):
        flops.peak("cpu")


def test_padding_taps_are_not_counted():
    # a 3x3 SAME convolution over 4 pixels: 2 + 3 + 3 + 2 taps per axis
    assert flops._taps(4, 3, 1) == 10
    # the stride-2 7x7 stem over 8 pixels: out 4, low pad 2
    assert flops._taps(8, 7, 2) == sum(
        min(o * 2 - 2 + 7, 8) - max(o * 2 - 2, 0) for o in range(4))
    assert np.isclose(flops._conv(4, 1, 5, 6), 2 * 16 * 5 * 6)
