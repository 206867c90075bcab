"""The paper's own diagnostic model: DenseNet-style encoder + classifier head.

Faithful to §3.3 of the paper: input images pass through **four encoder
modules of four layers each**, pooled to a feature vector (the paper reports
1152 features into the head), then FC(→512)+BN+ReLU, then FC(512→3)+BN with a
sigmoid applied at the loss. TorchXRayVision's pre-trained weights are not
available offline; we reproduce the *architecture* and treat "pre-trained"
as a warm-start option (`init_cnn(..., pretrained_key=...)` reuses a shared
seed across nodes — all swarm nodes start from the same backbone, exactly the
effect pre-training has on the swarm experiment).

BatchNorm note: implemented in batch-statistics mode (no running averages) to
stay purely functional; with the paper's batch size (32) this is the standard
train-mode behaviour. Recorded as a simplification in DESIGN.md.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig


def _conv_init(key, kh, kw, cin, cout):
    fan_in = kh * kw * cin
    return jax.random.normal(key, (kh, kw, cin, cout)) * jnp.sqrt(2.0 / fan_in)


def conv2d(w, x, stride=1, padding="SAME"):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def batchnorm(p, x, eps=1e-5):
    axes = tuple(range(x.ndim - 1))
    mu = jnp.mean(x, axes, keepdims=True)
    var = jnp.var(x, axes, keepdims=True)
    xn = (x - mu) * jax.lax.rsqrt(var + eps)
    return xn * p["scale"] + p["bias"]


def _bn_init(c):
    return {"scale": jnp.ones((c,)), "bias": jnp.zeros((c,))}


def init_cnn(key, cfg: ModelConfig, *, growth=32, stem=64, n_blocks=4,
             layers_per_block=4, feat_dim=1152, hidden=512, n_classes=3):
    """DenseNet-lite: n_blocks dense blocks × layers_per_block conv layers."""
    ks = iter(jax.random.split(key, 2 + n_blocks * (layers_per_block + 1) + 4))
    params = {"stem": {"w": _conv_init(next(ks), 7, 7, 3, stem), "bn": _bn_init(stem)}}
    c = stem
    blocks = []
    for b in range(n_blocks):
        layers = []
        for _ in range(layers_per_block):
            layers.append({"bn": _bn_init(c), "w": _conv_init(next(ks), 3, 3, c, growth)})
            c += growth
        trans_out = c // 2 if b < n_blocks - 1 else feat_dim
        blocks.append({
            "layers": layers,
            "trans": {"bn": _bn_init(c), "w": _conv_init(next(ks), 1, 1, c, trans_out)},
        })
        c = trans_out
    params["blocks"] = blocks
    params["head"] = {
        "fc1": {"w": jax.random.normal(next(ks), (feat_dim, hidden)) * jnp.sqrt(2.0 / feat_dim),
                "b": jnp.zeros((hidden,)), "bn": _bn_init(hidden)},
        "fc2": {"w": jax.random.normal(next(ks), (hidden, n_classes)) * jnp.sqrt(2.0 / hidden),
                "b": jnp.zeros((n_classes,)), "bn": _bn_init(n_classes)},
    }
    return params


def forward_cnn(params, images, *, return_features=False):
    """images [B,H,W,3] -> logits [B,3] (sigmoid applied at the loss)."""
    x = conv2d(params["stem"]["w"], images, stride=2)
    x = jax.nn.relu(batchnorm(params["stem"]["bn"], x))
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
    for block in params["blocks"]:
        for layer in block["layers"]:
            h = jax.nn.relu(batchnorm(layer["bn"], x))
            h = conv2d(layer["w"], h)
            x = jnp.concatenate([x, h], axis=-1)  # dense connectivity
        x = jax.nn.relu(batchnorm(block["trans"]["bn"], x))
        x = conv2d(block["trans"]["w"], x)
        if min(x.shape[1], x.shape[2]) >= 2:  # keep ≥1×1 for small test images
            x = jax.lax.reduce_window(x, 0.0, jax.lax.add, (1, 2, 2, 1),
                                      (1, 2, 2, 1), "VALID") / 4.0
    feats = jnp.mean(x, axis=(1, 2))  # global average pool -> [B, feat_dim]
    h = params["head"]["fc1"]
    z = feats @ h["w"] + h["b"]
    z = jax.nn.relu(batchnorm(h["bn"], z))
    penultimate = z
    h = params["head"]["fc2"]
    logits = batchnorm(h["bn"], z @ h["w"] + h["b"])
    if return_features:
        return logits, penultimate
    return logits


def bce_loss(logits, labels_onehot):
    """Paper head uses sigmoid -> multi-label BCE over the 3 classes."""
    logp = jax.nn.log_sigmoid(logits)
    lognp = jax.nn.log_sigmoid(-logits)
    return -jnp.mean(labels_onehot * logp + (1 - labels_onehot) * lognp)


# ---------------------------------------------------------------------------
# site-folded forward: N sites' models in the lanes of one activation
# ---------------------------------------------------------------------------
# `jax.vmap(forward_cnn)` keeps each site's channels as the minor (lane)
# axis of its activations; the TPU stores that axis in 128-lane tiles, and
# the paper's widths (growth 32, stem 64, blocks of 96-248) leave much of
# each tile as padding. The folded forward carries the sites inside the
# lane axis instead: activations ``[B, H, W, C·N]`` with the site as the
# minor index of each channel, so N = 4 growth outputs fill 128 lanes and
# the dense blocks' concatenations grow by whole lane groups. Batch norm
# and pooling act per lane, which is per site and channel; only the
# convolutions mix lanes, through weights that are block-diagonal across
# sites.
#
# Which stages run folded is chosen by shape. A forward alone (the gate)
# folds them all. A training step folds the stem and the encoder modules
# whose input keeps more than 1/TRAIN_FOLD_SHARE of the image's pixels (at
# 224 px the stem and the first module, which hold most of the activation
# bytes) and vmaps the smaller ones: each folded convolution's compiled
# code, forward and backward, is about twice the vmapped one's, the device
# holds that code in its memory, and the bytes folding saves shrink with
# the activations.
#
# The stem runs as a space-to-depth convolution (`stem_sites`) in a
# forward alone, and strided in a training step: there XLA already gives
# the strided form's kernel a fast layout (its weight gradient fixes it),
# and at 224 px it estimates the space-to-depth form's transpose and two
# convolutions at more cycles than the strided form's.

TRAIN_FOLD_SHARE = 64


def fold_sites(x):
    """``[N, ..., C]`` -> ``[..., C·N]``, the site the minor index."""
    return jnp.moveaxis(x, 0, -1).reshape(x.shape[1:-1] + (-1,))


def unfold_sites(x, n: int):
    """Inverse of `fold_sites`: ``[..., C·N]`` -> ``[N, ..., C]``."""
    return jnp.moveaxis(x.reshape(x.shape[:-1] + (-1, n)), -1, 0)


def conv2d_sites(w, x, stride=1, padding="SAME"):
    """Per-site convolution on folded activations: ``w [N, kh, kw, cin,
    cout]``, ``x [B, H, W, cin·N]`` -> ``[B, H', W', cout·N]``, as one
    convolution whose weight is zero between sites."""
    n, kh, kw, cin, cout = w.shape
    rows = jnp.moveaxis(w, 0, 3).reshape(kh, kw, cin * n, cout)
    site_in = jnp.arange(cin * n) % n
    site_out = jnp.arange(cout * n) % n
    block_diag = jnp.where(site_in[:, None] == site_out[None, :],
                           jnp.repeat(rows, n, axis=-1), 0.0)
    return conv2d(block_diag, x, stride, padding)


def stem_sites(w, images):
    """The stem's folded convolution, ``conv2d_sites(w, fold_sites(images),
    stride=2)`` (SAME), as a stride-1 convolution over 2x2 pixel blocks.

    ``w [N, k, k, cin, cout]``, ``images [N, B, H, W, cin]`` ->
    ``[B, ceil(H/2), ceil(W/2), cout·N]``. One transpose folds each 2x2
    block of pixels into the channels beside the sites, ``[B, H/2, W/2,
    4·cin·N]`` (the site the minor index), and the kernel, zero-padded to
    an even size, becomes a block kernel of half the size; the padding
    taps add exact zeros. For the paper's 7x7 stem over 12 folded
    features, XLA lays the strided convolution's kernel out with those
    features in the lanes and picks a slow emitter: at the gate's shape,
    4 sites x 38 rows x 224 px on a v5e, XLA estimates that convolution at
    ten times the cycles of this one, and at nearly four times this one
    and its transpose together."""
    n, b, h, wd, cin = images.shape
    k, cout = w.shape[1], w.shape[-1]
    kb = (k + 1) // 2

    def pads(size):
        # SAME's padding for a stride-2 window, then the zero-tap row:
        # pixels where they pair a pixel with padding, the rest in blocks
        out = (size + 1) // 2
        lo = max(2 * (out - 1) + k - size, 0) // 2
        hi = 2 * (out + kb - 1) - size - lo
        pix = (lo % 2, (size + lo) % 2)
        return pix, ((lo - pix[0]) // 2, (hi - pix[1]) // 2)

    (pix_h, blk_h), (pix_w, blk_w) = pads(h), pads(wd)
    x = jnp.pad(images, ((0, 0), (0, 0), pix_h, pix_w, (0, 0)))
    hb, wb = x.shape[2] // 2, x.shape[3] // 2
    x = x.reshape(n, b, hb, 2, wb, 2, cin).transpose(1, 2, 4, 3, 5, 6, 0)
    x = x.reshape(b, hb, wb, 4 * cin * n)
    w = jnp.pad(w, ((0, 0), (0, 2 * kb - k), (0, 2 * kb - k), (0, 0), (0, 0)))
    w = w.reshape(n, kb, 2, kb, 2, cin, cout).transpose(1, 3, 2, 4, 5, 0, 6)
    # block-diagonal across sites by a product with the identity: from a
    # select, as in `conv2d_sites`, XLA lays this kernel out with its
    # input features in the lanes, and estimates the convolution at twice
    # the cycles
    w = w[..., None] * jnp.eye(n, dtype=w.dtype)[:, None, :]
    return conv2d(w.reshape(kb, kb, 4 * cin * n, cout * n), x, 1,
                  (blk_h, blk_w))


def _bn_sites(p):
    """Per-site batch-norm params ``[N, C]`` -> per-lane ``[C·N]``."""
    return jax.tree.map(lambda v: v.T.reshape(-1), p)


def _dense_block(block, x, conv, bn):
    """One encoder module of `forward_cnn`, given its convolution and the
    layout of its batch-norm params."""
    for layer in block["layers"]:
        h = jax.nn.relu(batchnorm(bn(layer["bn"]), x))
        x = jnp.concatenate([x, conv(layer["w"], h)], axis=-1)
    x = jax.nn.relu(batchnorm(bn(block["trans"]["bn"]), x))
    x = conv(block["trans"]["w"], x)
    if min(x.shape[1], x.shape[2]) >= 2:
        x = jax.lax.reduce_window(x, 0.0, jax.lax.add, (1, 2, 2, 1),
                                  (1, 2, 2, 1), "VALID") / 4.0
    return x


def _site_block(block, x):
    return _dense_block(block, x, conv2d, lambda p: p)


def forward_cnn_sites(params, images, fold_share=None):
    """`forward_cnn` of N sites at once, on site-folded activations.

    ``params``: per-site params stacked on a leading site axis ``[N, ...]``;
    ``images [N, B, H, W, 3]`` -> per-site logits ``[N, B, 3]``, what
    ``jax.vmap(forward_cnn)(params, images)`` returns (up to the order of
    accumulation: the zero blocks add exact zeros). With ``fold_share``,
    the encoder modules whose input keeps no more than 1/fold_share of the
    image's pixels run per site, vmapped, instead."""
    n, pixels = images.shape[0], images.shape[2] * images.shape[3]
    with jax.named_scope("cnn.stem"):
        if fold_share is None:
            x = stem_sites(params["stem"]["w"], images)
        else:
            x = conv2d_sites(params["stem"]["w"], fold_sites(images), stride=2)
    x = jax.nn.relu(batchnorm(_bn_sites(params["stem"]["bn"]), x))
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
    blocks = list(params["blocks"])
    while blocks and (fold_share is None
                      or x.shape[1] * x.shape[2] * fold_share > pixels):
        x = _dense_block(blocks.pop(0), x, conv2d_sites, _bn_sites)
    # the barrier keeps XLA from moving the unfold's lane shuffle into the
    # pool before it, where it lays the pool out with a width as lanes
    x = unfold_sites(jax.lax.optimization_barrier(x), n)
    for block in blocks:
        x = jax.vmap(_site_block)(block, x)
    feats = jnp.mean(x, axis=(2, 3))  # [N, B, feat_dim]

    def head(h, f):
        z = f @ h["fc1"]["w"] + h["fc1"]["b"]
        z = jax.nn.relu(batchnorm(h["fc1"]["bn"], z))
        return batchnorm(h["fc2"]["bn"], z @ h["fc2"]["w"] + h["fc2"]["b"])

    return jax.vmap(head)(params["head"], feats)
