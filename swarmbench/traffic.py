"""The one traffic generator: histopathology-like images, site shards and
the round blocks a swarm trainer feeds, all from a seed.

A copy of the program's generators (`repro.data.synthetic`
``make_histo_dataset`` / ``paper_splits`` / ``shard_to_nodes`` /
``batches`` / ``augment`` and `repro.experiments.histo` ``_batch_stream`` /
``_stack_vals``), kept here so the benchmark's inputs cannot move when the
program changes. A traffic mix is a JSON file of parameters under
``swarmbench/traffic/``; the model configuration names the data set.
"""
from __future__ import annotations

import numpy as np

_STAIN_REF = np.array([0.65, 0.70, 0.29])


def seed32(seed: int) -> int:
    """A 32-bit seed derived from any whole number (the driver's seeds may
    pass 2**31)."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0])


def _class_texture(rng, size: int, cls: int) -> np.ndarray:
    freq = [2, 5, 9][cls]
    phase = rng.uniform(0, 2 * np.pi, (2,))
    xx, yy = np.meshgrid(np.linspace(0, 2 * np.pi, size),
                         np.linspace(0, 2 * np.pi, size))
    base = np.sin(freq * xx + phase[0]) * np.cos(freq * yy + phase[1])
    blobs = rng.normal(0, 1, (size // 8, size // 8))
    blobs = np.kron(blobs, np.ones((8, 8)))[:size, :size]
    mix = [0.7, 0.5, 0.3][cls]
    return mix * base + (1 - mix) * blobs


def make_histo_dataset(n, *, size, class_probs, noise, seed):
    """(images [n, size, size, 3] f32, labels [n] int32), stain-normalized."""
    rng = np.random.default_rng(seed)
    probs = np.asarray(class_probs, float)
    probs = probs / probs.sum()
    labels = rng.choice(len(probs), size=n, p=probs).astype(np.int32)
    images = np.empty((n, size, size, 3), np.float32)
    for i, y in enumerate(labels):
        tex = _class_texture(rng, size, int(y))
        chan_w = _STAIN_REF * (1.0 + 0.3 * np.eye(3)[y % 3])
        images[i] = (tex[..., None] * chan_w[None, None, :]
                     + noise * rng.normal(0, 1, (size, size, 3)))
    mu = images.mean(axis=(1, 2), keepdims=True)
    sd = images.std(axis=(1, 2), keepdims=True) + 1e-6
    return ((images - mu) / sd * _STAIN_REF).astype(np.float32), labels


def augment(images, rng):
    """Flips, 90-degree rotations and a colour jitter of +-0.1."""
    out = images.copy()
    n = len(out)
    flip = rng.random(n) < 0.5
    out[flip] = out[flip, :, ::-1]
    rot = rng.integers(0, 4, n)
    for k in range(1, 4):
        idx = rot == k
        out[idx] = np.rot90(out[idx], k=k, axes=(1, 2))
    return out * (1.0 + rng.uniform(-0.1, 0.1, (n, 1, 1, 3)).astype(
        np.float32))


def paper_splits(n_total, fractions):
    sizes = [int(round(f * n_total)) for f in fractions]
    sizes[-1] = n_total - sum(sizes[:-1])
    return sizes


def shard_to_nodes(images, labels, sizes, *, seed):
    order = np.random.default_rng(seed).permutation(len(labels))
    images, labels = images[order], labels[order]
    cuts = np.cumsum([0] + list(sizes))
    return [(images[a:b], labels[a:b]) for a, b in zip(cuts[:-1], cuts[1:])]


def _epochs(x, y, batch, rng):
    """Shuffled minibatches of one epoch, augmented (drops the remainder)."""
    order = rng.permutation(len(y))
    for start in range(0, len(order) - batch + 1, batch):
        idx = order[start:start + batch]
        yield augment(x[idx], rng), y[idx]


def batch_stream(trains, steps, batch, seed):
    """(xs [steps, N, B, H, W, 3], ys [steps, N, B]): each site walks its own
    shuffled epochs; a shard smaller than a batch resamples with
    replacement."""
    n = len(trains)
    rngs = [np.random.default_rng(seed * 100 + i) for i in range(n)]
    iters = [iter(()) for _ in range(n)]
    h = trains[0][0].shape[1]
    xs = np.empty((steps, n, batch, h, h, 3), np.float32)
    ys = np.empty((steps, n, batch), np.int32)
    for s in range(steps):
        for i, (x, y) in enumerate(trains):
            if len(y) < batch:
                idx = rngs[i].integers(0, len(y), batch)
                xs[s, i], ys[s, i] = augment(x[idx], rngs[i]), y[idx]
                continue
            b = next(iters[i], None)
            if b is None:
                iters[i] = _epochs(x, y, batch, rngs[i])
                b = next(iters[i])
            xs[s, i], ys[s, i] = b
    return xs, ys


def stack_vals(vals):
    """Per-site validation sets padded to one length, with a validity
    mask: (vx [N, V, H, W, 3], vy [N, V], vm [N, V])."""
    n, vmax = len(vals), max(len(y) for _, y in vals)
    h = vals[0][0].shape[1]
    vx = np.zeros((n, vmax, h, h, 3), np.float32)
    vy = np.zeros((n, vmax), np.int32)
    vm = np.zeros((n, vmax), bool)
    for i, (x, y) in enumerate(vals):
        vx[i, :len(y)], vy[i, :len(y)], vm[i, :len(y)] = x, y, True
    return vx, vy, vm


def build(config: dict, traffic: dict, seed: int):
    """The cell's inputs from ``seed``: a pool of ``traffic["pool_rounds"]``
    round blocks ``(xs [T, N, B, ...], ys [T, N, B])``, the padded
    validation sets and each site's shard size."""
    data = config["data"]
    s = seed32(seed)
    images, labels = make_histo_dataset(
        data["n_train"], size=config["image_size"],
        class_probs=data["class_probs"], noise=data["noise"], seed=s)
    shards = shard_to_nodes(images, labels,
                            paper_splits(data["n_train"], data["fractions"]),
                            seed=s)
    vals, trains = [], []
    for x, y in shards:
        n_val = max(8, int(len(y) * data["val_frac"]))
        vals.append((x[:n_val], y[:n_val]))
        trains.append((x[n_val:], y[n_val:]))
    t, rounds = traffic["sync_every"], traffic["pool_rounds"]
    xs, ys = batch_stream(trains, rounds * t, traffic["batch"], s)
    pool = [(xs[r * t:(r + 1) * t], ys[r * t:(r + 1) * t])
            for r in range(rounds)]
    return {"pool": pool, "val": stack_vals(vals),
            "data_sizes": [len(y) for _, y in shards],
            "valid_val": sum(len(y) for _, y in vals)}
