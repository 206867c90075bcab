"""Operations and bytes the algorithm needs, from shapes, and the table of
chip peaks they are held against.

Model FLOPs count the convolutions and the head's matrix products (two
operations per multiply-add); batch norm, activations and pooling are left
out, so a share of a peak built from them errs low, never high.
"""
from __future__ import annotations

import math

# Published peaks per chip, keyed by `jax.Device.device_kind`.
PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "Google Cloud, TPU v5e"},
}


def peak(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a chip not in the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; add them to swarmbench/flops.py "
                         "with their source") from None


def _taps(n, k, stride):
    """Kernel taps that land inside an axis of ``n`` pixels, summed over the
    output positions of a SAME convolution (taps on the zero padding do no
    work the model needs)."""
    out = math.ceil(n / stride)
    lo = max((out - 1) * stride + k - n, 0) // 2
    return sum(min(o * stride - lo + k, n) - max(o * stride - lo, 0)
               for o in range(out))


def _conv(h, k, cin, cout, stride=1):
    return 2 * _taps(h, k, stride) ** 2 * cin * cout


def _layers(config: dict):
    """(stem FLOPs, all FLOPs) of one image's forward pass."""
    h = config["image_size"]
    stem = _conv(h, 7, 3, config["stem"], stride=2)
    total = stem
    h = math.ceil(math.ceil(h / 2) / 2)    # stride-2 stem, 3x3 max pool SAME
    c = config["stem"]
    for b in range(config["n_blocks"]):
        for _ in range(config["layers_per_block"]):
            total += _conv(h, 3, c, config["growth"])
            c += config["growth"]
        out = c // 2 if b < config["n_blocks"] - 1 else config["feat_dim"]
        total += _conv(h, 1, c, out)
        c = out
        if h >= 2:
            h //= 2                                    # 2x2 average, VALID
    total += 2 * config["feat_dim"] * config["hidden"]
    total += 2 * config["hidden"] * config["n_classes"]
    return stem, total


def forward_image(config: dict) -> int:
    """Model FLOPs of one image's forward pass."""
    return _layers(config)[1]


def train_image(config: dict) -> int:
    """Forward and backward of one image: the backward pass computes each
    layer's weight gradient and input gradient, each as costly as its
    forward product, except the stem's input gradient (the images need
    none)."""
    stem, total = _layers(config)
    return 3 * total - stem


def commit(leaf_sizes, n: int, wire: str) -> dict:
    """Bytes and FLOPs of one round's commit kernels, one launch per leaf of
    ``leaf_sizes`` elements a site: every site's tile read and written once
    (f32), plus the wire reference read and written (int8 error feedback),
    plus the [n, n] mixing matrix and the gate column; W times the tile."""
    streams = {"f32": 2, "int8": 4}[wire]
    nbytes = sum(streams * n * d * 4 + n * n * 4 + n * 4 for d in leaf_sizes)
    return {"bytes": nbytes, "flops": sum(2 * n * n * d for d in leaf_sizes),
            "launches": len(leaf_sizes)}


def least_seconds(work: dict, peaks: dict) -> float:
    """The least time the chip could take for ``work``: the larger of its
    operations at the peak rate and its bytes at the memory bandwidth."""
    return max(work["flops"] / peaks["bf16_flops_per_s"],
               work["bytes"] / peaks["hbm_bytes_per_s"])
