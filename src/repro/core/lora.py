"""LoRA adapters as the swarm exchange payload.

The paper's nodes exchange **LoRA-adapter weights only** (every 3 epochs,
gRPC/TLS). Here adapters are injected directly into the param pytree: any
2-D (or stacked 3-D, scan-over-layers) projection matrix named ``w`` under a
matching module gains ``lora_A``/``lora_B``/``lora_scale`` siblings, which
``repro.models.layers.linear`` applies transparently — zero model changes.

``split_adapters`` partitions a pytree into (adapters, base); the swarm sync
then merges only the adapter subtree, shrinking the gossip payload by ~99%
(see EXPERIMENTS.md §Perf for the measured collective-byte effect).
"""
from __future__ import annotations

import re
from typing import Tuple

import jax
import jax.numpy as jnp

DEFAULT_TARGETS = r"(attn|cross|mlp|experts|in_proj|out_proj|lm_head|head)"


def inject_lora(params, key, rank: int = 16, alpha: float = 32.0,
                targets: str = DEFAULT_TARGETS):
    """Returns a new pytree with LoRA params added to matching linears."""
    keys = iter(jax.random.split(key, 4096))

    def rec(node, path):
        if not isinstance(node, dict):
            if isinstance(node, list):
                return [rec(v, f"{path}/{i}") for i, v in enumerate(node)]
            return node
        out = {k: rec(v, f"{path}/{k}") for k, v in node.items()}
        w = node.get("w")
        if (
            w is not None
            and hasattr(w, "ndim")
            and w.ndim in (2, 3)
            and re.search(targets, path)
            and "lora_A" not in node
        ):
            if w.ndim == 2:
                i, o = w.shape
                a_shape, b_shape = (i, rank), (rank, o)
                scale = jnp.asarray(alpha / rank, jnp.float32)
            else:  # stacked over layers: [L, in, out] — scale must scan too
                l, i, o = w.shape
                a_shape, b_shape = (l, i, rank), (l, rank, o)
                scale = jnp.full((l,), alpha / rank, jnp.float32)
            out["lora_A"] = (jax.random.normal(next(keys), a_shape)
                             / jnp.sqrt(rank)).astype(w.dtype)
            out["lora_B"] = jnp.zeros(b_shape, w.dtype)
            out["lora_scale"] = scale
        return out

    return rec(params, "")


def is_adapter_path(path: str) -> bool:
    return "lora_" in path


def _paths(tree):
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return flat


def payload_path_str(path) -> str:
    """Canonical "/"-joined path string for a tree_*_with_path key tuple."""
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def flatten_payload(params, select=None):
    """Flatten the wire-payload subtree to ONE flat ``{path: leaf}`` dict.

    ``select(path_str) -> bool`` picks the leaves that cross the wire;
    the default is :func:`is_adapter_path` (``lora_`` leaves). The result is
    sorted by path, so every node with the same payload interface — whatever
    its backbone architecture — produces a structurally identical pytree
    that stacks along a node axis. :func:`unflatten_payload` is the inverse
    against a full-params template.

    This is THE single adapter flatten implementation (swarmlint SWL004
    sole-impl ``adapter_flatten``): engine, gossip, and kernel paths all
    share it, so payload membership can never silently diverge between what
    is merged, what is quantized, and what is checkpointed.
    """
    if select is None:
        select = is_adapter_path
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    out = {payload_path_str(p): x for p, x in flat
           if select(payload_path_str(p))}
    if not out:
        raise ValueError("flatten_payload: no leaf matched the payload "
                         "selector (nothing would cross the wire)")
    return dict(sorted(out.items()))


def unflatten_payload(flat, template):
    """Inverse of :func:`flatten_payload`: write the flat payload leaves
    back into a full-params ``template`` pytree (the frozen local backbone
    plus payload placeholders). Leaves whose path is not in ``flat`` pass
    through from the template untouched; gradients flow through the payload
    leaves only — exactly the frozen-backbone fine-tuning contract."""
    used = set()

    def sub(p, x):
        s = payload_path_str(p)
        if s in flat:
            used.add(s)
            return flat[s]
        return x

    out = jax.tree_util.tree_map_with_path(sub, template)
    missing = set(flat) - used
    if missing:
        raise ValueError("unflatten_payload: payload paths not present in "
                         f"the template: {sorted(missing)[:4]}")
    return out


def _without(tree, drop, prefix=""):
    """``tree`` (nested dicts and lists) without the leaves whose path is in
    ``drop``."""
    if isinstance(tree, dict):
        return {k: _without(v, drop, f"{prefix}{k}/") for k, v in tree.items()
                if f"{prefix}{k}" not in drop}
    if isinstance(tree, list):
        return [_without(v, drop, f"{prefix}{i}/") for i, v in enumerate(tree)]
    return tree


def split_payload(params, select=None):
    """(payload, base): the leaves ``select`` picks (default: the adapters)
    as one flat path-keyed dict (`flatten_payload`), and ``params`` without
    them: the frozen base that sites sharing one backbone hold once
    (`SwarmSession`'s ``shared``)."""
    flat = flatten_payload(params, select)
    return flat, _without(params, set(flat))


def attach_payload(flat, base):
    """Inverse of :func:`split_payload`: ``base`` with the flat payload's
    leaves written in at their paths, the containers along each path copied
    (``base`` itself is left as it was)."""
    def put(node, keys, leaf):
        node = list(node) if isinstance(node, list) else dict(node)
        k = int(keys[0]) if isinstance(node, list) else keys[0]
        node[k] = leaf if len(keys) == 1 else put(node[k], keys[1:], leaf)
        return node

    for path, leaf in flat.items():
        base = put(base, path.split("/"), leaf)
    return base


def split_adapters(params, is_leaf=None) -> Tuple[dict, dict]:
    """(adapters, base) — same treedef, non-matching leaves replaced by None.

    is_leaf: forwarded to tree_map_with_path (needed when leaves are
    PartitionSpecs, which are tuple subclasses jax would recurse into).
    """
    def path_str(p):
        return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in p)

    def select(pred):
        return jax.tree_util.tree_map_with_path(
            lambda p, x: x if pred(path_str(p)) else None, params,
            is_leaf=is_leaf)

    return select(is_adapter_path), select(lambda s: not is_adapter_path(s))


def combine(adapters, base):
    """Inverse of split_adapters."""
    return jax.tree.map(
        lambda a, b: a if b is None else b, adapters, base,
        is_leaf=lambda x: x is None)


def adapter_only(params):
    """Pytree with ONLY adapter leaves (others None) — the sync payload."""
    return split_adapters(params)[0]


def merge_lora_into_base(params):
    """Fold A@B into w and drop adapters (deployment export)."""
    def rec(node):
        if isinstance(node, list):
            return [rec(v) for v in node]
        if not isinstance(node, dict):
            return node
        out = {k: rec(v) for k, v in node.items() if not k.startswith("lora_")}
        if "lora_A" in node:
            a, b = node["lora_A"], node["lora_B"]
            scale = node["lora_scale"].astype(jnp.float32)
            delta = jnp.einsum("...ir,...ro->...io",
                               a.astype(jnp.float32), b.astype(jnp.float32))
            if scale.ndim == 1:  # stacked-over-layers scale [L]
                scale = scale[:, None, None]
            out["w"] = (node["w"].astype(jnp.float32) + scale * delta).astype(node["w"].dtype)
        return out

    return rec(params)


def payload_bytes(params, lora_only: bool) -> int:
    """Sync payload size — the paper's communication-efficiency claim."""
    tree = adapter_only(params) if lora_only else params
    return int(sum(x.size * x.dtype.itemsize
                   for x in jax.tree.leaves(tree) if x is not None))
