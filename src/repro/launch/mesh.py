"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device state
(the dry-run sets XLA_FLAGS before any jax import; smoke tests must keep
seeing 1 CPU device).

  single-pod: (16, 16)    axes ("data", "model")      — 256 chips (v5e pod)
  multi-pod:  (2, 16, 16) axes ("pod", "data", "model") — 512 chips

Swarm view: the P2P-SL gossip axis is `pod` on the multi-pod mesh (1 hospital
= 1 pod; gossip is the only cross-DCN traffic) and a factored `node` axis on
the single-pod swarm mesh.
"""
from __future__ import annotations

import numpy as np

# The single declared mesh-axis registry. Every axis name that appears in a
# collective call site or mesh construction anywhere in the repo must come
# from this tuple — `repro.analysis` (swarmlint SWL001) parses this constant
# at lint time and flags literal drift, so adding a new physical axis means
# adding it HERE first.
MESH_AXES = ("pod", "node", "data", "model")


def make_production_mesh(*, multi_pod: bool = False):
    import jax

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"need {n} devices, have {len(devs)} — the dry-run must set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "any jax import")
    return jax.make_mesh(shape, axes, devices=devs[:n])


def make_two_level_swarm_mesh(n_pods: int = 2, per_pod: int = 2):
    """Two-level swarm mesh: ``(n_pods, per_pod)`` over ``("pod", "node")``.

    The swarm axis is the AXIS TUPLE ``("pod", "node")`` — flat gossip
    schedules run over the joint axis unchanged, while the `core.comms`
    per-link-class cost model may lower to the hierarchical pod-delegate
    schedules (`core.gossip.hier_*_ring_q8`) that keep bulk traffic
    intra-pod. Devices are row-major: device ``p·per_pod + j`` is node ``j``
    of pod ``p`` (the layout `launch.hlo_stats.pod_device_map` assumes).
    Returns ``(mesh, ("pod", "node"))``.
    """
    import jax

    n = n_pods * per_pod
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"need {n} devices, have {len(devs)} — set "
            "XLA_FLAGS=--xla_force_host_platform_device_count before any "
            "jax import to simulate the two-level mesh on CPU")
    mesh = jax.make_mesh((n_pods, per_pod), ("pod", "node"),
                         devices=devs[:n])
    return mesh, ("pod", "node")


def make_swarm_mesh(n_nodes: int = 4, *, multi_pod: bool = False):
    """Swarm training mesh: leading `node` axis is the gossip axis, built
    from the devices present. Returns ``(mesh, axis_name)``.

    fewer than 256 devices (one host of 1-4 chips, or a forced CPU device
    count): a 1-D ``("node",)`` mesh over the first ``n_nodes`` devices —
    one site per device, e.g. four sites on a v5e 2x2 host.
    256-chip pod: (node, data, model) = (n, 16 // n, 16) — the data axis of
    the production mesh factored into (node, data).
    multi-pod: gossip over `pod` — (pod, data, model) = (2, 16, 16), i.e. the
    production mesh itself; swarm code treats `pod` as the node axis.
    """
    import jax

    if multi_pod:
        mesh = make_production_mesh(multi_pod=True)
        return mesh, "pod"
    devs = jax.devices()
    if len(devs) < 256:
        if len(devs) < n_nodes:
            raise RuntimeError(
                f"one site per device needs {n_nodes} devices, have "
                f"{len(devs)}")
        return jax.make_mesh((n_nodes,), ("node",),
                             devices=devs[:n_nodes]), "node"
    if 16 % n_nodes:
        raise ValueError("n_nodes must divide 16 on the single-pod mesh")
    shape = (n_nodes, 16 // n_nodes, 16)
    devs = devs[: int(np.prod(shape))]
    return jax.make_mesh(shape, ("node", "data", "model"), devices=devs), "node"
