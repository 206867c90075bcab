"""Roofline-term extraction from compiled dry-run artifacts.

compute term    = HLO_FLOPs / (chips × peak)
memory term     = HLO_bytes / (chips × HBM bw)
collective term = collective_bytes / (chips × link bw)

``cost_analysis`` provides flops/bytes; collective bytes are parsed out of the
(post-SPMD-partitioning) HLO text by summing the result-shape bytes of every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute.

Hardware constants: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI.
"""
from __future__ import annotations

import json
import re
from dataclasses import dataclass, field
from typing import Dict

PEAK_FLOPS = 197e12       # bf16 per chip
HBM_BW = 819e9            # bytes/s per chip
ICI_BW = 50e9             # bytes/s per link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# Matches the OP USE position (` all-reduce(`, ` all-gather-start(`, ...),
# not the instruction NAME (`%all-reduce.3 = ...`). Result types — possibly a
# tuple with /*index=k*/ comments — sit between `=` and the op keyword.
# `-done` variants are skipped (the `-start` already carries the bytes).
_OP_RE = re.compile(
    r"=\s*(.*?)\s"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")

_SHAPE_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")
_COMMENT_RE = re.compile(r"/\*.*?\*/")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(_COMMENT_RE.sub("", type_str)):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-collective-kind result bytes summed over the module (per device)."""
    out = {k: 0 for k in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        out[m.group(2)] += _shape_bytes(m.group(1))
        out["count"] += 1
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


# --- tile padding (TPU layouts) ---------------------------------------------
# A TPU array is stored in tiles over its most minor dims: ``T(8,128)`` rounds
# the minor dim up to 128 lanes and the one above it to 8 sublanes. A shape
# whose minor dim is 32 is stored at 4x its size. `tile_padding` sums, over
# the instructions of the computations that run unfused (the entry, loop
# bodies), the bytes of each result and operand as stored and as logical.

_ARRAY_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\](?:\{([0-9,]*)"
                       r"(?::([^}]*))?\})?")
_TILE_RE = re.compile(r"T\(([0-9,]+)\)")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = (.*?) ([\w\-]+)\((.*)$")
_COMP_RE = re.compile(r"^(ENTRY\s+)?%([\w.\-]+) .*\{\s*$")
_CALLED_RE = re.compile(r"\b(?:calls|to_apply)=%([\w.\-]+)")
# no bytes move: names, views, bookkeeping
_NO_BYTES = {"parameter", "constant", "get-tuple-element", "tuple",
             "bitcast", "after-all", "copy-start", "copy-done", "partition-id",
             "replica-id", "iota"}


def _array_bytes(type_str: str):
    """(stored, logical) bytes of every array in ``type_str``."""
    stored = logical = 0
    for dt, dims, layout, tiling in _ARRAY_RE.findall(
            _COMMENT_RE.sub("", type_str)):
        if dt not in _DTYPE_BYTES:
            continue
        shape = [int(d) for d in dims.split(",") if d]
        n = 1
        for d in shape:
            n *= d
        logical += n * _DTYPE_BYTES[dt]
        tile = _TILE_RE.search(tiling or "")
        if tile and layout:
            minor_to_major = [int(d) for d in layout.split(",") if d]
            padded = list(shape)
            for t, dim in zip(reversed(tile.group(1).split(",")),
                              minor_to_major):
                t = int(t)
                padded[dim] = -(-padded[dim] // t) * t
            n = 1
            for d in padded:
                n *= d
        stored += n * _DTYPE_BYTES[dt]
    return stored, logical


def tile_padding(hlo_text: str):
    """(stored, logical) bytes of the results and operands of every
    instruction in the unfused computations of a compiled module's text
    (``Compiled.as_text()``); fusion bodies and reducers are left out, and
    so are instructions that move no bytes (`_NO_BYTES`)."""
    types, comps, current = {}, {}, None
    for line in hlo_text.splitlines():
        c = _COMP_RE.match(line)
        if c:
            current = comps.setdefault(c.group(2), [])
            continue
        m = _INSTR_RE.match(line)
        if m and current is not None:
            types[m.group(1)] = m.group(2)
            current.append(m)
    called = set(_CALLED_RE.findall(hlo_text))
    stored = logical = 0
    for name, instrs in comps.items():
        if name in called:
            continue
        for m in instrs:
            if m.group(3) in _NO_BYTES:
                continue
            operands = re.findall(r"%([\w.\-]+)", m.group(4).split("), ")[0])
            for t in [m.group(2)] + [types.get(o, "") for o in operands]:
                s, l = _array_bytes(t)
                stored += s
                logical += l
    return stored, logical


_CONFIGURED_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*?)backend_config=", re.M)
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')


def estimated_cycles(hlo_text: str) -> list:
    """The TPU compiler's estimate for each instruction of a compiled
    module's text (``Compiled.as_text()``) that carries one: dicts of its
    ``name``, ``cycles``, ``op_name`` and, for a convolution, the
    ``emitter`` the compiler picked; the most cycles first. These are
    compiler estimates, not times, and a loop's body counts once."""
    decode = json.JSONDecoder().raw_decode
    out = []
    for m in _CONFIGURED_RE.finditer(hlo_text):
        try:
            config, _ = decode(hlo_text, m.end())
        except json.JSONDecodeError:
            continue
        cycles = config.get("window_config", {}).get("estimated_cycles")
        if cycles is None:
            continue
        op_name = _OP_NAME_RE.search(m.group(2))
        out.append({"name": m.group(1), "cycles": int(cycles),
                    "op_name": op_name.group(1) if op_name else "",
                    "emitter": config.get("convolution_algorithm_config",
                                          {}).get("emitter")})
    return sorted(out, key=lambda i: -i["cycles"])


# --- per-link-class split (two-level ("pod", "node") meshes) ----------------
# A collective participates in exactly one link class: "intra" when every one
# of its device groups (or source→target pairs) stays inside a single pod,
# "cross" as soon as any group spans pods — a global collective over the
# joint axis is bounded by its slowest (DCN) hop, so its whole payload prices
# as cross. This mirrors the `core.comms` analytic convention (flat schedules
# on a 2-D mesh carry cross_factor = payload_factor).

# literal groups: replica_groups={{0,1},{2,3}}
_GROUPS_RE = re.compile(r"replica_groups=\{(\{[^}]*\}(?:,\{[^}]*\})*)\}")
# iota form: replica_groups=[2,2]<=[4] — reshape iota(4) to [2,2], rows are
# groups; an optional T(perm) transposes the iota source first
_IOTA_RE = re.compile(
    r"replica_groups=\[([0-9,]+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?")
_PAIRS_RE = re.compile(r"source_target_pairs=\{(\{[^}]*\}(?:,\{[^}]*\})*)\}")


def _iota_list(src_dims, perm):
    """iota(prod(src_dims)) reshaped to src_dims, transposed by perm (or
    identity), flattened — pure-python strides."""
    total = 1
    for d in src_dims:
        total *= d
    if perm is None:
        return list(range(total))
    tshape = [src_dims[p] for p in perm]
    # row-major strides of the source shape
    strides = [1] * len(src_dims)
    for i in range(len(src_dims) - 2, -1, -1):
        strides[i] = strides[i + 1] * src_dims[i + 1]
    out = []
    for k in range(total):
        rem, tidx = k, []
        for d in reversed(tshape):
            tidx.append(rem % d)
            rem //= d
        tidx.reverse()
        out.append(sum(strides[perm[i]] * tidx[i] for i in range(len(perm))))
    return out


def _parse_groups(line: str):
    """Device groups of one collective instruction, or None if unparseable
    (an empty ``replica_groups={}`` means "all devices" and also maps to
    None — both conservatively classify as cross)."""
    m = _GROUPS_RE.search(line)
    if m:
        return [[int(x) for x in g.split(",") if x]
                for g in m.group(1).strip("{}").split("},{")]
    m = _IOTA_RE.search(line)
    if m:
        dims = [int(x) for x in m.group(1).split(",")]
        src = [int(x) for x in m.group(2).split(",")]
        perm = ([int(x) for x in m.group(3).split(",")]
                if m.group(3) else None)
        flat = _iota_list(src, perm)
        group_len = dims[-1]
        return [flat[i:i + group_len] for i in range(0, len(flat), group_len)]
    m = _PAIRS_RE.search(line)
    if m:
        return [[int(x) for x in g.split(",") if x]
                for g in m.group(1).strip("{}").split("},{")]
    return None


def pod_device_map(n_pods: int, per_pod: int) -> Dict[int, int]:
    """device id → pod id for the row-major ``(pod, node)`` mesh layout of
    `launch.mesh.make_two_level_swarm_mesh` (device p·per_pod + j ∈ pod p)."""
    return {p * per_pod + j: p
            for p in range(n_pods) for j in range(per_pod)}


def collective_bytes_by_link(hlo_text: str,
                             pod_of: Dict[int, int]) -> Dict[str, int]:
    """Split :func:`collective_bytes` per link class on a two-level mesh.

    ``pod_of`` maps device id → pod id (see :func:`pod_device_map`). An
    instruction whose every replica group / permute pair stays inside one
    pod counts as ``intra``; any pod-spanning group — or unparseable /
    unknown-device groups — counts as ``cross`` (unattributed traffic must
    never inflate the cheap class)."""
    out = {"intra": 0, "cross": 0, "count": 0}

    def one_pod(group) -> bool:
        pods = set()
        for d in group:
            if d not in pod_of:
                return False
            pods.add(pod_of[d])
        return len(pods) <= 1

    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        groups = _parse_groups(line)
        intra = groups is not None and all(one_pod(g) for g in groups)
        out["intra" if intra else "cross"] += _shape_bytes(m.group(1))
        out["count"] += 1
    out["total"] = out["intra"] + out["cross"]
    return out


@dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float
    hlo_bytes: float
    coll_bytes: float
    model_flops: float = 0.0
    coll_detail: dict = field(default_factory=dict)

    @property
    def compute_s(self) -> float:
        return self.hlo_flops / (self.chips * PEAK_FLOPS)

    @property
    def memory_s(self) -> float:
        return self.hlo_bytes / (self.chips * HBM_BW)

    @property
    def collective_s(self) -> float:
        # coll_bytes is already per-device (post-partition HLO result shapes)
        return self.coll_bytes / ICI_BW

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def useful_ratio(self) -> float:
        return self.model_flops / self.hlo_flops if self.hlo_flops else 0.0

    def row(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips,
            "hlo_flops": self.hlo_flops, "hlo_bytes": self.hlo_bytes,
            "coll_bytes": self.coll_bytes,
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s, "dominant": self.dominant,
            "model_flops": self.model_flops, "useful_ratio": self.useful_ratio,
            "coll_detail": self.coll_detail,
        }


def roofline_from_compiled(compiled, *, arch, shape, mesh_name, chips,
                           model_flops=0.0) -> Roofline:
    cost = compiled.cost_analysis()
    if isinstance(cost, list):  # older jax returns [dict]
        cost = cost[0]
    flops = float(cost.get("flops", 0.0))
    mem = float(cost.get("bytes accessed", 0.0))
    txt = compiled.as_text()
    coll = collective_bytes(txt)
    return Roofline(arch=arch, shape=shape, mesh=mesh_name, chips=chips,
                    hlo_flops=flops, hlo_bytes=mem,
                    coll_bytes=float(coll["total"]), model_flops=model_flops,
                    coll_detail=coll)
