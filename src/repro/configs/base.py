"""Config dataclasses for the repro framework.

Every assigned architecture instantiates :class:`ModelConfig`; the swarm layer
is configured by :class:`SwarmConfig`; training by :class:`TrainConfig`.
Configs are plain frozen dataclasses so they hash (usable as jit static args).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple


# the mixers of an interleaved stack (`ModelConfig.layer_kinds`)
LAYER_KINDS = ("mamba", "attention")


@dataclass(frozen=True)
class ModelConfig:
    """Architecture description, rich enough for all 6 assigned families."""

    name: str = "unnamed"
    family: str = "dense"  # dense | moe | ssm | hybrid | vlm | audio (enc-dec)
    source: str = ""       # citation for the config numbers

    # transformer backbone
    n_layers: int = 2
    d_model: int = 256
    n_heads: int = 4
    n_kv_heads: int = 4
    d_ff: int = 1024
    vocab_size: int = 1000
    head_dim: int = 0          # 0 -> d_model // n_heads
    # swiglu | swiglu_fused (gate and up in one input projection) | sq_relu
    # | gelu
    activation: str = "swiglu"
    use_bias: bool = False
    tie_embeddings: bool = False
    norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    max_seq_len: int = 524_288
    logit_softcap: float = 0.0
    # attention variant
    sliding_window: int = 0     # 0 = full attention; >0 = window size
    attn_every: int = 0         # hybrid/SWA: full-attn every k-th layer (0=never)
    use_rope: bool = True       # False: no positional embedding ("nope")
    # interleaved stack: each layer's mixer, "mamba" or "attention", each
    # followed by the MLP (empty: one kind of block per family)
    layer_kinds: Tuple[str, ...] = ()
    # scalar multipliers (Granite): embeddings times, residual branches
    # times, logits divided by; the softmax scale (0: 1/sqrt(head_dim))
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    attention_multiplier: float = 0.0
    # tokens per chunk of the head's logits in the loss and the accuracy,
    # and query positions per chunk of the attention scores (0: all at once)
    loss_chunk: int = 0
    attn_chunk: int = 0

    # MoE
    n_experts: int = 0
    top_k: int = 0
    d_ff_expert: int = 0
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01

    # SSM (mamba2 / hybrid)
    ssm_state: int = 0
    ssm_heads: int = 0          # 0 -> derived: d_inner // ssm_head_dim
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_chunk: int = 256
    conv_width: int = 4
    ssm_groups: int = 1

    # enc-dec (audio family)
    n_enc_layers: int = 0       # >0 enables encoder-decoder
    enc_seq_len: int = 0        # encoder (frame) length for dry-run specs

    # multimodal frontends (stubs per assignment carve-out)
    n_patches: int = 0          # vlm: number of image patch embeddings
    frontend_dim: int = 0       # raw embedding dim out of the stub frontend

    # numerics
    param_dtype: str = "float32"
    compute_dtype: str = "float32"
    # dry-run: unroll scan-over-layers so cost_analysis counts every layer
    unroll_layers: bool = False
    # Megatron-style vocab padding so embedding/logits shard evenly
    vocab_pad_to: int = 256

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        if self.layer_kinds and len(self.layer_kinds) != self.n_layers:
            raise ValueError(f"{len(self.layer_kinds)} layer kinds for "
                             f"{self.n_layers} layers")
        if not set(self.layer_kinds) <= set(LAYER_KINDS):
            raise ValueError(f"layer kinds {set(self.layer_kinds)} not in "
                             f"{LAYER_KINDS}")

    @property
    def padded_vocab(self) -> int:
        p = self.vocab_pad_to
        return ((self.vocab_size + p - 1) // p) * p if p else self.vocab_size

    @property
    def is_encdec(self) -> bool:
        return self.n_enc_layers > 0

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def n_ssm_heads(self) -> int:
        return self.ssm_heads or (self.d_inner // self.ssm_head_dim)

    def param_count(self) -> int:
        """Analytic parameter count (embedding + blocks + head)."""
        d, f, v = self.d_model, self.d_ff, self.vocab_size
        hd, nh, nkv = self.head_dim, self.n_heads, self.n_kv_heads
        attn = d * hd * (nh + 2 * nkv) + nh * hd * d
        if self.activation.startswith("swiglu"):
            mlp = 3 * d * f
        else:
            mlp = 2 * d * f
        if self.family in ("moe",):
            fe = self.d_ff_expert or f
            mlp = self.n_experts * (3 * d * fe) + d * self.n_experts
        ssm = 0
        if self.family in ("ssm", "hybrid"):
            di, ns, nh_s = self.d_inner, self.ssm_state, self.n_ssm_heads
            conv_dim = di + 2 * self.ssm_groups * ns
            # in_proj, depthwise conv and its bias, A_log / D / dt_bias,
            # the gated norm, out_proj
            ssm = (d * (di + conv_dim + nh_s) + conv_dim * (self.conv_width + 1)
                   + 3 * nh_s + di + di * d)
            if self.family == "ssm":
                attn, mlp = 0, 0
        if self.layer_kinds:
            mixer = {"mamba": ssm, "attention": attn}
            blocks = sum(mixer[k] + mlp + 2 * d for k in self.layer_kinds)
        else:
            blocks = (self.n_layers + self.n_enc_layers) * (attn + mlp + ssm
                                                            + 2 * d)
        cross = 0
        if self.is_encdec:
            cross = self.n_layers * (d * hd * (nh + 2 * nkv) + nh * hd * d + d)
        emb = v * d * (1 if self.tie_embeddings else 2)
        front = 0
        if self.frontend_dim:
            front = self.frontend_dim * d + d  # projector
        return emb + blocks + cross + front

    def active_param_count(self) -> int:
        """Active params per token (MoE uses top_k of n_experts)."""
        if self.family != "moe":
            return self.param_count()
        d, fe = self.d_model, (self.d_ff_expert or self.d_ff)
        total = self.param_count()
        all_experts = self.n_layers * self.n_experts * 3 * d * fe
        active = self.n_layers * self.top_k * 3 * d * fe
        return total - all_experts + active

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class SwarmConfig:
    """P2P-SL: the paper's technique as a first-class feature."""

    n_nodes: int = 4
    sync_every: int = 10          # steps between peer exchanges (paper: 3 epochs)
    topology: str = "ring"        # ring | full | dynamic
    merge: str = "fedavg"         # mean | fedavg | fisher | gradmatch
    lora_only: bool = True        # paper: exchange LoRA-adapter weights only
    # what SwarmState.params covers (docs/heterogeneous.md):
    #   "full" — the stacked state is every node's full param pytree;
    #            lora_only then selects the adapter SUBTREE at sync time.
    #   "lora" — heterogeneous swarm: the stacked state IS the shared wire
    #            payload (LoRA adapters + decoder head, one flat path-keyed
    #            dict per node via `core.lora.flatten_payload`); each node's
    #            frozen, architecture-specific backbone stays local inside
    #            its train/eval closure and never crosses the wire. Needs a
    #            compiled backend; per-node closure lists ("model zoo") are
    #            engine-backend only.
    payload: str = "full"
    lora_rank: int = 16
    lora_alpha: float = 32.0
    val_threshold: float = 0.8    # paper: validation-based acceptance at 80%
    gate_metric: str = "auc"      # traced gate: auc | accuracy | f1 | sensitivity
    self_weight: float = 0.5      # gossip self-mixing weight (ring)
    fisher_decay: float = 0.95    # EMA decay of in-graph importance stats
    overlap_sync: bool = False    # stale-by-one double-buffered round overlap
    # wire compression (core.comms): payload dtype on the sync wire.
    #   "f32"  — uncompressed (default; bit-identical to the pre-comms paths)
    #   "bf16" — payloads cast to bf16 on the wire, f32 accumulation
    #   "int8" — error-feedback quantized deltas with per-block scales; the
    #            EF state rides in SwarmState.wire on BOTH compiled backends:
    #            the θ̂ reference on "engine", the sharded per-shard residual
    #            pytree of the picked *_q8 collective schedule on "gossip"
    wire_dtype: str = "f32"
    wire_block: int = 512         # elements per int8 scale block (mult. of 128)
    # two-level mesh cost model (core.comms): relative per-byte cost of the
    # two link classes on a ("pod", "node") mesh. Intra-pod (ICI) links are
    # cheap and plentiful; cross-pod (DCN) links are the scarce resource —
    # real deployments sit around a 10:1 ratio. pick_schedule argmins
    # Σ bytes(class)·cost(class), so raising cross_pod_cost above ~5.4× the
    # intra cost flips a 2×2 int8 ring swarm onto the hierarchical
    # pod-delegate schedules. On flat (1-D) meshes only the ratio's sign
    # matters (all candidates ride one class) and defaults are neutral.
    intra_pod_cost: float = 1.0
    cross_pod_cost: float = 1.0
    # graceful degradation (repro.faults, docs/faults.md): minimum number
    # of active nodes for a sync to commit. Below quorum the round still
    # trains locally but every gate is held closed — nodes keep their
    # locals and the merge is skipped (0 disables the policy). Evaluated
    # in-graph on the post-quarantine membership mask, so membership
    # changes never retrace.
    quorum: int = 0
    # per-site fairness gate (docs/heterogeneous.md): minimum gate metric
    # (cfg.gate_metric — worst-site sensitivity/AUC in the paper's reading)
    # that every ACTIVE site's merged candidate must clear for the round to
    # commit. Below the floor every gate is held closed — like `quorum`, the
    # whole swarm keeps its locals rather than committing a merge that
    # degrades the worst site. 0.0 disables; evaluated in-graph on the
    # traced per-site metrics, so metric/membership swings never retrace.
    fairness_floor: float = 0.0
    seed: int = 0


@dataclass(frozen=True)
class TrainConfig:
    batch_size: int = 32          # global
    seq_len: int = 128
    lr: float = 1e-4
    weight_decay: float = 1e-4    # paper: AdamW wd 1e-4
    schedule: str = "cosine"      # cosine | wsd | const
    warmup_steps: int = 100
    max_steps: int = 1000
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    grad_clip: float = 1.0
    early_stop_patience: int = 5  # paper: patience of five
    remat: bool = True
    accum_steps: int = 1          # microbatch gradient accumulation
    seed: int = 0


@dataclass(frozen=True)
class ShapeConfig:
    """One of the 4 assigned input shapes."""

    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


INPUT_SHAPES: Tuple[ShapeConfig, ...] = (
    ShapeConfig("train_4k", 4_096, 256, "train"),
    ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    ShapeConfig("decode_32k", 32_768, 128, "decode"),
    ShapeConfig("long_500k", 524_288, 1, "decode"),
)

SHAPES_BY_NAME = {s.name: s for s in INPUT_SHAPES}
