"""Granite-4.0-H-Micro: 40 layers of which 36 are Mamba-2 mixers and 4 are
GQA attention, each followed by a SwiGLU MLP, with Granite's scalar
multipliers (https://huggingface.co/ibm-granite/granite-4.0-h-micro,
``config.json``, ``model_type`` ``granitemoehybrid``; HF's
``GraniteMoeHybrid*`` classes give the layer equations).

Where the repo departs from HF:
- the Mamba-2 scan is the chunked SSD of `models.ssm.ssd_chunked` (chunk
  256), in place of HF's fused kernels; both compute the same recurrence;
- ``time_step_limit`` is ``(0, inf)``, so the step sizes are not clamped
  and the limit is left out;
- the MLP's input projection ``input_linear`` (2048 -> 2 x 8192) is
  ``mlp/gate_up``, its first half gated by SiLU (``swiglu_fused``), and
  ``output_linear`` is ``mlp/down``;
- ``initializer_range`` is not used: weights are drawn by the repo's
  initializers (random weights serve speed and the reference alike).
"""
from repro.configs.base import ModelConfig

# the published layer pattern: attention at 5, 15, 25 and 35
LAYER_TYPES = tuple("attention" if i % 10 == 5 else "mamba"
                    for i in range(40))

CONFIG = ModelConfig(
    name="granite-4.0-h-micro", family="hybrid",
    source="https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/"
           "main/config.json",
    n_layers=40, layer_kinds=LAYER_TYPES, d_model=2048,
    n_heads=32, n_kv_heads=8, head_dim=64, use_rope=False,
    d_ff=8192, activation="swiglu_fused", vocab_size=100_352,
    tie_embeddings=True, norm_eps=1e-5,
    ssm_state=128, ssm_heads=64, ssm_head_dim=64, ssm_expand=2,
    ssm_chunk=256, conv_width=4, ssm_groups=1,
    embedding_multiplier=12.0, residual_multiplier=0.22, logits_scaling=8.0,
    attention_multiplier=1 / 64,
    # 4096 tokens a sequence: 512 query rows of scores at a time, and the
    # 100,352-word logits 512 tokens at a time
    attn_chunk=512, loss_chunk=512,
    param_dtype="bfloat16", compute_dtype="bfloat16",
)


def period_cut(cfg: ModelConfig = CONFIG) -> ModelConfig:
    """The first whole period of the pattern, ``m m m m m A m m m m``: the
    published 9 : 1 ratio at every width, with the tied embedding and
    head."""
    return cfg.replace(n_layers=10, layer_kinds=cfg.layer_kinds[:10])
