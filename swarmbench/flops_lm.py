"""Operations a LoRA swarm round of an interleaved Mamba-2 / attention LM
needs, from the shapes in its configuration file (HF's keys).

Model FLOPs count the matrix products (two operations per multiply-add):
every projection and its adapters, the MLP, the tied head, causal
attention's two products, and the SSD's products with one set of B and C
shared by each group's heads (what the algorithm needs, not what the
program materializes); norms, activations, the convolution and the
softmax are left out, so a share of a peak built from them errs low. A
training token's backward pass computes the input gradient of every
product and the weight gradient of the adapters only (the base is
frozen): twice the frozen products' forward, three times the adapters',
attention's and the SSD's. Rematerialized forwards are not counted.
"""
from __future__ import annotations


def _parts(config: dict, seq_len: int) -> dict:
    """Forward operations per token: ``frozen`` (products with frozen
    weights), ``lora`` (with adapters), ``act`` (activation by activation:
    attention and the SSD)."""
    d = config["hidden_size"]
    f = config["shared_intermediate_size"]
    nh, nkv = config["num_attention_heads"], config["num_key_value_heads"]
    hd = d // nh
    di = config["mamba_expand"] * d
    n, g = config["mamba_d_state"], config["mamba_n_groups"]
    heads, p = config["mamba_n_heads"], config["mamba_d_head"]
    chunk = min(config["mamba_chunk_size"], seq_len)
    r = config["lora"]["rank"]
    mlp = [(d, 2 * f), (f, d)]
    proj = {"mamba": [(d, 2 * di + 2 * g * n + heads), (di, d)] + mlp,
            "attention": [(d, nh * hd), (d, nkv * hd), (d, nkv * hd),
                          (nh * hd, d)] + mlp}
    # causal attention: q.k and p.v over (S + 1) / 2 keys on average; the
    # SSD: C.B over the (chunk + 1) / 2 earlier tokens of a chunk, the
    # decay-weighted sum of their x, the token's share of its chunk's state
    # and the state's read-out
    back = (chunk + 1) / 2
    act = {"attention": 2 * 2 * nh * hd * (seq_len + 1) / 2,
           "mamba": 2 * (back * g * n + back * heads * p
                         + 2 * heads * p * n)}
    out = {"frozen": 2 * d * config["vocab_size"], "lora": 0, "act": 0}
    for kind in config["layer_types"]:
        out["frozen"] += sum(2 * i * o for i, o in proj[kind])
        out["lora"] += sum(2 * r * (i + o) for i, o in proj[kind])
        out["act"] += act[kind]
    return out


def forward_token(config: dict, seq_len: int) -> float:
    """Model FLOPs of one token's forward pass."""
    return sum(_parts(config, seq_len).values())


def train_token(config: dict, seq_len: int) -> float:
    """Forward and backward of one training token."""
    p = _parts(config, seq_len)
    return 2 * p["frozen"] + 3 * (p["lora"] + p["act"])


def round_flops(config: dict, traffic: dict) -> float:
    """One round: every site's local steps, then the gate's two forward
    passes (local and merged adapters) over each site's validation text."""
    s, n = traffic["seq_len"], config["n_sites"]
    train = n * traffic["sync_every"] * traffic["batch"] * s
    val = n * traffic["val_seqs"] * s
    return train * train_token(config, s) + 2 * val * forward_token(config, s)
