"""Layout of a dense decoder (Qwen2, GLM-4): the program's parameter tree,
the `ModelConfig` fields a configuration file sets, the vocabulary rows
held, the operations a token needs, and the widths of the CPU shrink.

A configuration file names this module with `"layout": "decoder"`; the
harness reaches it through `harness.layout_module`, and nothing else in
the benchmark reads the keys below.
"""
from __future__ import annotations

import math

PROGRAM_KEYS = ("n_layers", "d_model", "n_heads", "n_kv", "head_dim", "d_ff",
                "vocab", "qkv_bias", "tie_embeddings", "rope_theta",
                "param_dtype", "compute_dtype")


def param_layout(cfg: dict) -> dict:
    """{path: (shape, std)} in the program's layout: one stacked leaf per
    kind over the layers.  std 0 means ones (norm scales); every other
    leaf is normal(0, std)."""
    L, d, H, K = cfg["n_layers"], cfg["d_model"], cfg["n_heads"], cfg["n_kv"]
    hd, ff, V = cfg["head_dim"], cfg["d_ff"], cfg["vocab"]
    g = "groups/b0_dense/"
    out = {
        "embed/tok": ((V, d), 0.02),
        "final_norm/scale": ((d,), 0.0),
        g + "ln1/scale": ((L, d), 0.0),
        g + "ln2/scale": ((L, d), 0.0),
        g + "attn/wq": ((L, d, H, hd), 1 / math.sqrt(d)),
        g + "attn/wk": ((L, d, K, hd), 1 / math.sqrt(d)),
        g + "attn/wv": ((L, d, K, hd), 1 / math.sqrt(d)),
        g + "attn/wo": ((L, H, hd, d), 1 / math.sqrt(H * hd)),
        g + "ffn/wi": ((L, d, ff), 1 / math.sqrt(d)),
        g + "ffn/wg": ((L, d, ff), 1 / math.sqrt(d)),
        g + "ffn/wo": ((L, ff, d), 1 / math.sqrt(ff)),
    }
    if not cfg["tie_embeddings"]:
        out["embed/unembed"] = ((d, V), 0.02)
    if cfg["qkv_bias"]:
        out[g + "attn/bq"] = ((L, H, hd), 0.02)
        out[g + "attn/bk"] = ((L, K, hd), 0.02)
        out[g + "attn/bv"] = ((L, K, hd), 0.02)
    return dict(sorted(out.items()))


def program_overrides(cfg: dict) -> dict:
    """The fields of the program's `ModelConfig` that the file sets."""
    return {k: cfg[k] for k in PROGRAM_KEYS}


def vocab(cfg: dict) -> int:
    """Rows of the vocabulary held here: the traffic's token ids are drawn
    from them, and the loss is over them."""
    return cfg["vocab"]


def matmul_params(cfg: dict) -> int:
    """Weights that take part in a matmul per token: every layer's
    projections and MLP, and the output head (the embedding lookup is a
    gather, not a matmul)."""
    d, H, K, hd, ff = (cfg["d_model"], cfg["n_heads"], cfg["n_kv"],
                       cfg["head_dim"], cfg["d_ff"])
    per_layer = d * H * hd + 2 * d * K * hd + H * hd * d + 3 * d * ff
    return cfg["n_layers"] * per_layer + d * cfg["vocab"]


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward operations per token.

    6 per matmul weight (2 forward, 4 backward), plus causal attention:
    a query at position i scores and mixes i + 1 keys, (S + 1) / 2 on
    average, at 2 * H * hd operations each for QK^T and for PV, times 3
    for the backward pass.
    """
    attn = 6 * cfg["n_layers"] * cfg["n_heads"] * cfg["head_dim"] \
        * (seq_len + 1)
    return 6.0 * matmul_params(cfg) + attn


def tiny(cfg: dict) -> dict:
    """The widths of the cut the CPU tests run in seconds."""
    return dict(n_layers=2, d_model=64, n_heads=4, n_kv=2, head_dim=16,
                d_ff=128, vocab=512)
