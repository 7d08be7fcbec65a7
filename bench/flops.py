"""Operations and bytes the work needs, counted from shapes.

These are the yardstick's own counts: what the algorithm requires, not
what the program happens to execute (recomputation does not count).
"""
from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    """Weights that take part in a matmul per token: every layer's
    projections and MLP, and the output head (the embedding lookup is a
    gather, not a matmul)."""
    d, H, K, hd, ff = (cfg["d_model"], cfg["n_heads"], cfg["n_kv"],
                       cfg["head_dim"], cfg["d_ff"])
    per_layer = d * H * hd + 2 * d * K * hd + H * hd * d + 3 * d * ff
    return cfg["n_layers"] * per_layer + d * cfg["vocab"]


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward operations per token of a dense decoder.

    6 per matmul weight (2 forward, 4 backward), plus causal attention:
    a query at position i scores and mixes i + 1 keys, (S + 1) / 2 on
    average, at 2 * H * hd operations each for QK^T and for PV, times 3
    for the backward pass.
    """
    attn = 6 * cfg["n_layers"] * cfg["n_heads"] * cfg["head_dim"] \
        * (seq_len + 1)
    return 6.0 * matmul_params(cfg) + attn


def fletcher_bytes(row_words: int, block_words: int) -> int:
    """Least HBM traffic of one checksum sweep over a row of u32 words:
    the row read once, two u32 terms written per block, and the two-word
    digest."""
    blocks = -(-row_words // block_words)
    return 4 * row_words + 8 * blocks + 8
