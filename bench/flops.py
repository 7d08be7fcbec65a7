"""Operations and bytes the work needs, counted from shapes.

These are the yardstick's own counts: what the algorithm requires, not
what the program happens to execute (recomputation does not count).
"""
from __future__ import annotations

import harness


def matmul_params(cfg: dict) -> int:
    """Weights that take part in a matmul per token (a gather, such as the
    embedding lookup, is none), from the configuration's layout."""
    return harness.layout_module(cfg).matmul_params(cfg)


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward operations per token at `seq_len`, from the
    configuration's layout; recomputation does not count."""
    return harness.layout_module(cfg).train_flops_per_token(cfg, seq_len)


def fletcher_bytes(row_words: int, block_words: int) -> int:
    """Least HBM traffic of one checksum sweep over a row of u32 words:
    the row read once, two u32 terms written per block, and the two-word
    digest."""
    blocks = -(-row_words // block_words)
    return 4 * row_words + 8 * blocks + 8
