"""Shared tiling policy for the protection kernels.

Every kernel in this package tiles a (n, m) u32 buffer along the leading
axis.  A TPU block's row count must be a whole number of 8-row sublane
tiles or the full axis, so the tile is min(n, cap) for a cap that is a
multiple of 8, and the grid rounds up: a partial last block reads
padding past row n, and its write-back drops those rows.
"""
from __future__ import annotations


def row_tile(n: int, cap: int) -> tuple[int, int]:
    """(tile rows, grid steps) for an n-row buffer and an 8-aligned cap."""
    t = min(cap, n)
    return t, -(-n // t)
