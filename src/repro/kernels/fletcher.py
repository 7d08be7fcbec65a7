"""Pallas TPU kernel: per-block Fletcher-64 checksum terms.

This is the TPU analogue of Pangolin's ISA-L SIMD checksum loop (§3.5): a
memory-bound sweep that reads each word once and produces two 32-bit
accumulator lanes per 4 KB page block.  Tiling: commit_fused.TILE_BLOCKS
pages per grid step, each (TILE_BLOCKS, block_words) u32 tile in VMEM;
block_words = 1024 = 8 x 128 keeps the lane dimension MXU/VPU-aligned.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import commit_fused as _cf


def _fletcher_kernel(x_ref, out_ref):
    out_ref[...] = _cf._fletcher_terms(x_ref[...])   # (tb, 2)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fletcher_blocks(blocks: jax.Array, *, interpret: bool = False
                    ) -> jax.Array:
    """blocks: (n_blocks, block_words) u32 -> (n_blocks, 2) u32."""
    return _cf._flat_call(_fletcher_kernel, [blocks], [2], interpret)[0]


def _stream_fletcher_body(n):
    def body(ins, outs, start):
        outs[0][...], da, db = _cf._chunk_fletcher(ins[0][...], start, n)
        return da, db
    return body


@functools.partial(jax.jit, static_argnames=("chunk_blocks", "interpret"))
def fletcher_stream(blocks: jax.Array, *, chunk_blocks: int = 8,
                    interpret: bool = False):
    """Streamed sweep: (per-block terms, combined (A, B) row digest).

    Double-buffered HBM->VMEM chunks (see commit_fused's streamed family);
    the digest rides the loop carry, so the flat path's separate
    `checksum.combine` pass over the term table disappears.
    """
    return _cf._rows_call(_stream_fletcher_body(blocks.shape[0]), [blocks],
                          [2], chunk_blocks, interpret)
