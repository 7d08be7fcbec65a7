"""Pallas TPU kernels: XOR parity delta and accumulate.

The TPU analogue of Pangolin's ISA-L XOR loops: pure element-wise u32
bit-ops, VPU-bound, tiled through VMEM.  `xor_delta` computes the parity
patch Delta = old ^ new; `xor_accum` applies a patch to a parity buffer
(the "atomic XOR" application — order-free by commutativity, so the
collective that delivers patches needs no ordering either).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import row_tile

U32 = jnp.uint32
# (rows, lanes) tile: 512 x 1024 x 4 B = 2 MB per operand; 3 operands = 6 MB
# of VMEM traffic per step, comfortably under the ~16 MB v5e VMEM budget.
TILE_ROWS = 512


def _xor2_kernel(a_ref, b_ref, o_ref):
    o_ref[...] = a_ref[...] ^ b_ref[...]


def _xor2(a: jax.Array, b: jax.Array, interpret: bool) -> jax.Array:
    assert a.shape == b.shape and a.dtype == U32 == b.dtype
    shape = a.shape
    if a.ndim == 1:
        a = a.reshape(-1, 1024) if a.size % 1024 == 0 else a.reshape(1, -1)
        b = b.reshape(a.shape)
    n, m = a.shape
    t, steps = row_tile(n, TILE_ROWS)
    out = pl.pallas_call(
        _xor2_kernel,
        grid=(steps,),
        in_specs=[pl.BlockSpec((t, m), lambda i: (i, 0)),
                  pl.BlockSpec((t, m), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((t, m), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, m), U32),
        interpret=interpret,
    )(a, b)
    return out.reshape(shape)


@functools.partial(jax.jit, static_argnames=("interpret",))
def xor_delta(old: jax.Array, new: jax.Array, *, interpret: bool = False
              ) -> jax.Array:
    return _xor2(old, new, interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def xor_accum(parity: jax.Array, patch: jax.Array, *, interpret: bool = False
              ) -> jax.Array:
    return _xor2(parity, patch, interpret)
