"""Pallas TPU kernels: GF(2^32)-weighted syndrome sweeps (Reed-Solomon).

The syndrome stack is S_k = XOR_i g^(k·i)·row_i, k = 0..r-1, with
multiplication in GF(2^32) (core/gf.py), so a commit that already sweeps
(old, new) for the XOR delta can emit ALL r weighted deltas from the
same VMEM tiles: sdelta_k = g^(k·me) · (old ^ new), a 32-step branch-free
clmul per word per extra syndrome — pure VPU bit-ops, no extra HBM
reads.  The kernels here fuse that weighting with the existing
verify+checksum sweep (kernels/commit_fused.py):

  * `gf_scale`               — standalone element-wise y = coeff · x
    (epoch-flush syndrome patches for parity-only modes).
  * `fused_commit_s`         — one sweep over (old, new) emitting
    ((r, n, bw) weighted deltas, new Fletcher terms).
  * `fused_verify_commit_s`  — additionally folds verify-at-open over
    the old tile (terms XOR stored, all-zero == clean).
  * `fused_commit_old_terms_s` — the stored=0 specialization whose
    mismatch output is the raw old terms (MLP's incremental digest).

Syndrome 0's weight is g^0 = 1 by construction, so the k=0 plane is the
raw delta written without any clmul — r=1 costs exactly what the
single-parity fused sweep costs (and kernels/ops.py routes r=1 straight
to the commit_fused family, keeping the compiled program byte-identical
to the pre-stack engine).  HBM traffic per page is r-proportional only
in the unavoidable weighted-delta *writes* (r old + r new reads never
happen — one read each); the GF weighting itself is free, which is what
makes redundancy=r cost r-1 extra write streams rather than extra
passes.

The per-rank coefficient vector (g^(k·me))_k is a *traced* operand (one
axis_index table lookup), fed to the kernel as an (r, 1) u32 operand so
one compiled program serves every rank of the zone.  `kernels/ref.py`
carries the jnp oracles these must match bit-for-bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import commit_fused as _cf
from repro.kernels.tiling import row_tile

U32 = jnp.uint32
TILE_BLOCKS = 8
TILE_ROWS = 512          # gf_scale tile height (matches xor_parity.py)
# VMEM one `sdelta_stack` grid step may stage: its input tile and r output
# planes, each double-buffered.  Half of v5e's 16 MiB default scoped VMEM,
# leaving the compiler headroom.
STACK_VMEM_BYTES = 8 << 20
SLAB_ROWS = 8            # sdelta_stack rows weighted per inner step


def _gf_mul_tile(x, coeff):
    """Branch-free 32-step clmul of a tile by a scalar coefficient."""
    poly = U32(0x400007)                      # gf.POLY, inlined for Mosaic
    acc = jnp.zeros_like(x)
    cur = x
    for i in range(32):
        bit = (coeff >> U32(i)) & U32(1)
        acc = acc ^ (bit * cur)
        cur = (cur << U32(1)) ^ ((cur >> U32(31)) * poly)
    return acc


# ---------------------------------------------------------------------------
# standalone scale
# ---------------------------------------------------------------------------

def _gf_scale_kernel(coeff_ref, x_ref, o_ref):
    o_ref[...] = _gf_mul_tile(x_ref[...], coeff_ref[0, 0])


@functools.partial(jax.jit, static_argnames=("interpret",))
def gf_scale(x: jax.Array, coeff: jax.Array, *, interpret: bool = False
             ) -> jax.Array:
    """Element-wise y = coeff · x in GF(2^32); coeff a (traced) u32 scalar."""
    assert x.dtype == U32, x.dtype
    shape = x.shape
    if x.ndim == 1:
        x = x.reshape(-1, 1024) if x.size % 1024 == 0 else x.reshape(1, -1)
    n, m = x.shape
    t, steps = row_tile(n, TILE_ROWS)
    coeff = jnp.asarray(coeff, U32).reshape(1, 1)
    out = pl.pallas_call(
        _gf_scale_kernel,
        grid=(steps,),
        in_specs=[pl.BlockSpec((1, 1), lambda i: (0, 0)),
                  pl.BlockSpec((t, m), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((t, m), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, m), U32),
        interpret=interpret,
    )(coeff, x)
    return out.reshape(shape)


# ---------------------------------------------------------------------------
# fused r-syndrome commit sweeps
# ---------------------------------------------------------------------------

def _make_s_kernel(r: int, verify: bool):
    """Kernel body: delta + r-1 weighted planes [+ verify] + checksums.

    The delta tile is computed once in VMEM; plane 0 writes it raw
    (g^0 = 1 statically), planes 1..r-1 each run one clmul over the
    same registers — no tile is re-read.
    """
    def kernel(coeff_ref, old_ref, new_ref, *refs):
        if verify:
            stored_ref, sdelta_ref, ck_ref, mism_ref = refs
        else:
            sdelta_ref, ck_ref = refs
        old = old_ref[...]
        new = new_ref[...]
        d = old ^ new
        sdelta_ref[0] = d
        for k in range(1, r):
            sdelta_ref[k] = _gf_mul_tile(d, coeff_ref[k, 0])
        if verify:
            mism_ref[...] = _cf._fletcher_terms(old) ^ stored_ref[...]
        ck_ref[...] = _cf._fletcher_terms(new)
    return kernel


def _s_call(old, new, stored, coeffs, r, interpret):
    assert old.shape == new.shape and old.dtype == U32 == new.dtype
    n, bw = old.shape
    tb, steps = row_tile(n, TILE_BLOCKS)
    coeffs = jnp.asarray(coeffs, U32).reshape(r, 1)
    verify = stored is not None
    in_specs = [pl.BlockSpec((r, 1), lambda i: (0, 0)),
                pl.BlockSpec((tb, bw), lambda i: (i, 0)),
                pl.BlockSpec((tb, bw), lambda i: (i, 0))]
    operands = [coeffs, old, new]
    out_specs = [pl.BlockSpec((r, tb, bw), lambda i: (0, i, 0)),
                 pl.BlockSpec((tb, 2), lambda i: (i, 0))]
    out_shape = [jax.ShapeDtypeStruct((r, n, bw), U32),
                 jax.ShapeDtypeStruct((n, 2), U32)]
    if verify:
        assert stored.shape == (n, 2) and stored.dtype == U32, stored.shape
        in_specs.append(pl.BlockSpec((tb, 2), lambda i: (i, 0)))
        operands.append(stored)
        out_specs.append(pl.BlockSpec((tb, 2), lambda i: (i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((n, 2), U32))
    return pl.pallas_call(
        _make_s_kernel(r, verify),
        grid=(steps,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*operands)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_commit_s(old: jax.Array, new: jax.Array, coeffs: jax.Array, *,
                   interpret: bool = False):
    """One sweep over (old, new): ((r, n, bw) sdeltas, new Fletcher terms)."""
    r = coeffs.shape[0]
    sdelta, ck = _s_call(old, new, None, coeffs, r, interpret)
    return sdelta, ck


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_verify_commit_s(old: jax.Array, new: jax.Array, stored: jax.Array,
                          coeffs: jax.Array, *, interpret: bool = False):
    """Verify + r sdeltas + new checksums from one sweep.

    Returns (sdelta, new_cksums, bad) with bad True where the old
    block's recomputed Fletcher terms no longer match `stored`.
    """
    r = coeffs.shape[0]
    sdelta, ck, mism = _s_call(old, new, stored, coeffs, r, interpret)
    return sdelta, ck, jnp.any(mism != 0, axis=-1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_commit_old_terms_s(old: jax.Array, new: jax.Array,
                             coeffs: jax.Array, *, interpret: bool = False):
    """(sdelta, new cksums, old cksums) — the MLP-ladder patch sweep."""
    r = coeffs.shape[0]
    zeros = jnp.zeros((old.shape[0], 2), U32)
    return _s_call(old, new, zeros, coeffs, r, interpret)


# ---------------------------------------------------------------------------
# stacked-plane standalone scale
# ---------------------------------------------------------------------------

def _make_sdelta_stack_kernel(r: int):
    def kernel(coeff_ref, x_ref, o_ref):
        # Weight the tile 8 rows at a time: the clmul's temporaries then
        # stay slab-sized, so the tile is bounded by its buffers alone.
        def weigh(rows):
            x = x_ref[rows]
            o_ref[0, rows] = x
            for k in range(1, r):
                o_ref[k, rows] = _gf_mul_tile(x, coeff_ref[k, 0])

        t = x_ref.shape[0]
        if t % SLAB_ROWS:            # a whole short row: one slab
            weigh(slice(None))
            return

        def slab(j, carry):
            weigh(pl.ds(pl.multiple_of(j * SLAB_ROWS, SLAB_ROWS), SLAB_ROWS))
            return carry

        jax.lax.fori_loop(0, t // SLAB_ROWS, slab, 0)
    return kernel


def _stack_tile_rows(m: int, r: int) -> int:
    """Rows per `sdelta_stack` step that fit STACK_VMEM_BYTES (8-aligned).

    Per row: the input and r output planes, double-buffered.
    """
    row_bytes = 4 * m * 2 * (1 + r)
    return max(8, STACK_VMEM_BYTES // row_bytes // 8 * 8)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sdelta_stack(x: jax.Array, coeffs: jax.Array, *,
                 interpret: bool = False) -> jax.Array:
    """(r, *x.shape) weighted stack from ONE read of x.

    The per-plane `gf_scale` loop reads the delta r-1 times (and the
    stack concat copies it again); here each VMEM tile is weighted into
    all r output planes while resident, so HBM traffic is 1 read +
    r writes regardless of redundancy.  Plane 0 is the raw delta
    (coeffs[0] = g^0 = 1, statically skipped).
    """
    assert x.dtype == U32, x.dtype
    shape = x.shape
    if x.ndim == 1:
        x = x.reshape(-1, 1024) if x.size % 1024 == 0 else x.reshape(1, -1)
    n, m = x.shape
    r = coeffs.shape[0]
    t, steps = row_tile(n, _stack_tile_rows(m, r))
    coeffs = jnp.asarray(coeffs, U32).reshape(r, 1)
    out = pl.pallas_call(
        _make_sdelta_stack_kernel(r),
        grid=(steps,),
        in_specs=[pl.BlockSpec((r, 1), lambda i: (0, 0)),
                  pl.BlockSpec((t, m), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((r, t, m), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, n, m), U32),
        interpret=interpret,
    )(coeffs, x)
    return out.reshape((r,) + shape)


# ---------------------------------------------------------------------------
# blockwise double-buffered streaming variants
# ---------------------------------------------------------------------------
# Same pipeline as commit_fused's streamed family (see the discussion
# there): a sequential chunk grid double-buffers operands and results
# through VMEM, and the whole-row Fletcher digest rides an SMEM carry.  Each
# resident delta chunk is weighted into all r syndrome planes before its
# buffers are recycled — one read of (old, new) regardless of redundancy.

def _stream_s_body(n, r, verify):
    def body(ins, outs, start):
        coeff_smem, old, new = ins[0], ins[1][...], ins[2][...]
        sdelta_ref, ck_ref = outs[0], outs[1]
        d = old ^ new
        sdelta_ref[0] = d
        for k in range(1, r):
            sdelta_ref[k] = _gf_mul_tile(d, coeff_smem[k])
        if verify:
            outs[2][...] = _cf._fletcher_terms(old) ^ ins[3][...]
        ck_ref[...], da, db = _cf._chunk_fletcher(new, start, n)
        return da, db
    return body


def _s_stream_call(old, new, stored, coeffs, r, chunk_blocks, interpret):
    assert old.shape == new.shape and old.dtype == U32 == new.dtype
    n, bw = old.shape
    cb = _cf._clamp_cb(chunk_blocks, n)
    coeffs = jnp.asarray(coeffs, U32).reshape(r)
    verify = stored is not None
    rows = functools.partial(_cf._rows, cb)
    in_specs = [_cf._SMEM(), rows(bw), rows(bw)]
    operands = [coeffs, old, new]
    out_specs = [pl.BlockSpec((r, cb, bw), lambda i: (0, i, 0)), rows(2)]
    out_shape = [jax.ShapeDtypeStruct((r, n, bw), U32),
                 jax.ShapeDtypeStruct((n, 2), U32)]
    if verify:
        assert stored.shape == (n, 2) and stored.dtype == U32, stored.shape
        in_specs.append(rows(2))
        operands.append(stored)
        out_specs.append(rows(2))
        out_shape.append(jax.ShapeDtypeStruct((n, 2), U32))
    return _cf._stream_call(_stream_s_body(n, r, verify), n, cb, operands,
                           in_specs, out_specs, out_shape, interpret)


@functools.partial(jax.jit, static_argnames=("chunk_blocks", "interpret"))
def fused_commit_s_stream(old: jax.Array, new: jax.Array,
                          coeffs: jax.Array, *,
                          chunk_blocks: int = _cf.STREAM_CHUNK_BLOCKS,
                          interpret: bool = False):
    """Streamed fused_commit_s: (sdeltas, new cksums, row digest)."""
    r = coeffs.shape[0]
    return _s_stream_call(old, new, None, coeffs, r, chunk_blocks, interpret)


@functools.partial(jax.jit, static_argnames=("chunk_blocks", "interpret"))
def fused_verify_commit_s_stream(old: jax.Array, new: jax.Array,
                                 stored: jax.Array, coeffs: jax.Array, *,
                                 chunk_blocks: int = _cf.STREAM_CHUNK_BLOCKS,
                                 interpret: bool = False):
    """Streamed fused_verify_commit_s: (sdeltas, cksums, bad, digest)."""
    r = coeffs.shape[0]
    sdelta, ck, mism, dig = _s_stream_call(old, new, stored, coeffs, r,
                                           chunk_blocks, interpret)
    return sdelta, ck, jnp.any(mism != 0, axis=-1), dig
