"""Pallas TPU kernels: fused commit sweeps (beyond-paper optimization).

Pangolin's commit makes separate passes over the modified ranges: verify
the old data's checksums at micro-buffer open, compute the checksum of the
new data, compute the parity patch old ^ new (§3.4-3.5).  All of them are
memory-bound, so on TPU the win is to touch HBM once per operand:

  * `fused_commit`         streams (old, new) tiles through VMEM once and
    emits the parity delta plus the new per-page Fletcher terms.
  * `fused_verify_commit`  additionally folds the verify-at-open into the
    same sweep: the old tile — already in VMEM for the delta — also
    produces its Fletcher terms, compared against the stored checksums.

HBM traffic per page (r = read, w = write, bad = 2-word compare):

  unfused verify+commit = r old (verify) + r old + r new (delta)
                          + r new (checksum) + w delta         = 4 reads
  fused                 = r old + r new + w delta              = 2 reads

=> half the read traffic on the commit hot path; with the Protector's row
cache eliminating the old-state re-flatten as well, the whole MLPC commit
is one sweep over each operand (see EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import row_tile

U32 = jnp.uint32
I32 = jnp.int32
TILE_BLOCKS = 8
# Streamed variants: pages per double-buffered VMEM chunk.  Each operand
# stages 2 x chunk x block_words words, so the default 8-page chunk costs
# 64 KB of VMEM per u32 operand at bw=1024 — small enough that the 3-operand
# accumulate sweep still fits comfortably alongside the output tiles.  A
# chunk is a whole number of 8-row sublane tiles (or the whole row).
STREAM_CHUNK_BLOCKS = 8


def _i32(x):
    return jax.lax.bitcast_convert_type(x, I32)


def _u32(x):
    return jax.lax.bitcast_convert_type(x, U32)


def _fletcher_ab(x):
    """Per-block Fletcher (A, B) terms of a (tb, bw) u32 tile, as (tb, 1) i32.

    Mosaic has no unsigned reductions, so the sums run in int32 on the
    bitcast words: two's-complement add and multiply give the same bits
    mod 2^32 as the u32 arithmetic `kernels/ref.py` specifies.
    """
    bw = x.shape[-1]
    w = I32(bw) - jax.lax.broadcasted_iota(I32, (1, bw), 1)
    xi = _i32(x)
    return (jnp.sum(xi, axis=-1, keepdims=True),
            jnp.sum(xi * w, axis=-1, keepdims=True))


def _fletcher_terms(x):
    """(tb, bw) u32 tile -> (tb, 2) u32 per-block Fletcher terms."""
    return _u32(jnp.concatenate(_fletcher_ab(x), axis=-1))


def _rows(tb, width):
    """Block of `tb` rows of an (n, width) operand, one per grid step."""
    return pl.BlockSpec((tb, width), lambda i: (i, 0))


def _fused_kernel(old_ref, new_ref, delta_ref, ck_ref):
    old = old_ref[...]
    new = new_ref[...]
    delta_ref[...] = old ^ new
    ck_ref[...] = _fletcher_terms(new)


def _fused_verify_kernel(old_ref, new_ref, stored_ref, delta_ref, ck_ref,
                         mism_ref):
    old = old_ref[...]
    new = new_ref[...]
    delta_ref[...] = old ^ new
    # old tile is in VMEM for the delta anyway: its Fletcher terms are free;
    # XOR difference vs stored terms: all-zero == block verifies clean
    mism_ref[...] = _fletcher_terms(old) ^ stored_ref[...]
    ck_ref[...] = _fletcher_terms(new)


def _flat_call(kernel, operands, widths, interpret):
    """Tile (n, width) u32 operands into TILE_BLOCKS-row grid steps.

    `widths` lists the outputs' widths (block_words or 2 terms); every
    output is (n, width) u32.
    """
    n = operands[0].shape[0]
    tb, steps = row_tile(n, TILE_BLOCKS)
    return pl.pallas_call(
        kernel,
        grid=(steps,),
        in_specs=[_rows(tb, x.shape[1]) for x in operands],
        out_specs=[_rows(tb, w) for w in widths],
        out_shape=[jax.ShapeDtypeStruct((n, w), U32) for w in widths],
        interpret=interpret,
    )(*operands)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_commit(old: jax.Array, new: jax.Array, *, interpret: bool = False):
    """old/new: (n_blocks, block_words) u32 -> (delta, cksums)."""
    assert old.shape == new.shape and old.dtype == U32 == new.dtype
    return _flat_call(_fused_kernel, [old, new], [old.shape[1], 2],
                      interpret)


def _verify_call(old: jax.Array, new: jax.Array, stored: jax.Array,
                 interpret: bool):
    """Shared sweep: (delta, new terms, old terms XOR stored)."""
    assert old.shape == new.shape and old.dtype == U32 == new.dtype
    n, bw = old.shape
    assert stored.shape == (n, 2) and stored.dtype == U32, stored.shape
    return _flat_call(_fused_verify_kernel, [old, new, stored], [bw, 2, 2],
                      interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_verify_commit(old: jax.Array, new: jax.Array, stored: jax.Array,
                        *, interpret: bool = False):
    """Single sweep: verify old vs `stored` + delta + new checksums.

    old/new: (n_blocks, block_words) u32; stored: (n_blocks, 2) u32 Fletcher
    terms the old blocks must still match.  Returns (delta, new_cksums,
    bad) with bad: (n_blocks,) bool, True where the old block fails
    verification (the paper's verify-at-micro-buffer-open).
    """
    delta, ck, mism = _verify_call(old, new, stored, interpret)
    return delta, ck, jnp.any(mism != 0, axis=-1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_commit_old_terms(old: jax.Array, new: jax.Array, *,
                           interpret: bool = False):
    """Single sweep: (delta, new checksums, old checksums).

    The verify kernel's mismatch output is `old_terms XOR stored`; with
    stored = 0 it is the raw old terms — so the parity-only (MLP) patch
    path gets the old-page Fletcher terms its incremental digest needs
    from the same pass that produced the delta, not a second sweep.
    """
    zeros = jnp.zeros((old.shape[0], 2), U32)
    return _verify_call(old, new, zeros, interpret)


def _accum_kernel(acc_ref, old_ref, new_ref, acc_out_ref, old_ck_ref,
                  new_ck_ref):
    acc = acc_ref[...]
    old = old_ref[...]
    new = new_ref[...]
    # XOR deltas telescope: acc ^ (old ^ new) after W steps equals
    # row_epoch_start ^ row_now, the exact delta the epoch flush applies.
    acc_out_ref[...] = acc ^ old ^ new
    # both tiles are in VMEM for the accumulate: their Fletcher terms are
    # free, and they are exactly what the incremental row digest needs
    old_ck_ref[...] = _fletcher_terms(old)
    new_ck_ref[...] = _fletcher_terms(new)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_accum_commit(acc: jax.Array, old: jax.Array, new: jax.Array, *,
                       interpret: bool = False):
    """Delta-accumulate variant for the deferred-epoch engine.

    One sweep over (acc, old, new), each (n_blocks, block_words) u32,
    emits the running epoch delta `acc ^ old ^ new` plus the old and new
    per-block Fletcher terms.  In-window commits use it to fold the
    step's XOR delta into the epoch accumulator and keep the row digest
    current (from the term deltas) without touching parity or the
    checksum table — those consume the accumulator once per epoch, so
    the flush is still one sweep per operand.
    """
    assert acc.shape == old.shape == new.shape, (acc.shape, old.shape,
                                                 new.shape)
    assert acc.dtype == old.dtype == new.dtype == U32
    return _flat_call(_accum_kernel, [acc, old, new], [old.shape[1], 2, 2],
                      interpret)


# ---------------------------------------------------------------------------
# blockwise double-buffered streaming variants
# ---------------------------------------------------------------------------
# The `*_stream` family walks the row in chunk_blocks-page chunks on a
# sequential grid: Mosaic double-buffers every operand and result through
# VMEM — chunk i+1's copy-in and chunk i-1's copy-out overlap chunk i's
# compute — and the Fletcher digest of the whole row rides along as an
# SMEM (A, B) accumulator, so one sweep emits the delta, the per-block
# terms AND the combined row digest (the flat path needs a separate
# `checksum.combine` pass over the terms).  The chunk copies are the
# pipeline's own and not hand-written DMAs: a TPU DMA may not slice the
# narrow (n, 2) term tables off their (8, 128) tiling, while the grid's
# blocks carry them.  The ragged tail (n % chunk) is the grid's partial
# last block: its padding rows are masked out of the digest by global
# position and dropped on write-back.


def _chunk_fletcher(x, start, n):
    """Per-block Fletcher terms of a chunk + its global digest contribution.

    The combine rule (core/checksum.py) weights block p's A term by the
    words after it, (n - 1 - p) * bw; positions are global, so a running
    (sum dA, sum dB) carry over chunks lands bit-identical to
    `checksum.combine` over the full term table.  The digest terms are
    int32 (see `_fletcher_ab`); rows at or past n (a partial last chunk's
    padding) contribute nothing.
    """
    a, b = _fletcher_ab(x)
    rows, bw = x.shape
    pos = start + jax.lax.broadcasted_iota(I32, (rows, 1), 0)
    db = b + (I32(n - 1) - pos) * I32(bw) * a
    if n % rows:
        live = pos < n
        a = jnp.where(live, a, 0)
        db = jnp.where(live, db, 0)
    return _u32(jnp.concatenate([a, b], axis=-1)), jnp.sum(a), jnp.sum(db)


def _clamp_cb(chunk_blocks: int, n: int) -> int:
    """Chunk height: whole 8-row tiles (at least one), or the whole row
    when it is shorter."""
    return row_tile(n, max(8, int(chunk_blocks) // 8 * 8))[0]


_SMEM = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)


def _stream_call(body, n, cb, operands, in_specs, out_specs, out_shape,
                interpret):
    """Sequential cb-row chunk grid whose results end with the row digest.

    `body(in_refs, out_refs, start)` fills one chunk's output blocks from
    its input blocks and returns the chunk's (dA, dB) int32 digest terms
    (`_chunk_fletcher`); the final (2,) u32 output is their sum.
    """
    steps = pl.cdiv(n, cb)
    n_in = len(operands)

    def kernel(*refs):
        ins, outs = refs[:n_in], refs[n_in:-2]
        dig_ref, acc_ref = refs[-2:]
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            acc_ref[0] = I32(0)
            acc_ref[1] = I32(0)

        da, db = body(ins, outs, i * cb)
        acc_ref[0] += da
        acc_ref[1] += db

        @pl.when(i == steps - 1)
        def _emit():
            dig_ref[0] = acc_ref[0].astype(U32)
            dig_ref[1] = acc_ref[1].astype(U32)

    return pl.pallas_call(
        kernel,
        grid=(steps,),
        in_specs=in_specs,
        out_specs=list(out_specs) + [_SMEM()],
        out_shape=list(out_shape) + [jax.ShapeDtypeStruct((2,), U32)],
        scratch_shapes=[pltpu.SMEM((2,), I32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*operands)


def _rows_call(body, operands, widths, chunk_blocks, interpret):
    """`_stream_call` over (n, width) u32 operands and outputs."""
    n = operands[0].shape[0]
    cb = _clamp_cb(chunk_blocks, n)
    return _stream_call(body, n, cb, operands,
                       [_rows(cb, x.shape[1]) for x in operands],
                       [_rows(cb, w) for w in widths],
                       [jax.ShapeDtypeStruct((n, w), U32) for w in widths],
                       interpret)


def _commit_body(n):
    def body(ins, outs, start):
        old, new = ins[0][...], ins[1][...]
        delta_ref, ck_ref = outs
        delta_ref[...] = old ^ new
        ck_ref[...], da, db = _chunk_fletcher(new, start, n)
        return da, db
    return body


def _verify_body(n):
    def body(ins, outs, start):
        old, new = ins[0][...], ins[1][...]
        delta_ref, ck_ref, mism_ref = outs
        delta_ref[...] = old ^ new
        mism_ref[...] = _fletcher_terms(old) ^ ins[2][...]
        ck_ref[...], da, db = _chunk_fletcher(new, start, n)
        return da, db
    return body


def _accum_body(n):
    def body(ins, outs, start):
        acc, old, new = (r[...] for r in ins)
        acc_out_ref, old_ck_ref, new_ck_ref = outs
        acc_out_ref[...] = acc ^ old ^ new
        old_ck_ref[...] = _fletcher_terms(old)
        new_ck_ref[...], da, db = _chunk_fletcher(new, start, n)
        return da, db
    return body


@functools.partial(jax.jit, static_argnames=("chunk_blocks", "interpret"))
def fused_commit_stream(old: jax.Array, new: jax.Array, *,
                        chunk_blocks: int = STREAM_CHUNK_BLOCKS,
                        interpret: bool = False):
    """Streamed fused_commit: (delta, cksums, (A, B) row digest)."""
    assert old.shape == new.shape and old.dtype == U32 == new.dtype
    n, bw = old.shape
    return _rows_call(_commit_body(n), [old, new], [bw, 2], chunk_blocks,
                      interpret)


def _verify_stream_call(old, new, stored, chunk_blocks, interpret):
    assert old.shape == new.shape and old.dtype == U32 == new.dtype
    n, bw = old.shape
    assert stored.shape == (n, 2) and stored.dtype == U32, stored.shape
    return _rows_call(_verify_body(n), [old, new, stored], [bw, 2, 2],
                      chunk_blocks, interpret)


@functools.partial(jax.jit, static_argnames=("chunk_blocks", "interpret"))
def fused_verify_commit_stream(old: jax.Array, new: jax.Array,
                               stored: jax.Array, *,
                               chunk_blocks: int = STREAM_CHUNK_BLOCKS,
                               interpret: bool = False):
    """Streamed fused_verify_commit: (delta, cksums, bad, digest)."""
    delta, ck, mism, dig = _verify_stream_call(old, new, stored,
                                               chunk_blocks, interpret)
    return delta, ck, jnp.any(mism != 0, axis=-1), dig


@functools.partial(jax.jit, static_argnames=("chunk_blocks", "interpret"))
def fused_commit_old_terms_stream(old: jax.Array, new: jax.Array, *,
                                  chunk_blocks: int = STREAM_CHUNK_BLOCKS,
                                  interpret: bool = False):
    """Streamed fused_commit_old_terms: (delta, new ck, old ck, digest)."""
    zeros = jnp.zeros((old.shape[0], 2), U32)
    return _verify_stream_call(old, new, zeros, chunk_blocks, interpret)


@functools.partial(jax.jit, static_argnames=("chunk_blocks", "interpret"))
def fused_accum_commit_stream(acc: jax.Array, old: jax.Array,
                              new: jax.Array, *,
                              chunk_blocks: int = STREAM_CHUNK_BLOCKS,
                              interpret: bool = False):
    """Streamed fused_accum_commit: (acc', old ck, new ck, new digest)."""
    assert acc.shape == old.shape == new.shape, (acc.shape, old.shape,
                                                 new.shape)
    assert acc.dtype == old.dtype == new.dtype == U32
    n, bw = old.shape
    return _rows_call(_accum_body(n), [acc, old, new], [bw, 2, 2],
                      chunk_blocks, interpret)
