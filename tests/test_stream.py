"""Blockwise double-buffered streaming commit pipeline (ISSUE 6).

Three layers, all exact-equality (uint32 integer math):

  * kernels — the streamed Pallas kernels (interpret mode) must be
    bit-identical to the flat kernels AND the jnp oracles for every
    chunk geometry: single-block rows, ragged tails (n % chunk != 0),
    and rows many chunks long ("larger than VMEM"), across the whole
    syndrome-stack range r in {1..4}; the loop-carried row digest must
    equal `checksum.combine` of the emitted per-block terms.
  * collectives — the chunked syndrome reduce-scatter / delta fold must
    be bit-identical to the unchunked collective (chunking slices the
    segment axis, so the concatenated pieces are positionally identical
    and the GF weighting commutes element-wise).
  * engines — a Protector forced onto the streamed path
    (stream_threshold_words=1) must commit bit-identically to the flat
    protector, and the deferred bulk engine (the fused_accum_commit
    accumulator) must land bit-identical to the synchronous engine at
    every window boundary with streaming enabled, W in {1, 16}.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import checksum as cksum
from repro.core import gf
from repro.core.epoch import DeferredProtector
from repro.core.txn import Mode, Protector
from repro.dist import collectives as coll
from repro.kernels import commit_fused, fletcher, gf_parity, ops, ref
from tests.conftest import small_state

U32 = jnp.uint32


def rand_u32(shape, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, 2**32, size=shape, dtype=np.uint32))


def assert_trees_equal(got, want):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def coeffs_for(r, me=3):
    return jnp.asarray([gf.pow_g_int(k * me) for k in range(r)], U32)


# (n_blocks, block_words, chunk_blocks): single-block, ragged tails,
# exact multiples, and a many-chunk row standing in for >> VMEM
GEOMS = [(1, 128, 4), (3, 128, 1), (5, 256, 2), (8, 128, 4),
         (17, 128, 4), (33, 128, 8), (64, 512, 4)]


@pytest.mark.parametrize("n,bw,cb", GEOMS)
def test_stream_single_parity_kernels_vs_flat_and_ref(n, bw, cb):
    old, new = rand_u32((n, bw), seed=n), rand_u32((n, bw), seed=n + 1)
    stored = ref.fletcher_blocks_ref(old)

    d_s, c_s, dig = commit_fused.fused_commit_stream(
        old, new, chunk_blocks=cb, interpret=True)
    d_f, c_f = commit_fused.fused_commit(old, new, interpret=True)
    assert_trees_equal((d_s, c_s), (d_f, c_f))
    assert_trees_equal((d_s, c_s, dig), ref.fused_commit_stream_ref(old, new))
    # the loop-carried digest == combine of the emitted per-block terms
    np.testing.assert_array_equal(np.asarray(dig),
                                  np.asarray(cksum.combine(c_s, bw)))

    out_s = commit_fused.fused_verify_commit_stream(
        old, new, stored, chunk_blocks=cb, interpret=True)
    assert_trees_equal(out_s[:3], commit_fused.fused_verify_commit(
        old, new, stored, interpret=True))
    assert_trees_equal(out_s, ref.fused_verify_commit_stream_ref(
        old, new, stored))

    out_s = commit_fused.fused_commit_old_terms_stream(
        old, new, chunk_blocks=cb, interpret=True)
    assert_trees_equal(out_s[:3], commit_fused.fused_commit_old_terms(
        old, new, interpret=True))
    assert_trees_equal(out_s, ref.fused_commit_old_terms_stream_ref(old, new))

    ck_s, dig = fletcher.fletcher_stream(new, chunk_blocks=cb,
                                         interpret=True)
    assert_trees_equal((ck_s, dig), ref.fletcher_stream_ref(new))


@pytest.mark.parametrize("n,bw,cb", GEOMS)
def test_stream_accum_kernel_vs_flat_and_ref(n, bw, cb):
    acc = rand_u32((n, bw), seed=n + 2)
    old, new = rand_u32((n, bw), seed=n + 3), rand_u32((n, bw), seed=n + 4)
    out_s = commit_fused.fused_accum_commit_stream(
        acc, old, new, chunk_blocks=cb, interpret=True)
    assert_trees_equal(out_s[:3], commit_fused.fused_accum_commit(
        acc, old, new, interpret=True))
    assert_trees_equal(out_s, ref.fused_accum_commit_stream_ref(
        acc, old, new))
    np.testing.assert_array_equal(
        np.asarray(out_s[3]), np.asarray(cksum.combine(out_s[2], bw)))


@pytest.mark.parametrize("n,bw,cb", [(1, 128, 4), (5, 256, 2), (17, 128, 4),
                                     (33, 128, 8)])
@pytest.mark.parametrize("r", [2, 3, 4])
def test_stream_syndrome_kernels_vs_flat_and_ref(n, bw, cb, r):
    """One streamed pass must emit ALL r weighted planes bit-identically
    to the flat stacked kernel — the row is read once per commit
    regardless of the redundancy."""
    old, new = rand_u32((n, bw), seed=7 * n), rand_u32((n, bw), seed=7 * n + 1)
    stored = ref.fletcher_blocks_ref(old)
    co = coeffs_for(r)

    out_s = gf_parity.fused_commit_s_stream(old, new, co, chunk_blocks=cb,
                                            interpret=True)
    assert_trees_equal(out_s[:2], gf_parity.fused_commit_s(
        old, new, co, interpret=True))
    assert_trees_equal(out_s, ref.fused_commit_s_stream_ref(old, new, co))

    out_s = gf_parity.fused_verify_commit_s_stream(
        old, new, stored, co, chunk_blocks=cb, interpret=True)
    assert_trees_equal(out_s[:3], gf_parity.fused_verify_commit_s(
        old, new, stored, co, interpret=True))
    assert_trees_equal(out_s, ref.fused_verify_commit_s_stream_ref(
        old, new, stored, co))
    np.testing.assert_array_equal(
        np.asarray(out_s[3]), np.asarray(cksum.combine(out_s[1], bw)))


@pytest.mark.parametrize("r", [1, 2, 3, 4])
def test_ops_stream_dispatch_r_sweep(r):
    """ops-level dispatch: coeffs=None (r=1) routes to the single-parity
    stream and reshapes the delta plane; interpret and CPU-oracle routes
    agree bit-for-bit."""
    old, new = rand_u32((6, 128), seed=40), rand_u32((6, 128), seed=41)
    stored = ref.fletcher_blocks_ref(old)
    co = coeffs_for(r) if r > 1 else None
    for interpret in (None, True):      # None -> CPU oracle route
        sd, ck, dig = ops.fused_commit_s_stream(old, new, co,
                                                chunk_blocks=4,
                                                interpret=interpret)
        assert sd.shape == (r, 6, 128)
        want_sd = (old ^ new)[None] if r == 1 else ref.sdelta_stack_ref(
            old ^ new, co)
        assert_trees_equal((sd, ck), (want_sd, ref.fletcher_blocks_ref(new)))
        np.testing.assert_array_equal(np.asarray(dig),
                                      np.asarray(cksum.combine(ck, 128)))
        sd, ck, bad, dig = ops.fused_verify_commit_s_stream(
            old, new, stored, co, chunk_blocks=4, interpret=interpret)
        assert not np.asarray(bad).any()
        np.testing.assert_array_equal(np.asarray(sd), np.asarray(want_sd))


def test_syndrome_scale_stacked_kernel_vs_oracle():
    """Satellite: one stacked-plane kernel replaces the per-plane gf_scale
    loop — 2-D and 1-D deltas (the flush path flattens), 1024-divisible
    and not."""
    co = coeffs_for(3)
    for shape, seed in [((8, 1024), 50), ((7, 96), 51), ((4096,), 52),
                        ((1000,), 53)]:
        d = rand_u32(shape, seed=seed)
        got = ops.syndrome_scale(d, co, interpret=True)
        want = ref.sdelta_stack_ref(d, co)
        assert got.shape == (3,) + shape
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(
            np.asarray(ops.syndrome_scale(d, co)), np.asarray(want))
    # r=1 stays the PR 1 program: the raw delta, never recomputed
    d = rand_u32((4, 64), seed=54)
    np.testing.assert_array_equal(
        np.asarray(ops.syndrome_scale(d, None)), np.asarray(d)[None])


def test_stream_policy_thresholds():
    kw = dict(threshold_words=1 << 20, chunk_words=1 << 16)
    assert ops.stream_chunk_blocks(256, 1024, **kw) is None   # 1 MB < 4 MiB
    assert ops.stream_chunk_blocks(4096, 1024, **kw) == 64    # 16 MiB row
    assert ops.stream_chunk_blocks(4096, 1024, threshold_words=0,
                                   chunk_words=1 << 16) is None
    # chunk never exceeds the row, never drops below one page
    assert ops.stream_chunk_blocks(4, 1024, threshold_words=1,
                                   chunk_words=1 << 16) == 4
    assert ops.stream_chunk_blocks(8, 4096, threshold_words=1,
                                   chunk_words=64) == 1


# -- chunked collectives ------------------------------------------------------

def put_rows(mesh, rows):
    return jax.device_put(jnp.asarray(rows.reshape(-1)),
                          NamedSharding(mesh, P(("data",))))


@pytest.mark.parametrize("r", [1, 3])
@pytest.mark.parametrize("chunks", [2, 4, 7])
def test_chunked_syndrome_reduce_scatter_matches_unchunked(mesh81, r,
                                                           chunks):
    g = mesh81.shape["data"]
    n = 64 * g
    rows = np.random.default_rng(r * 10 + chunks).integers(
        0, 2**32, size=(g, n), dtype=np.uint32)
    x = put_rows(mesh81, rows)

    def run(c):
        f = shard_map(
            lambda row: coll.syndrome_reduce_scatter(row, r, "data",
                                                     chunks=c),
            mesh=mesh81, in_specs=(P(("data",)),),
            out_specs=P(None, ("data",)), check_vma=False)
        return np.asarray(jax.jit(f)(x))

    np.testing.assert_array_equal(run(chunks), run(1))


@pytest.mark.parametrize("r", [1, 3])
def test_chunked_syndrome_apply_delta_matches_unchunked(mesh81, r):
    g = mesh81.shape["data"]
    n = 64 * g
    rng = np.random.default_rng(77 + r)
    sdelta = rng.integers(0, 2**32, size=(g, r, n), dtype=np.uint32)
    synd = rng.integers(0, 2**32, size=(g, r, n // g), dtype=np.uint32)
    sd = jax.device_put(jnp.asarray(sdelta.reshape(g * r, n)),
                        NamedSharding(mesh81, P(("data",))))
    sy = jax.device_put(jnp.asarray(synd.reshape(g * r, n // g)),
                        NamedSharding(mesh81, P(("data",))))

    def run(c):
        f = shard_map(
            lambda s, d: coll.syndrome_apply_delta(
                s.reshape(r, n // g), d.reshape(r, n), "data", chunks=c),
            mesh=mesh81, in_specs=(P(("data",)), P(("data",))),
            out_specs=P(None, ("data",)), check_vma=False)
        return np.asarray(jax.jit(f)(sy, sd))

    np.testing.assert_array_equal(run(4), run(1))


# -- engine-level bit-identity ------------------------------------------------

def _assert_protection_equal(pa, pb, mode):
    np.testing.assert_array_equal(np.asarray(pa.synd), np.asarray(pb.synd))
    np.testing.assert_array_equal(np.asarray(pa.digest),
                                  np.asarray(pb.digest))
    np.testing.assert_array_equal(np.asarray(pa.row), np.asarray(pb.row))
    if mode.has_cksums:
        np.testing.assert_array_equal(np.asarray(pa.cksums),
                                      np.asarray(pb.cksums))


def make_protector(mesh, state, specs, mode, **kw):
    kw.setdefault("block_words", 64)
    return Protector(mesh, jax.eval_shape(lambda: state), specs, mode=mode,
                     **kw)


STREAM_KW = dict(stream_threshold_words=1, stream_chunk_words=128)


@pytest.mark.parametrize("mode,red", [(Mode.MLPC, 1), (Mode.MLPC, 3),
                                      (Mode.MLP, 2)])
def test_streamed_protector_commits_match_flat(mesh42, mode, red):
    """stream_threshold_words=1 forces every bulk commit through the
    streamed kernels + chunked collectives; the protected state must
    stay bit-identical to the flat protector's after every commit."""
    state, specs, _ = small_state(mesh42)
    p_flat = make_protector(mesh42, state, specs, mode, redundancy=red,
                            stream_threshold_words=0)
    p_str = make_protector(mesh42, state, specs, mode, redundancy=red,
                           **STREAM_KW)
    assert p_str.stream_chunk() is not None, \
        "test must exercise the streamed path"
    pr_f, pr_s = p_flat.init(state), p_str.init(state)
    cur = state
    for i in range(3):
        cur = jax.tree.map(lambda x: (x * 1.01 + 0.01).astype(x.dtype), cur)
        key = jax.random.PRNGKey(i)
        pr_f, ok_f = p_flat.commit(pr_f, cur, rng_key=key, data_cursor=i,
                                   verify_old=True)
        pr_s, ok_s = p_str.commit(pr_s, cur, rng_key=key, data_cursor=i,
                                  verify_old=True)
        assert bool(ok_f) and bool(ok_s)
        _assert_protection_equal(pr_f, pr_s, mode)
    # the non-verifying commit takes the fletcher_stream + rebuild route
    cur = jax.tree.map(lambda x: (x + 1).astype(x.dtype), cur)
    pr_f, _ = p_flat.commit(pr_f, cur)
    pr_s, _ = p_str.commit(pr_s, cur)
    _assert_protection_equal(pr_f, pr_s, mode)


@pytest.mark.parametrize("window", [1, 16])
@pytest.mark.parametrize("red", [1, 3])
def test_streamed_deferred_bulk_matches_sync_at_boundaries(mesh42, window,
                                                           red):
    """The deferred bulk engine's fused_accum_commit path, with the
    streaming threshold forced on: at every window boundary (and per
    step for the digest) it must land exactly where the synchronous
    streamed engine lands."""
    state, specs, _ = small_state(mesh42)
    p = make_protector(mesh42, state, specs, Mode.MLPC, redundancy=red,
                       **STREAM_KW)
    prot_sync = p.init(state)
    eng = DeferredProtector(p, window=window, donate=False)
    est = eng.init(state)
    cur = state
    steps = 2 * window if window > 1 else 3
    for i in range(steps):
        cur = jax.tree.map(lambda x: (x * 1.02 + 0.005).astype(x.dtype),
                           cur)
        key = jax.random.PRNGKey(100 + i)
        prot_sync, ok_s = p.commit(prot_sync, cur, rng_key=key)
        est, ok_d = eng.commit(est, cur, rng_key=key)
        assert bool(ok_s) and bool(ok_d)
        np.testing.assert_array_equal(np.asarray(prot_sync.digest),
                                      np.asarray(est.prot.digest))
        if (i + 1) % window == 0:
            _assert_protection_equal(prot_sync, est.prot, Mode.MLPC)
