"""XOR parity over zone rows (Pangolin §3.1, §3.5).

All functions run *inside* a shard_map over the full mesh and operate on the
local word row; `axis_name` is the zone (data) axis of size G.

Three update paths, mirroring the paper's hybrid scheme:

  * build      — full XOR reduce-scatter of the rows (initialization, and
                 the "writer lock / plain XOR" path for large updates).
  * patch      — incremental: Delta = old XOR new on the *dirty pages only*,
                 XOR-reduced and applied to the owners' parity segments.
                 XOR's commutativity makes concurrent patches order-free —
                 the paper's atomic-XOR insight, realized as a collective.
  * hybrid     — picks patch vs build from the dirty fraction, the analogue
                 of the paper's 512 B threshold.

Reconstruction (§3.6): lost row r = XOR of surviving rows XOR parity,
computed online by all survivors.

Beyond the paper, the same three paths generalize to the Reed-Solomon
syndrome stack S_0..S_{r-1} (S_k = XOR_i g^(k·i)·row_i over GF(2^32),
core/gf.py): `build_syndromes` / `apply_sdelta` / `patch_syndrome_delta`
are the stack forms of build / bulk-delta / patch, `verify_syndromes`
the per-syndrome invariant, and `reconstruct_e` solves any e <= r
simultaneous rank losses through the e x e Vandermonde inverse.  S_0 IS
the parity above — the single-parity functions are kept for the
r=1-specialized paths (single-loss reconstruction, page repair).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import gf
from repro.core.layout import ZoneLayout
from repro.dist import collectives as coll


# ---------------------------------------------------------------------------
# bulk path
# ---------------------------------------------------------------------------

def build_parity(row: jax.Array, axis_name: str) -> jax.Array:
    """Full parity build: XOR-reduce-scatter rows; rank keeps its segment."""
    return coll.xor_reduce_scatter(row, axis_name)


def apply_delta(parity_seg: jax.Array, delta_row: jax.Array,
                axis_name: str) -> jax.Array:
    """Bulk delta path: parity ^= XOR-reduce-scatter(old ^ new).

    Algebraically identical to `build_parity(row_new)` whenever the XOR
    invariant held before the commit (rs distributes over XOR), but it
    consumes the delta the fused commit kernel already produced — so the
    commit never re-reads the rows to rebuild parity.
    """
    return parity_seg ^ coll.xor_reduce_scatter(delta_row, axis_name)


# ---------------------------------------------------------------------------
# incremental patch path
# ---------------------------------------------------------------------------

def page_view(row: jax.Array, block_words: int) -> jax.Array:
    return row.reshape(-1, block_words)


def gather_pages(row: jax.Array, page_idx: jax.Array,
                 block_words: int) -> jax.Array:
    """(k, block_words) dirty page contents."""
    return page_view(row, block_words)[page_idx]


def patch_parity(parity_seg: jax.Array, old_pages: jax.Array,
                 new_pages: jax.Array, page_idx: jax.Array,
                 layout: ZoneLayout, axis_name: str) -> jax.Array:
    """Apply an incremental parity patch for the dirty pages.

    old_pages/new_pages: (k, block_words) contents of the dirty pages on this
    rank (page set must be SPMD-uniform across the zone); page_idx: (k,)
    global page indices within the row.  Communicates only k pages (XOR
    all-reduce), then each owner XORs the patch into its parity segment.
    """
    from repro.kernels import ops as kops
    delta = kops.xor_delta(old_pages, new_pages)         # (k, bw)
    return patch_parity_delta(parity_seg, delta, page_idx, layout, axis_name)


def patch_parity_delta(parity_seg: jax.Array, delta_pages: jax.Array,
                       page_idx: jax.Array, layout: ZoneLayout,
                       axis_name: str) -> jax.Array:
    """`patch_parity` for callers that already hold the delta.

    The fused commit sweep emits delta pages as a by-product of its single
    pass over (old, new); this entry point applies them without re-reading
    either operand.  The r=1 view of `patch_syndrome_delta`, so the
    owner-scatter semantics live in exactly one place.
    """
    return patch_syndrome_delta(parity_seg[None], delta_pages[None],
                                page_idx, layout, axis_name)[0]


# ---------------------------------------------------------------------------
# syndrome stack: generalized Reed-Solomon S_0..S_{r-1} (beyond paper)
# ---------------------------------------------------------------------------

def build_syndromes(row: jax.Array, r: int, axis_name: str, *,
                    chunks: int = 1) -> jax.Array:
    """Full stack build: (r, seg) — one overlapped collective for all r.

    S_k = XOR_i g^(k·i)·row_i; S_0 is classic XOR parity, so
    `build_syndromes(row, 1, ax)[0] == build_parity(row, ax)` bit-exactly
    (and lowers to the same program).  `chunks > 1` pipelines the GF
    weighting against the all-to-all per segment slice (bit-identical;
    see collectives.syndrome_reduce_scatter).
    """
    return coll.syndrome_reduce_scatter(row, r, axis_name, chunks=chunks)


def apply_sdelta(synd: jax.Array, sdelta_rows: jax.Array,
                 axis_name: str, *, chunks: int = 1) -> jax.Array:
    """Bulk stack delta: synd ^= reduce-scatter of pre-weighted deltas.

    `sdelta_rows` is the (r, n) stack the fused commit sweep emits —
    row k already weighted by g^(k·me) — so the combine is the plain XOR
    collective (GF addition IS XOR), batched across syndromes.
    `chunks > 1` splits the transfer so large-pool commits pipeline.
    """
    return coll.syndrome_apply_delta(synd, sdelta_rows, axis_name,
                                     chunks=chunks)


def patch_syndrome_delta(synd: jax.Array, sdelta_pages: jax.Array,
                         page_idx: jax.Array, layout: ZoneLayout,
                         axis_name: str) -> jax.Array:
    """Incremental stack patch for pre-weighted dirty-page deltas.

    `synd`: (r, seg_words) stack; `sdelta_pages`: (r, k, bw) — syndrome
    k's deltas weighted by g^(k·me).  Every syndrome is linear over XOR
    once the rank scaled its delta, so ONE batched XOR all-reduce
    combines all r patch sets and the owner-scatter routing (computed
    once from `page_idx`) applies across the stack.
    """
    bw = layout.block_words
    r = synd.shape[0]
    patch = coll.xor_all_reduce(sdelta_pages, axis_name)     # (r, k, bw)
    # Page p lives in the segment of rank p // pages_per_seg.
    pages_per_seg = layout.seg_words // bw
    me = lax.axis_index(axis_name)
    owner = page_idx // pages_per_seg
    local_page = page_idx % pages_per_seg
    mine = (owner == me)
    seg_pages = synd.reshape(r, pages_per_seg, bw)
    # Scatter-XOR with O(k) work per syndrome: page indices within one
    # commit are unique, so gather -> xor -> scatter-set is exact;
    # non-owned rows route to the out-of-range sentinel and are dropped
    # by the scatter itself (an earlier version concatenated a dummy row
    # and sliced it back off, which copied the whole segment per patch).
    # This is the "atomic XOR" application — commutativity already did
    # the cross-rank combining in the all-reduce above.
    scatter_idx = jnp.where(mine, local_page, pages_per_seg)
    cur = seg_pages[:, jnp.minimum(scatter_idx, pages_per_seg - 1)]
    out = seg_pages.at[:, scatter_idx].set(cur ^ patch, mode="drop")
    return out.reshape(r, -1)


def verify_syndromes(row: jax.Array, synd: jax.Array,
                     axis_name: str, *, chunks: int = 1) -> jax.Array:
    """Zone invariant per syndrome: returns (r,) bool, zone-agreed.

    Entry k is True iff XOR_i g^(k·i)·row_i equals the stored S_k on
    every rank (entry 0 is the classic parity invariant).
    """
    r = synd.shape[0]
    fresh = coll.syndrome_reduce_scatter(row, r, axis_name, chunks=chunks)
    ok_local = jnp.all(fresh == synd, axis=-1)               # (r,)
    return lax.pmin(ok_local.astype(jnp.int32), axis_name) > 0


def reconstruct_e(row: jax.Array, synd: jax.Array, lost_ranks,
                  axis_name: str) -> tuple:
    """Rebuild e <= r lost ranks' rows online from the syndrome stack.

    `lost_ranks` are *static* distinct rank indices (recovery is rare;
    one compiled program per erasure set).  Survivors contribute their
    rows to the first e syndromes; the lost ranks contribute zeros, so

        S_k ^ s_k = XOR_j g^(k·a_j) · X_j        k = 0..e-1

    which `gf.solve_e` inverts with exact host-integer constants.  Every
    rank returns all e reconstructed rows in `lost_ranks` order (the
    lost ranks replace their state; survivors may verify or discard).
    Also covers e-1 losses with an outstanding scribbled rank: name the
    scribbled rank as the extra loss and all come back to intended
    values.
    """
    ranks = tuple(int(a) for a in lost_ranks)
    e = len(ranks)
    assert e >= 1 and len(set(ranks)) == e, ranks
    assert e <= synd.shape[0], (
        f"{e} erasures need {e} syndromes; stack holds {synd.shape[0]}")
    me = lax.axis_index(axis_name)
    lost = functools.reduce(jnp.logical_or,
                            [me == a for a in ranks])
    contrib = jnp.where(lost, jnp.zeros_like(row), row)
    survivors = coll.syndrome_reduce_scatter(contrib, e, axis_name)
    segs = gf.solve_e(synd[:e] ^ survivors, ranks)
    return tuple(coll.all_gather_row(s, axis_name) for s in segs)


# ---------------------------------------------------------------------------
# hybrid (paper §3.5)
# ---------------------------------------------------------------------------

def hybrid_update(row_old: jax.Array, row_new: jax.Array,
                  parity_seg: jax.Array, layout: ZoneLayout,
                  axis_name: str, dirty_page_idx=None,
                  threshold_fraction: float = 0.5) -> jax.Array:
    """Choose the patch or bulk path by dirty fraction (static decision).

    `dirty_page_idx` is a static list/array of dirty page indices, or None
    for "everything changed".  The threshold plays the role of the paper's
    512 B atomic-XOR/plain-XOR crossover.
    """
    n_pages = layout.n_blocks
    if dirty_page_idx is not None and len(dirty_page_idx) == 0:
        # metadata-only transaction (the paper's "free"): parity unchanged
        return parity_seg
    if dirty_page_idx is None or len(dirty_page_idx) / n_pages >= threshold_fraction:
        return build_parity(row_new, axis_name)
    idx = jnp.asarray(dirty_page_idx, jnp.int32)
    old_pages = gather_pages(row_old, idx, layout.block_words)
    new_pages = gather_pages(row_new, idx, layout.block_words)
    return patch_parity(parity_seg, old_pages, new_pages, idx, layout,
                        axis_name)


# ---------------------------------------------------------------------------
# reconstruction (paper §3.6)
# ---------------------------------------------------------------------------

def reconstruct_row(row: jax.Array, parity_seg: jax.Array,
                    lost_rank, axis_name: str) -> jax.Array:
    """Rebuild the lost rank's row online; survivors contribute their rows.

    Every rank returns the same reconstructed row (the lost rank replaces its
    state from it; survivors can discard it or use it for verification).
    """
    me = lax.axis_index(axis_name)
    contrib = jnp.where(me == lost_rank, jnp.zeros_like(row), row)
    # XOR of surviving rows, scattered by segment...
    survivor_seg = coll.xor_reduce_scatter(contrib, axis_name)
    # ... XOR parity segment = lost row's segment, held by each owner.
    # But parity segments are owned per rank; segment i of the lost row is
    # survivor_seg_i XOR parity_seg_i on rank i.
    lost_seg = survivor_seg ^ parity_seg
    return coll.all_gather_row(lost_seg, axis_name)


def verify_parity(row: jax.Array, parity_seg: jax.Array,
                  axis_name: str) -> jax.Array:
    """Zone-wide invariant: XOR of all rows equals parity. Returns bool."""
    fresh = coll.xor_reduce_scatter(row, axis_name)
    ok_local = jnp.all(fresh == parity_seg)
    # AND across the zone == min over {0,1}
    return lax.pmin(ok_local.astype(jnp.int32), axis_name) > 0
