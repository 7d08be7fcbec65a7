"""Fault-tolerant transactions over distributed state (Pangolin §3.4).

The `Protector` wraps a sharded state pytree (params, optimizer moments, KV
caches, ...) with Pangolin's protection stack and exposes the transactional
API:

    prot   = protector.init(state)                      # build parity+checksums
    prot', ok = protector.commit(prot, new_state, ...)  # transactional update
    report = protector.scrub(prot)                      # periodic verification
    prot'  = protector.recover_rank(prot, lost)         # online media recovery
    prot'  = protector.repair_pages(prot, rank, pages)  # online scribble repair

Commit pipeline (paper order: redo log -> objects -> parity, idempotent):
  1. redo record appended + commit-marked (replicated),
  2. canary verified (abort without touching state on mismatch),
  3. object checksums refreshed (incremental where dirty pages are known),
  4. parity updated via the hybrid patch/bulk scheme,
  5. the new state replaces the old (functional swap).

Protection-mode ladder mirrors the paper's evaluation (Table 2):
  NONE   ~ Pangolin baseline (micro-buffering + canary only)
  ML     ~ + metadata/redo-log replication
  MLP    ~ + XOR parity (media-error recovery; compare w/ REPLICA)
  MLPC   ~ + object checksums (scribble detection)
  REPLICA~ libpmemobj's replicated mode (2x storage, the paper's baseline)

Orthogonal to the ladder, `redundancy` r (1..4) selects the syndrome
stack height of the parity modes: S_0 is the XOR parity above, and each
extra syndrome S_k = XOR_i g^(k·i)·row_i (GF(2^32) Reed-Solomon,
core/gf.py) buys one more simultaneous rank loss at one more parity
fraction of storage — any e <= r losses reconstruct online
(`recover_e`).  The former MLP2/MLPC2 dual-parity modes dissolved into
(mlp|mlpc, redundancy=2); `resolved_mode` keeps the aliases working.
"""
from __future__ import annotations

import dataclasses
import enum
import functools
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import checksum as ck
from repro.core import gf
from repro.core import layout as layout_mod
from repro.core import parity as parity_mod
from repro.core import redolog
from repro.dist import collectives as coll
from repro.kernels import ops as kops

PyTree = Any
U32 = jnp.uint32


class Mode(enum.Enum):
    NONE = "none"          # micro-buffering + canary only (pgl baseline)
    ML = "ml"              # + redo-log/metadata replication
    MLP = "mlp"            # + parity (syndrome stack, height = redundancy)
    MLPC = "mlpc"          # + checksums
    REPLICA = "replica"    # full replica (Pmemobj-R analogue)

    @property
    def has_parity(self) -> bool:
        return self in (Mode.MLP, Mode.MLPC)

    @property
    def has_cksums(self) -> bool:
        return self is Mode.MLPC

    @property
    def has_log(self) -> bool:
        return self in (Mode.ML, Mode.MLP, Mode.MLPC)

    @property
    def has_replica(self) -> bool:
        return self is Mode.REPLICA


# redundancy is orthogonal to the ladder now: a parity mode carries a
# syndrome stack S_0..S_{r-1} (S_0 = XOR parity; S_1 the former Q), and
# r = ProtectConfig.redundancy selects its height.  The old dual-parity
# mode names survive only as config aliases.
MAX_REDUNDANCY = 4
_MODE_ALIASES = {"mlp2": ("mlp", 2), "mlpc2": ("mlpc", 2)}


def resolved_mode(mode, redundancy: int = 1) -> tuple:
    """Resolve (mode-or-alias, redundancy) to the (Mode, r) pair.

    The former dual-parity Mode members dissolved into this: "mlp2" /
    "mlpc2" resolve to their base mode with redundancy >= 2 (an explicit
    higher `redundancy` wins, so `("mlp2", 3)` means a 3-syndrome MLP
    stack).  Raises with an actionable message for r outside 1..4 or a
    redundancy > 1 on a mode that keeps no parity to stack onto.
    """
    implied = 1
    if isinstance(mode, Mode):
        m = mode
    else:
        name, implied = _MODE_ALIASES.get(mode, (mode, 1))
        m = Mode(name)
    r = max(int(redundancy), implied)
    if not 1 <= int(redundancy) <= MAX_REDUNDANCY or \
            not 1 <= r <= MAX_REDUNDANCY:
        raise ValueError(
            f"redundancy={redundancy} — the syndrome stack holds 1 to "
            f"{MAX_REDUNDANCY} syndromes (1 = XOR parity P, 2 adds the "
            "GF(2^32) Q row, 3-4 add higher Vandermonde rows); larger "
            "stacks exceed the validated Reed-Solomon configuration")
    if r > 1 and not m.has_parity:
        raise ValueError(
            f"redundancy={r} with mode='{m.value}' — extra syndromes "
            "extend parity, they cannot replace it; use a parity mode "
            "(mlp or mlpc)")
    return m, r


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ProtectedState:
    state: PyTree
    # Syndrome stack, (*mesh_dims, r, seg_words) u32 — parity modes only.
    # Plane k holds this rank's segment of S_k = XOR_i g^(k·i)·row_i over
    # GF(2^32) (core/gf.py); plane 0 is classic XOR parity, plane 1 the
    # former Q.  Any e <= r simultaneous rank losses solve through the
    # e x e Vandermonde inverse (parity.reconstruct_e).
    synd: Optional[jax.Array]
    cksums: Optional[jax.Array]      # (*mesh_dims, n_blocks, 2) u32
    digest: Optional[jax.Array]      # (*mesh_dims, 2) u32 whole-row digest
    replica: Optional[PyTree]
    log: Optional[redolog.RedoLog]
    step: jax.Array                  # scalar u32, replicated
    # Cached flattened word row, (*mesh_dims, row_words) u32.  Invariant:
    # row == flatten_row(layout, state) whenever protection is active, so
    # commits diff rows directly instead of re-flattening the whole state
    # every step.  Rebuilt (never trusted) by recovery and repair.
    row: Optional[jax.Array] = None

    @property
    def parity(self) -> Optional[jax.Array]:
        """The S_0 (XOR parity) plane of the syndrome stack, read-only."""
        return None if self.synd is None else self.synd[..., 0, :]

    def tree_flatten(self):
        return ((self.state, self.synd, self.cksums, self.digest,
                 self.replica, self.log, self.step, self.row), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def tree_select(pred, on_true: PyTree, on_false: PyTree) -> PyTree:
    return jax.tree.map(lambda t, f: jnp.where(pred, t, f), on_true, on_false)


def _zone_clean(ok, bad, axis_name):
    """AND `no block is bad` into ok, agreed across the zone (pmin)."""
    ok = jnp.logical_and(ok, jnp.logical_not(jnp.any(bad)))
    return lax.pmin(ok.astype(jnp.int32), axis_name) > 0


def _spec_leaf(x):
    return isinstance(x, P)


class Protector:
    """Builds jitted, shard_map'd protection operations for one state layout."""

    def __init__(self, mesh: Mesh, abstract_state: PyTree, state_specs: PyTree,
                 *, data_axis: str = "data", mode: Mode = Mode.MLPC,
                 redundancy: int = 1,
                 block_words: int = layout_mod.PAGE_WORDS,
                 hybrid_threshold: float = 0.5,
                 log_capacity: int = 64,
                 stream_threshold_words: int = 1 << 20,
                 stream_chunk_words: int = 1 << 16):
        mode, redundancy = resolved_mode(mode, redundancy)
        self.mesh = mesh
        self.mode = mode
        self.data_axis = data_axis
        self.axis_names = tuple(mesh.axis_names)
        self.n_axes = len(self.axis_names)
        self.group_size = mesh.shape[data_axis]
        if mode.has_parity and redundancy > self.group_size - 1:
            raise ValueError(
                f"redundancy={redundancy} on a zone of "
                f"{self.group_size} data ranks — at most "
                f"num_ranks - 1 = {self.group_size - 1} simultaneous "
                "losses are solvable (the erasure system needs at least "
                "one survivor); shrink redundancy or grow the data axis")
        self.redundancy = redundancy if mode.has_parity else 1
        self.hybrid_threshold = hybrid_threshold
        self.log_capacity = log_capacity
        self.stream_threshold_words = int(stream_threshold_words)
        self.stream_chunk_words = int(stream_chunk_words)
        self.state_specs = state_specs

        shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s), state_specs, is_leaf=_spec_leaf)
        self.layout = layout_mod.build_layout(
            abstract_state, self.group_size, shardings,
            block_words=block_words)

        self._zone_spec = P(*self.axis_names)
        self._mesh_dims = tuple(mesh.shape[a] for a in self.axis_names)
        self._jit_cache: dict = {}

    # -- sharding helpers -----------------------------------------------------

    def parity_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, self._zone_spec)

    def abstract_protected(self, abstract_state: PyTree) -> ProtectedState:
        """ShapeDtypeStruct ProtectedState (dry-run: no allocation)."""
        lo, mode = self.layout, self.mode
        zdims = self._mesh_dims

        def sds(shape, dtype=U32):
            return jax.ShapeDtypeStruct(shape, dtype)

        synd = (sds(zdims + (self.redundancy, lo.seg_words))
                if mode.has_parity else None)
        cksums = sds(zdims + (lo.n_blocks, 2)) if mode.has_cksums else None
        dig = (sds(zdims + (2,))
               if (mode.has_parity or mode.has_cksums) else None)
        row = (sds(zdims + (lo.row_words,))
               if (mode.has_parity or mode.has_cksums) else None)
        replica = (jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), abstract_state)
            if mode.has_replica else None)
        log = (jax.eval_shape(lambda: redolog.make(self.log_capacity))
               if mode.has_log else None)
        return ProtectedState(state=abstract_state, synd=synd,
                              cksums=cksums, digest=dig, replica=replica,
                              log=log, step=sds((), U32), row=row)

    def protected_specs(self) -> ProtectedState:
        """PartitionSpec tree matching ProtectedState."""
        mode = self.mode
        z = self._zone_spec
        log = (jax.tree.map(lambda _: P(),
                            jax.eval_shape(lambda: redolog.make(
                                self.log_capacity)))
               if mode.has_log else None)
        return ProtectedState(
            state=self.state_specs,
            synd=z if mode.has_parity else None,
            cksums=z if mode.has_cksums else None,
            digest=z if (mode.has_parity or mode.has_cksums) else None,
            replica=self.state_specs if mode.has_replica else None,
            log=log, step=P(),
            row=z if (mode.has_parity or mode.has_cksums) else None)

    def _pack(self, x: jax.Array) -> jax.Array:
        """Local per-rank value -> shard_map output layout (leading 1s)."""
        return x.reshape((1,) * self.n_axes + x.shape)

    def _unpack(self, x: jax.Array) -> jax.Array:
        return x.reshape(x.shape[self.n_axes:])

    def _smap(self, f, in_specs, out_specs):
        return shard_map(f, mesh=self.mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

    # -- streaming policy -----------------------------------------------------

    def stream_chunk(self) -> Optional[int]:
        """Pages per streamed VMEM chunk for full-row sweeps, or None.

        None means the local row is below `stream_threshold_words` and the
        flat whole-grid kernels keep the commit (their automatic pipelining
        wins on small rows); otherwise the blockwise double-buffered
        streaming kernels take it, `stream_chunk_words` per chunk.
        """
        lo = self.layout
        return kops.stream_chunk_blocks(
            lo.n_blocks, lo.block_words,
            threshold_words=self.stream_threshold_words,
            chunk_words=self.stream_chunk_words)

    def coll_chunks(self) -> int:
        """Slices per syndrome collective when the commit streams.

        Matches the kernel chunking scale so the per-chunk syndrome delta
        overlaps the all-to-all of the previous slice; capped at 8 —
        beyond that the per-launch latency dominates the overlap win.
        """
        if self.stream_chunk() is None:
            return 1
        return max(1, min(
            8, self.layout.seg_words // max(1, self.stream_chunk_words)))

    # -- init ------------------------------------------------------------------

    def init(self, state: PyTree, *, jit: bool = True) -> ProtectedState:
        lo, ax = self.layout, self.data_axis
        mode, r = self.mode, self.redundancy

        def _init(state):
            row = layout_mod.flatten_row(lo, state)
            outs = {}
            if mode.has_parity:
                outs["synd"] = self._pack(
                    parity_mod.build_syndromes(row, r, ax,
                                               chunks=self.coll_chunks()))
            if mode.has_cksums:
                cks = ck.block_checksums(row, lo.block_words)
                outs["cksums"] = self._pack(cks)
                outs["digest"] = self._pack(ck.combine(cks, lo.block_words))
            elif mode.has_parity:
                outs["digest"] = self._pack(ck.digest(row, lo.block_words))
            if mode.has_parity or mode.has_cksums:
                outs["row"] = self._pack(row)
            return outs

        out_specs = {}
        if mode.has_parity:
            out_specs["synd"] = self._zone_spec
        if mode.has_cksums:
            out_specs["cksums"] = self._zone_spec
        if mode.has_parity or mode.has_cksums:
            out_specs["digest"] = self._zone_spec
            out_specs["row"] = self._zone_spec
        fn = self._smap(_init, in_specs=(self.state_specs,),
                        out_specs=out_specs)
        if jit:
            fn = jax.jit(fn)
        outs = fn(state)
        replica = jax.tree.map(jnp.copy, state) if mode.has_replica else None
        log = redolog.make(self.log_capacity) if mode.has_log else None
        return ProtectedState(
            state=state, synd=outs.get("synd"), cksums=outs.get("cksums"),
            digest=outs.get("digest"), replica=replica, log=log,
            step=jnp.zeros((), U32), row=outs.get("row"))

    # -- commit ------------------------------------------------------------------

    def make_commit(self, dirty_pages: Optional[Sequence[int]] = None,
                    verify_old: bool = False):
        """Build the jitted commit function (single-sweep engine).

        `dirty_pages`: static page-index list when the update's footprint is
        known (decode-time KV appends); None = whole state dirty (train).
        `verify_old`: verify the old row's checksums before committing (the
        paper's verify-at-micro-buffer-open), abort on mismatch.

        The engine touches HBM once per operand.  The cached row
        (`ProtectedState.row`) stands in for the old state, so the old
        pytree is never re-flattened; the digest folds from per-block
        Fletcher terms instead of re-reading the row.  Per path:

          bulk, no verify   — old is not read at all: one fused checksum
            sweep over new + the parity reduce-scatter of new.
          bulk, verify      — old must be swept once anyway, so the fused
            kernel emits verify + parity delta + new checksums from one
            pass over (old, new) and parity consumes the delta
            (parity ^ rs(delta) == rs(new) under the XOR invariant).
          patch (dirty set) — the new row is word-spliced from the cache
            (no full re-flatten) and one fused sweep over the dirty pages
            yields [verify +] delta + checksums; the delta feeds the
            owner-scatter parity patch.  Cost ∝ modified range.

        With `verify_old` the old row is re-flattened from the live state
        (a scribble lives in the state; a clean cache would launder it);
        verification covers the full row on the bulk path and the opened
        (dirty) pages on the patch path.
        """
        lo, ax, mode = self.layout, self.data_axis, self.mode
        r = self.redundancy
        thresh = self.hybrid_threshold
        bw = lo.block_words
        # static path choice, the paper's atomic-XOR/plain-XOR crossover
        meta_only = dirty_pages is not None and len(dirty_pages) == 0
        patch = (dirty_pages is not None and not meta_only
                 and len(dirty_pages) / lo.n_blocks < thresh)
        dirty_leaves = (layout_mod.leaves_for_pages(lo, dirty_pages)
                        if (meta_only or patch) else None)
        dirty_idx = (np.asarray(list(dirty_pages), np.int32)
                     if patch else None)
        # flat-vs-streamed is a static program choice: large rows stream
        # through the double-buffered kernels and chunk the syndrome
        # collective to overlap weighting with the wire; the patch path
        # is below-threshold by construction and always stays flat
        scb = self.stream_chunk()
        cc = self.coll_chunks()

        def _protect(state_old, row_cache, synd, cksums, digest,
                     state_new, canary_ok):
            synd_l = self._unpack(synd) if synd is not None else None
            cksums_l = self._unpack(cksums) if cksums is not None else None
            digest_l = self._unpack(digest)
            # this rank's syndrome coefficient vector (g^(k·me))_k; None
            # for r=1 keeps the single-parity kernels and their program
            coeffs = (gf.rank_syndrome_coeffs(self.group_size, r, ax)
                      if r > 1 else None)
            row_old = (layout_mod.flatten_row(lo, state_old) if verify_old
                       else self._unpack(row_cache))
            if meta_only or patch:
                row_new = layout_mod.update_row(lo, row_old, state_new,
                                                dirty_leaves)
            else:
                row_new = layout_mod.flatten_row(lo, state_new)
            ok = canary_ok
            new_synd, new_cksums, new_digest = synd_l, cksums_l, digest_l
            if meta_only:
                pass          # the paper's "free" metadata-only transaction
            elif patch:
                idx = jnp.asarray(dirty_idx)
                old_pages = parity_mod.gather_pages(row_old, idx, bw)
                new_pages = parity_mod.gather_pages(row_new, idx, bw)
                if mode.has_cksums:
                    if verify_old:
                        sdelta_p, fresh, bad = kops.fused_verify_commit_s(
                            old_pages, new_pages, cksums_l[idx], coeffs)
                        ok = _zone_clean(ok, bad, ax)
                    else:
                        sdelta_p, fresh = kops.fused_commit_s(
                            old_pages, new_pages, coeffs)
                    new_cksums = ck.set_blocks(cksums_l, fresh, idx)
                    new_digest = ck.combine(new_cksums, bw)
                else:
                    sdelta_p, fresh, old_ck = kops.fused_commit_old_terms_s(
                        old_pages, new_pages, coeffs)
                    new_digest = ck.update_digest(digest_l, old_ck, fresh,
                                                  idx, lo.n_blocks, bw)
                if mode.has_parity:
                    new_synd = parity_mod.patch_syndrome_delta(
                        synd_l, sdelta_p, idx, lo, ax)
            else:
                pages_new = parity_mod.page_view(row_new, bw)
                dig_new = None
                if verify_old and mode.has_cksums:
                    # old must be swept for verify anyway: the fused kernel
                    # shares that read with all r syndrome deltas, and the
                    # stack consumes them (S ^ rs(sdelta) == rs-stack(new))
                    pages_old = parity_mod.page_view(row_old, bw)
                    if scb is None:
                        sdelta, fresh, bad = kops.fused_verify_commit_s(
                            pages_old, pages_new, cksums_l, coeffs)
                    else:
                        sdelta, fresh, bad, dig_new = (
                            kops.fused_verify_commit_s_stream(
                                pages_old, pages_new, cksums_l, coeffs,
                                chunk_blocks=scb))
                    ok = _zone_clean(ok, bad, ax)
                    if mode.has_parity:
                        new_synd = parity_mod.apply_sdelta(
                            synd_l, sdelta.reshape(r, -1), ax, chunks=cc)
                else:
                    # without verify the old row is not read at all: a
                    # delta here would cost a write+read of a row-sized
                    # buffer for nothing — reduce-scatter the new row
                    if scb is None:
                        fresh = kops.fletcher_blocks(pages_new)
                    else:
                        fresh, dig_new = kops.fletcher_stream(
                            pages_new, chunk_blocks=scb)
                    if mode.has_parity:
                        new_synd = parity_mod.build_syndromes(row_new, r,
                                                              ax, chunks=cc)
                if mode.has_cksums:
                    new_cksums = fresh
                # streamed sweeps fold the digest into the loop carry
                # (bit-identical to the combine over the term table)
                new_digest = (ck.combine(fresh, bw) if dig_new is None
                              else dig_new)
            outs = {"ok": ok,
                    "row": self._pack(jnp.where(ok, row_new, row_old)),
                    "digest": self._pack(jnp.where(ok, new_digest,
                                                   digest_l))}
            if mode.has_parity:
                outs["synd"] = self._pack(jnp.where(ok, new_synd, synd_l))
            if mode.has_cksums:
                outs["cksums"] = self._pack(
                    jnp.where(ok, new_cksums, cksums_l))
            return outs

        out_specs = {"ok": P(), "row": self._zone_spec,
                     "digest": self._zone_spec}
        if mode.has_parity:
            out_specs["synd"] = self._zone_spec
        if mode.has_cksums:
            out_specs["cksums"] = self._zone_spec
        protect = self._smap(
            _protect,
            in_specs=(self.state_specs, self._zone_spec, self._zone_spec,
                      self._zone_spec, self._zone_spec,
                      self.state_specs, P()),
            out_specs=out_specs)

        def commit(prot: ProtectedState, state_new: PyTree, *,
                   data_cursor=0, rng_key=None, canary_ok=True):
            step = prot.step + U32(1)
            canary_ok = jnp.asarray(canary_ok, bool)
            log = prot.log
            digest_for_log = jnp.zeros((2,), U32)
            new_row = prot.row
            if mode.has_parity or mode.has_cksums:
                outs = protect(prot.state, prot.row, prot.synd,
                               prot.cksums, prot.digest,
                               state_new, canary_ok)
                ok = outs["ok"]
                new_row = outs["row"]
                new_synd = outs.get("synd", prot.synd)
                new_cksums = outs.get("cksums", prot.cksums)
                new_digest = outs["digest"]
                digest_for_log = new_digest.reshape(-1, 2)[0]
            else:
                ok = canary_ok
                new_synd, new_cksums, new_digest = (prot.synd,
                                                    prot.cksums,
                                                    prot.digest)
            # paper ordering: log record (replicated) persists before object
            # writes; the commit mark follows the protected update.
            if mode.has_log:
                if rng_key is None:
                    rng_key = jax.random.PRNGKey(0)
                log = redolog.append(prot.log, step, data_cursor, rng_key,
                                     digest_for_log)
                log = tree_select(ok, redolog.commit_mark(log, step), log)
            new_state = tree_select(ok, state_new, prot.state)
            replica = prot.replica
            if mode.has_replica:
                replica = tree_select(ok, jax.tree.map(jnp.copy, state_new),
                                      prot.replica)
            return ProtectedState(
                state=new_state, synd=new_synd, cksums=new_cksums,
                digest=new_digest, replica=replica, log=log,
                step=jnp.where(ok, step, prot.step), row=new_row), ok

        return commit

    def commit(self, prot, state_new, *, dirty_pages=None, verify_old=False,
               donate=False, donate_new=False, **kw):
        """Cached-jit commit entry point.

        Distinct dirty-page sets (and the verify flag) key distinct
        compiled commits — a previous version folded `_dirty_key` into the
        cache key but always built the no-dirty-pages commit, silently
        sharing one stale program across different footprints.

        `donate=True` donates `prot` into its successor (row, parity,
        cksums, digest, log and state reuse their buffers in place —
        allocation-free steady state); the caller must then drop the old
        `prot` and keep only the returned one.

        `donate_new=True` is the ownership contract for a micro-buffer:
        the caller hands `state_new` over and never reads it again (it
        must share no buffer with `prot`).  The committed state then
        lives in `state_new`'s own buffers — the commit passes it
        through instead of copying it into the old state's.  An abort
        still returns the old state, copied into those buffers.
        """
        program = self.commit_program(
            dirty_pages=dirty_pages, verify_old=verify_old,
            donate=donate, donate_new=donate_new)
        return program(*self.commit_operands(prot, state_new,
                                             donate_new=donate_new), **kw)

    @staticmethod
    def commit_operands(prot, state_new, *, donate_new=False) -> tuple:
        """The positional operands of `commit_program(donate_new=...)`:
        `(prot, state_new)`, or for a consuming commit
        `(state_new, prot without its state, prot.state)`."""
        if donate_new:
            return (state_new, dataclasses.replace(prot, state=None),
                    prot.state)
        return (prot, state_new)

    def commit_program(self, *, dirty_pages=None, verify_old=False,
                       donate=False, donate_new=False):
        """The cached compiled commit for one (dirty set, verify, donate,
        donate_new) key — what `commit` dispatches and what the Pool
        facade routes through (benchmarks lower it to assert facade ==
        direct bytes).  `commit_operands` gives its arguments.

        JAX pairs each output with the first DONATED input of the same
        shape and dtype, in flattened-argument order.  The default
        program takes `(prot, state_new)`: with `donate` the output
        state lands in `prot.state`'s buffers, so XLA copies `state_new`
        there.  The `donate_new` program takes `(state_new, prot_meta,
        state_old)` and donates `state_new` (and, with `donate`,
        `prot_meta` — `prot` without its state): `state_new` comes
        first, so the output state aliases it and the pass-through is
        free.  `state_old` is never donated: every output already has a
        donated buffer to land in, and XLA would warn of a wasted one.
        Donating `prot` whole ahead of `state_new`, or anything of
        state shape before `state_new`, brings the copy back.
        """
        key = ("commit",
               tuple(int(p) for p in dirty_pages)
               if dirty_pages is not None else None,
               bool(verify_old), bool(donate), bool(donate_new))
        if key not in self._jit_cache:
            fn = self.make_commit(dirty_pages=dirty_pages,
                                  verify_old=verify_old)
            if donate_new:
                inner = fn

                def commit(state_new, prot_meta, state_old, **kw):
                    return inner(dataclasses.replace(prot_meta,
                                                     state=state_old),
                                 state_new, **kw)

                fn, donated = commit, ((0, 1) if donate else (0,))
            else:
                donated = (0,) if donate else ()
            # the canary verdict is host-known before dispatch: static,
            # so the all-clear program folds its abort select-chains away
            # (an abort compiles the cheap no-op variant once)
            self._jit_cache[key] = jax.jit(
                fn, donate_argnums=donated,
                static_argnames=("canary_ok",))
        return self._jit_cache[key]

    # -- scrub -------------------------------------------------------------------

    def make_scrub(self):
        """One fused scrub program: a single flatten of the live state
        feeds the checksum verify, the parity invariant check AND a
        row-cache divergence check (`row == flatten(state)` — nearly free
        with the row already in hand, and it catches a cache gone stale
        before a commit would trust it as the old operand).  All outputs
        land in one dict so the Scrubber fetches them with a single
        device_get."""
        lo, ax = self.layout, self.data_axis
        mode = self.mode

        def _scrub(state, row_cache, synd, cksums):
            row = layout_mod.flatten_row(lo, state)
            out = {}
            if mode.has_cksums:
                bad = ck.verify_blocks(row, self._unpack(cksums),
                                       lo.block_words)
                out["bad_pages"] = self._pack(bad)
            if mode.has_parity:
                # every syndrome invariant from one overlapped collective
                out["synd_ok"] = parity_mod.verify_syndromes(
                    row, self._unpack(synd), ax, chunks=self.coll_chunks())
            if mode.has_parity or mode.has_cksums:
                same = jnp.all(row == self._unpack(row_cache))
                out["row_cache_ok"] = (
                    lax.pmin(same.astype(jnp.int32), self.axis_names) > 0)
            return out

        out_specs = {}
        if mode.has_cksums:
            out_specs["bad_pages"] = self._zone_spec
        if mode.has_parity:
            out_specs["synd_ok"] = P()
        if mode.has_parity or mode.has_cksums:
            out_specs["row_cache_ok"] = P()
        fn = self._smap(_scrub, in_specs=(self.state_specs, self._zone_spec,
                                          self._zone_spec, self._zone_spec),
                        out_specs=out_specs)

        def scrub(prot: ProtectedState):
            return fn(prot.state, prot.row, prot.synd, prot.cksums)

        return scrub

    def scrub(self, prot):
        if "scrub" not in self._jit_cache:
            self._jit_cache["scrub"] = jax.jit(self.make_scrub())
        return self._jit_cache["scrub"](prot)

    def make_local_scrub(self):
        """Rank-local pre-check: no full-row collective anywhere.

        The global scrub's dominant cost is the syndrome reduce-scatter
        (r full-row weighted collectives).  This program verifies the
        same three surfaces with zone traffic of O(r·G) *words*:

          * this rank's state blocks against the checksum table — pure
            local compute, catches scribbles exactly like the global
            scrub does — reduced on device to a replicated mismatch
            *count* (the pre-check only decides suspect-or-not; block
            locations are the escalated global scrub's job);
          * the cached row against the live state — local compare;
          * this rank's syndrome segments against everyone's rows via a
            *folded* syndrome: the stacked-plane kernel weights the row
            into all r planes from one read (kernels/ops.syndrome_scale
            — the same device clmul the commit sweeps use, never host
            GF math), each rank XOR-folds per (syndrome, owner-segment)
            into an (r, G) word matrix, one tiny XOR all-reduce
            combines them (fold commutes with the XOR sum across
            ranks), and each owner compares the fold of its stored
            segments.  A fold catches any single corruption; only
            colliding corruptions that cancel in the fold escape to the
            global scrub — which is why this is the cheap pre-check,
            not a replacement.

        Every output is a replicated scalar (bad_count / synd_ok /
        row_cache_ok), so `Scrubber.precheck` fetches ONE device_get of
        a verdict — no row-sized or table-sized host transfer.
        """
        lo, ax = self.layout, self.data_axis
        mode, r, g = self.mode, self.redundancy, self.group_size

        def _local(state, row_cache, synd, cksums):
            row = layout_mod.flatten_row(lo, state)
            out = {}
            if mode.has_cksums:
                bad = ck.verify_blocks(row, self._unpack(cksums),
                                       lo.block_words)
                out["bad_count"] = lax.psum(
                    jnp.sum(bad.astype(jnp.uint32)), self.axis_names)
            if mode.has_parity:
                synd_l = self._unpack(synd)
                coeffs = (gf.rank_syndrome_coeffs(g, r, ax)
                          if r > 1 else None)
                weighted = kops.syndrome_scale(row, coeffs)
                segs = weighted.reshape(r, g, -1)
                folds = coll.xor_fold(segs, axis=2)          # (r, G)
                want = coll.xor_all_reduce(folds, ax)        # (r, G)
                me = lax.axis_index(ax)
                mine = coll.xor_fold(synd_l, axis=1)         # (r,)
                ok = mine == want[:, me]
                out["synd_ok"] = (
                    lax.pmin(ok.astype(jnp.int32), ax) > 0)
            if mode.has_parity or mode.has_cksums:
                same = jnp.all(row == self._unpack(row_cache))
                out["row_cache_ok"] = (
                    lax.pmin(same.astype(jnp.int32), self.axis_names) > 0)
            return out

        out_specs = {}
        if mode.has_cksums:
            out_specs["bad_count"] = P()
        if mode.has_parity:
            out_specs["synd_ok"] = P()
        if mode.has_parity or mode.has_cksums:
            out_specs["row_cache_ok"] = P()
        fn = self._smap(_local, in_specs=(self.state_specs, self._zone_spec,
                                          self._zone_spec, self._zone_spec),
                        out_specs=out_specs)

        def local_scrub(prot: ProtectedState):
            return fn(prot.state, prot.row, prot.synd, prot.cksums)

        return local_scrub

    def local_scrub(self, prot):
        if "local_scrub" not in self._jit_cache:
            self._jit_cache["local_scrub"] = jax.jit(self.make_local_scrub())
        return self._jit_cache["local_scrub"](prot)

    # -- recovery ------------------------------------------------------------------

    def make_recover_rank(self):
        """Online reconstruction of one lost data-rank's entire row."""
        lo, ax = self.layout, self.data_axis
        mode = self.mode

        def _recover(state, synd, cksums, lost):
            # flatten the live (damaged) state — the row cache is rebuilt,
            # never trusted, across recovery
            row = layout_mod.flatten_row(lo, state)
            rebuilt = parity_mod.reconstruct_row(
                row, self._unpack(synd)[0], lost, ax)
            me = lax.axis_index(ax)
            row_out = jnp.where(me == lost, rebuilt, row)
            out = {"state": layout_mod.unflatten_row(lo, row_out),
                   "row": self._pack(row_out)}
            if mode.has_cksums:
                bad = ck.verify_blocks(row_out, self._unpack(cksums),
                                       lo.block_words)
                any_bad = lax.pmax(jnp.any(bad).astype(jnp.int32), ax)
                out["ok"] = any_bad == 0
            else:
                out["ok"] = jnp.asarray(True)
            return out

        out_specs = {"state": self.state_specs, "ok": P(),
                     "row": self._zone_spec}
        fn = self._smap(_recover,
                        in_specs=(self.state_specs, self._zone_spec,
                                  self._zone_spec, P()),
                        out_specs=out_specs)

        def recover(prot: ProtectedState, lost_rank):
            out = fn(prot.state, prot.synd, prot.cksums,
                     jnp.asarray(lost_rank, jnp.int32))
            return dataclasses.replace(prot, state=out["state"],
                                       row=out["row"]), out["ok"]

        return recover

    def recover_rank(self, prot, lost_rank):
        if "recover" not in self._jit_cache:
            self._jit_cache["recover"] = jax.jit(self.make_recover_rank())
        return self._jit_cache["recover"](prot, lost_rank)

    def make_recover_e(self, lost_ranks):
        """Online reconstruction of e <= r lost data-ranks' rows.

        The erasure set is static (recovery is rare; one compiled
        program per set) so the Vandermonde inverse folds in as exact
        host integers.  Also the losses-with-outstanding-scribble path:
        name the scribbled rank as an extra loss.
        """
        lo, ax = self.layout, self.data_axis
        mode = self.mode
        ranks = tuple(sorted(int(a) for a in lost_ranks))
        e = len(ranks)
        assert len(set(ranks)) == e, (
            f"erasure recovery needs distinct ranks, got {ranks}")
        if e > self.redundancy:
            raise RuntimeError(
                f"syndrome budget exhausted: {e} simultaneous losses "
                f"(ranks {list(ranks)}) exceed redundancy="
                f"{self.redundancy} — a zone solves at most r losses "
                "online (raise ProtectConfig.redundancy, or restore "
                "from checkpoint and re-protect)")

        def _recover(state, synd, cksums):
            # flatten the live (damaged) state — the row cache is rebuilt,
            # never trusted, across recovery
            row = layout_mod.flatten_row(lo, state)
            rebuilt = parity_mod.reconstruct_e(
                row, self._unpack(synd), ranks, ax)
            me = lax.axis_index(ax)
            row_out = row
            for a, row_a in zip(ranks, rebuilt):
                row_out = jnp.where(me == a, row_a, row_out)
            out = {"state": layout_mod.unflatten_row(lo, row_out),
                   "row": self._pack(row_out)}
            if mode.has_cksums:
                bad = ck.verify_blocks(row_out, self._unpack(cksums),
                                       lo.block_words)
                any_bad = lax.pmax(jnp.any(bad).astype(jnp.int32), ax)
                out["ok"] = any_bad == 0
            else:
                out["ok"] = jnp.asarray(True)
            return out

        out_specs = {"state": self.state_specs, "ok": P(),
                     "row": self._zone_spec}
        fn = self._smap(_recover,
                        in_specs=(self.state_specs, self._zone_spec,
                                  self._zone_spec),
                        out_specs=out_specs)

        def recover(prot: ProtectedState):
            out = fn(prot.state, prot.synd, prot.cksums)
            return dataclasses.replace(prot, state=out["state"],
                                       row=out["row"]), out["ok"]

        return recover

    def recover_e(self, prot, lost_ranks):
        ranks = tuple(sorted(int(a) for a in lost_ranks))
        key = ("recover_e", ranks)
        if key not in self._jit_cache:
            self._jit_cache[key] = jax.jit(self.make_recover_e(ranks))
        return self._jit_cache[key](prot)

    def recover_two(self, prot, lost_a, lost_b):
        """Back-compat alias: the e=2 erasure recovery."""
        a, b = sorted((int(lost_a), int(lost_b)))
        assert a != b, "double-loss recovery needs two distinct ranks"
        return self.recover_e(prot, (a, b))

    def make_repair_pages(self, n_pages: int):
        """Targeted scribble repair: fix `n_pages` (rank, page) locations."""
        lo, ax = self.layout, self.data_axis
        mode = self.mode
        bw = lo.block_words
        pages_per_seg = lo.seg_words // bw

        def _repair(state, synd, cksums, bad_rank, bad_page):
            row = layout_mod.flatten_row(lo, state)
            pages = parity_mod.page_view(row, bw)
            me = lax.axis_index(ax)
            mine_bad = (bad_rank == me)                      # (k,)
            contents = pages[bad_page]                       # (k, bw)
            contrib = jnp.where(mine_bad[:, None], 0, contents)
            others = coll.xor_all_reduce(contrib, ax)        # (k, bw)
            # broadcast each bad page's parity (the stack's S_0 plane)
            # from its owner via the XOR trick
            owner = bad_page // pages_per_seg
            local_idx = bad_page % pages_per_seg
            seg_pages = self._unpack(synd)[0].reshape(pages_per_seg, bw)
            par_contrib = jnp.where((owner == me)[:, None],
                                    seg_pages[local_idx], 0)
            par_pages = coll.xor_all_reduce(par_contrib, ax)  # (k, bw)
            fixed = others ^ par_pages
            new_pages = jnp.where(mine_bad[:, None], fixed, contents)
            row_out = pages.at[bad_page].set(new_pages).reshape(-1)
            out = {"state": layout_mod.unflatten_row(lo, row_out),
                   "row": self._pack(row_out)}
            if mode.has_cksums:
                bad = ck.verify_blocks(row_out, self._unpack(cksums), bw)
                any_bad = lax.pmax(jnp.any(bad).astype(jnp.int32), ax)
                out["ok"] = any_bad == 0
            else:
                out["ok"] = jnp.asarray(True)
            return out

        fn = self._smap(_repair,
                        in_specs=(self.state_specs, self._zone_spec,
                                  self._zone_spec, P(), P()),
                        out_specs={"state": self.state_specs, "ok": P(),
                                   "row": self._zone_spec})

        def repair(prot: ProtectedState, bad_rank, bad_page):
            bad_rank = jnp.asarray(bad_rank, jnp.int32).reshape(n_pages)
            bad_page = jnp.asarray(bad_page, jnp.int32).reshape(n_pages)
            out = fn(prot.state, prot.synd, prot.cksums, bad_rank, bad_page)
            return dataclasses.replace(prot, state=out["state"],
                                       row=out["row"]), out["ok"]

        return repair

    def repair_pages(self, prot, bad_rank, bad_page):
        n = int(np.asarray(bad_rank).size)
        key = ("repair", n)
        if key not in self._jit_cache:
            self._jit_cache[key] = jax.jit(self.make_repair_pages(n))
        return self._jit_cache[key](prot, bad_rank, bad_page)

    # -- introspection ---------------------------------------------------------

    def overhead_report(self) -> dict:
        rep = self.layout.overhead_report()
        rep["mode"] = self.mode.value
        rep["group_size"] = self.group_size
        r = self.redundancy if self.mode.has_parity else 0
        rep["redundancy"] = r
        # every syndrome is one seg_words row per rank — same bytes as P —
        # so the stack's storage tax is exactly r x the parity fraction
        rep["syndrome_rows"] = r
        rep["syndrome_bytes_per_rank"] = r * rep["parity_bytes_per_rank"]
        rep["syndrome_fraction"] = r * rep["parity_fraction"]
        rep["syndrome_r_over_p"] = float(r) if r else 0.0
        if self.mode.has_replica:
            rep["protection_fraction"] = 1.0
        else:
            frac = rep["syndrome_fraction"]
            if self.mode.has_cksums:
                frac += rep["checksum_fraction"]
            rep["protection_fraction"] = frac
        return rep
