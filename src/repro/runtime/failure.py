"""Failure injection (Pangolin §4.6).

The paper emulates NVMM media errors with mprotect+SIGSEGV and injects
targeted scribbles.  Here:

  * `inject_rank_loss`   — garbles one data-rank's entire state shard
    (chip/host failure, HBM UE).  The "SIGBUS" analogue is the returned
    FailureEvent the runtime feeds to recovery.
  * `inject_scribble`    — XORs a corruption mask into chosen words of one
    rank's flat row (SDC / wild-store analogue), invisible until a checksum
    verification catches it.
  * `inject_canary_smash`— simulates a kernel overrun into a staged
    micro-buffer's guard page (caught at commit, before state is touched).

All injections are jitted shard_map ops against the protected state so they
work at any mesh size.
"""
from __future__ import annotations

import dataclasses
import functools
import zlib
from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from repro.core import layout as layout_mod
from repro.core import microbuffer
from repro.core.txn import ProtectedState, Protector


@dataclasses.dataclass
class FailureEvent:
    kind: str                  # "rank_loss" | "multi_loss" | "scribble"
                               # | "canary"
    lost_rank: Optional[int] = None
    locations: Optional[list] = None   # [(rank, page)] for scribbles
    lost_ranks: Optional[list] = None  # every lost rank for multi_loss


def inject_rank_loss(protector: Protector, prot: ProtectedState,
                     rank: int) -> tuple:
    """Overwrite one data-rank's shards with garbage; returns (prot, event)."""
    lo, ax = protector.layout, protector.data_axis

    def _garble(state):
        row = layout_mod.flatten_row(lo, state)
        me = lax.axis_index(ax)
        garbage = row ^ jnp.uint32(0xA5A5A5A5)
        out = jnp.where(me == rank, garbage, row)
        return layout_mod.unflatten_row(lo, out)

    fn = jax.jit(shard_map(_garble, mesh=protector.mesh,
                           in_specs=(protector.state_specs,),
                           out_specs=protector.state_specs,
                           check_vma=False))
    bad_state = fn(prot.state)
    return (dataclasses.replace(prot, state=bad_state),
            FailureEvent("rank_loss", lost_rank=rank))


def inject_multi_rank_loss(protector: Protector, prot: ProtectedState,
                           ranks) -> tuple:
    """Garble e data-ranks' shards at once (overlapping failures).

    The pod-scale scenario an (e-1)-syndrome zone cannot survive: all e
    rows gone before any could be rebuilt.  Returns (prot, event) with a
    "multi_loss" event carrying every lost rank.
    """
    dead = sorted({int(r) for r in ranks})
    assert len(dead) == len(list(ranks)) and len(dead) >= 2, (
        f"multi loss needs >= 2 distinct ranks, got {list(ranks)}")
    lo, ax = protector.layout, protector.data_axis

    def _garble(state):
        row = layout_mod.flatten_row(lo, state)
        me = lax.axis_index(ax)
        garbage = row ^ jnp.uint32(0xA5A5A5A5)
        lost = functools.reduce(jnp.logical_or, [me == a for a in dead])
        out = jnp.where(lost, garbage, row)
        return layout_mod.unflatten_row(lo, out)

    fn = jax.jit(shard_map(_garble, mesh=protector.mesh,
                           in_specs=(protector.state_specs,),
                           out_specs=protector.state_specs,
                           check_vma=False))
    bad_state = fn(prot.state)
    return (dataclasses.replace(prot, state=bad_state),
            FailureEvent("multi_loss", lost_ranks=dead))


def inject_double_rank_loss(protector: Protector, prot: ProtectedState,
                            ranks) -> tuple:
    """Back-compat alias: the e=2 multi-rank loss."""
    a, b = (int(r) for r in ranks)
    return inject_multi_rank_loss(protector, prot, (a, b))


def inject_scribble(protector: Protector, prot: ProtectedState,
                    rank: int, word_offsets: Sequence[int],
                    xor_mask: int = 0x00010000) -> tuple:
    """Flip bits at given word offsets of one rank's row (silent until scrub)."""
    lo, ax = protector.layout, protector.data_axis
    offsets = jnp.asarray(list(word_offsets), jnp.int32)

    def _scribble(state):
        row = layout_mod.flatten_row(lo, state)
        me = lax.axis_index(ax)
        vals = row[offsets] ^ jnp.uint32(xor_mask)
        scribbled = row.at[offsets].set(vals)
        out = jnp.where(me == rank, scribbled, row)
        return layout_mod.unflatten_row(lo, out)

    fn = jax.jit(shard_map(_scribble, mesh=protector.mesh,
                           in_specs=(protector.state_specs,),
                           out_specs=protector.state_specs,
                           check_vma=False))
    bad_state = fn(prot.state)
    pages = sorted({int(o) // lo.block_words for o in word_offsets})
    return (dataclasses.replace(prot, state=bad_state),
            FailureEvent("scribble", locations=[(rank, p) for p in pages]))


# ---------------------------------------------------------------------------
# Seeded deterministic injectors (chaos campaign).
#
# The raw injectors above take their victims from the caller; the chaos
# runner needs the *same* victims on every run of a scenario so the
# recovered end state can be diffed bit-for-bit against a fault-free
# golden run.  Each seeded form derives its choices from
# np.random.default_rng seeded with (seed, crc32(kind)) — crc32, not
# hash(), because hash() is salted per process and would break replay.
# ---------------------------------------------------------------------------


def _rng(seed: int, kind: str) -> np.random.Generator:
    return np.random.default_rng((int(seed), zlib.crc32(kind.encode())))


def seeded_rank_loss(protector: Protector, prot: ProtectedState,
                     seed: int, rank: Optional[int] = None) -> tuple:
    """Deterministic rank loss: victim drawn from (seed, "rank_loss")."""
    if rank is None:
        rank = int(_rng(seed, "rank_loss").integers(protector.group_size))
    return inject_rank_loss(protector, prot, rank)


def seeded_multi_rank_loss(protector: Protector, prot: ProtectedState,
                           seed: int, e: int = 2,
                           ranks: Optional[Sequence[int]] = None) -> tuple:
    """Deterministic e-rank loss: victims drawn without replacement."""
    if ranks is None:
        ranks = _rng(seed, "multi_loss").choice(
            protector.group_size, size=e, replace=False)
    return inject_multi_rank_loss(protector, prot,
                                  [int(r) for r in ranks])


def scribble_plan(protector: Protector, seed: int,
                  n_words: int = 4, rank: Optional[int] = None) -> tuple:
    """Deterministic scribble parameters: (rank, word_offsets, xor_mask).

    Offsets land in the rank's flat row; the mask is any nonzero u32 so
    the flip is guaranteed visible to the checksums.  Exposed separately
    from `seeded_scribble` so tests and the chaos runner can predict the
    victim pages without touching state.
    """
    g = _rng(seed, "scribble")
    if rank is None:
        rank = int(g.integers(protector.group_size))
    # draw from the payload region only — a scribble into row padding
    # vanishes on unflatten and would test nothing
    row_words = protector.layout.payload_words
    offsets = sorted(int(o) for o in g.choice(
        row_words, size=min(n_words, row_words), replace=False))
    mask = int(g.integers(1, 1 << 32))
    return rank, offsets, mask


def seeded_scribble(protector: Protector, prot: ProtectedState,
                    seed: int, n_words: int = 4,
                    rank: Optional[int] = None) -> tuple:
    """Deterministic scribble: victims from `scribble_plan(seed)`."""
    rank, offsets, mask = scribble_plan(protector, seed,
                                        n_words=n_words, rank=rank)
    return inject_scribble(protector, prot, rank, offsets, xor_mask=mask)


def smashed_canary_buffer(n_words: int = 4096) -> jax.Array:
    """A staged micro-buffer whose guard page was overrun (for tests)."""
    buf = microbuffer.guard(jnp.zeros((n_words,), jnp.uint32))
    # simulate an out-of-bounds kernel write running past the payload
    return buf.at[n_words + 3].set(jnp.uint32(0x12345678))
