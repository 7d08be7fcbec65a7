"""Paper Fig. 4 + §3.5 — scalability of concurrent parity updates and the
hybrid small/large threshold.

The paper's threads are SPMD ranks here: a G-rank zone commits G updates
concurrently in one SPMD program (every rank is a committer — the "multi-
threaded random overwrite" workload).  Two axes:

  * zone width G (1..8 ranks) x update size — throughput of concurrent
    commits (Fig. 4's thread axis),
  * dirty fraction sweep at fixed G — the patch path (incremental parity,
    'atomic XOR' analog) vs the bulk path (full rebuild, 'column lock'
    analog), locating the crossover the paper puts at 512 B.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmarks import common
from repro.configs.base import ProtectConfig
from repro.core import layout as layout_mod
from repro.pool import Pool


def concurrent_commits(quick: bool) -> list:
    rows = []
    sizes = [4096, 64 * 1024] if quick else [4096, 64 * 1024, 1024 * 1024]
    for g in (2, 4, 8):
        mesh = common.get_mesh(g, 1)
        for size in sizes:
            state, specs = common.state_of_bytes(size * g, mesh)
            pool = Pool.open(state, specs, mesh=mesh,
                             config=ProtectConfig(mode="mlpc",
                                                  block_words=64),
                             donate=False)
            prot = pool.prot
            commit = jax.jit(pool.protector.make_commit())
            new_state = jax.tree.map(lambda x: x * 1.01, state)
            t = common.timeit(commit, prot, new_state,
                              rng_key=jax.random.PRNGKey(0),
                              reps=(5 if quick else 12))
            rows.append({
                "G": g, "update_B_per_rank": size,
                "commit_us": round(t["median_s"] * 1e6, 1),
                "zone_MBps": round(size * g / t["median_s"] / 1e6, 1),
            })
    common.print_table("concurrent committers (G ranks, one zone)", rows,
                       ["G", "update_B_per_rank", "commit_us", "zone_MBps"])
    return rows


def hybrid_sweep(quick: bool) -> list:
    """Dirty-fraction sweep: patch path vs bulk path latency.

    Both paths pay the O(state) row flatten; the differential is in the
    parity traffic — k pages XOR-all-reduced vs a full-row reduce-scatter —
    so the state must be large enough for that traffic to show over
    dispatch noise.
    """
    mesh = common.get_mesh()
    size = 4 * 1024 * 1024 if quick else 32 * 1024 * 1024
    state, specs = common.state_of_bytes(size, mesh)
    pool = Pool.open(state, specs, mesh=mesh,
                     config=ProtectConfig(mode="mlpc", block_words=1024),
                     donate=False)
    p = pool.protector
    prot = pool.prot
    n_pages = p.layout.n_blocks
    rows = []
    fracs = [0.004, 0.02, 0.1, 0.5, 1.0]
    for frac in fracs:
        k = max(1, int(frac * n_pages))
        dirty = list(range(k))
        # force patch path
        p.hybrid_threshold = 1.1
        commit_patch = jax.jit(p.make_commit(dirty_pages=dirty))
        # force bulk path
        p.hybrid_threshold = 0.0
        commit_bulk = jax.jit(p.make_commit(dirty_pages=dirty))
        new_state = jax.tree.map(lambda x: x * 1.01, state)
        tp = common.timeit(commit_patch, prot, new_state,
                           rng_key=jax.random.PRNGKey(0),
                           reps=(8 if quick else 15))
        tb = common.timeit(commit_bulk, prot, new_state,
                           rng_key=jax.random.PRNGKey(0),
                           reps=(8 if quick else 15))
        rows.append({
            "dirty_frac": frac, "dirty_pages": k,
            "patch_us": round(tp["median_s"] * 1e6, 1),
            "bulk_us": round(tb["median_s"] * 1e6, 1),
            "patch_wins": bool(tp["median_s"] < tb["median_s"]),
        })
    common.print_table("hybrid parity: patch vs bulk by dirty fraction",
                       rows, ["dirty_frac", "dirty_pages", "patch_us",
                              "bulk_us", "patch_wins"])
    return rows


def run(quick: bool = False) -> dict:
    rows_c = concurrent_commits(quick)
    rows_h = hybrid_sweep(quick)
    # reproduction target: a crossover exists — the patch path wins at small
    # dirty fractions, the bulk path at (or near) full-state updates
    assert rows_h[0]["patch_wins"], "patch path must win for tiny updates"
    assert not rows_h[-1]["patch_wins"], \
        "bulk path must win for full-state updates"
    payload = {"concurrent": rows_c, "hybrid": rows_h}
    common.save_result("scalability", payload)
    return payload


if __name__ == "__main__":
    run()
