"""Paper §4.6 — error injection, detection, and online correction.

Measures what the paper demonstrates qualitatively, plus latencies:

  * media error (rank loss): inject -> freeze -> rebuild row -> verify,
    across state sizes; reports recovery wall time and exactness,
  * scribble: inject targeted bit flips -> scrub detect -> page repair,
  * canary: a smashed staging buffer must abort the transaction,
  * detection completeness: every injected corruption is found (no false
    negatives) and clean pools scrub clean (no false positives),
  * double loss (beyond paper, redundancy=2): TWO simultaneous rank
    losses solved from P + the GF(2^32) Q syndrome — reconstruction wall
    time, exactness, and the Q storage tax (must stay <= 2x P; it is
    exactly 1x — gated by scripts/bench_gate.py via BENCH_commit.json),
  * r-sweep (generalized Reed-Solomon): for every stack height r in
    1..4, e = r simultaneous rank losses on a G=8 zone solve through the
    e x e Vandermonde inverse — reconstruction wall time, exactness, and
    the stack storage ratio syndrome_r_over_p (exactly r by
    construction; gated <= r in BENCH_commit.json §rs).

Everything routes through the public `Pool` facade: `pool.recover`
dispatches every fault kind (and flushes any open window first), and
`pool.scrub` is the detection path.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from benchmarks import common
from repro.configs.base import ProtectConfig
from repro.core import microbuffer
from repro.pool import Fault, Pool
from repro.runtime import failure


def run(quick: bool = False) -> dict:
    mesh = common.get_mesh()
    sizes = [64 * 1024, 1024 * 1024] if quick else \
        [64 * 1024, 1024 * 1024, 16 * 1024 * 1024]
    rows = []
    for size in sizes:
        state, specs = common.state_of_bytes(size, mesh)
        pool = Pool.open(state, specs, mesh=mesh,
                         config=ProtectConfig(mode="mlpc",
                                              block_words=1024),
                         donate=False)
        w0 = np.asarray(pool.state["w"]).copy()

        # media error: lose rank 2 entirely
        pool.prot, event = failure.inject_rank_loss(pool.protector,
                                                    pool.prot, rank=2)
        t0 = time.perf_counter()
        rep = pool.recover(Fault.from_event(event))
        jax.block_until_ready(jax.tree.leaves(pool.state)[0])
        t_rank = time.perf_counter() - t0
        exact = np.array_equal(np.asarray(pool.state["w"]), w0)

        # scribble: flip bits in 3 words, detect by scrub, repair pages
        pool.prot, ev2 = failure.inject_scribble(
            pool.protector, pool.prot, rank=1,
            word_offsets=[7, 2048, 100000])
        t0 = time.perf_counter()
        report = pool.scrub()
        jax.block_until_ready(jax.tree.leaves(pool.state)[0])
        t_scrub = time.perf_counter() - t0
        exact2 = np.array_equal(np.asarray(pool.state["w"]), w0)

        rows.append({
            "state_B": size,
            "rank_recover_ms": round(t_rank * 1e3, 2),
            "rank_exact": exact, "rank_verified": rep.verified,
            "scrub_repair_ms": round(t_scrub * 1e3, 2),
            "scribble_found": len(report.bad_locations),
            "scribble_exact": exact2,
            "repair_verified": bool(report.repair_ok),
        })

    common.print_table("error injection & online recovery", rows,
                       ["state_B", "rank_recover_ms", "rank_exact",
                        "scrub_repair_ms", "scribble_found",
                        "scribble_exact", "repair_verified"])
    assert all(r["rank_exact"] and r["scribble_exact"] for r in rows)

    # canary: overrun staging buffer must be caught before commit
    smashed = failure.smashed_canary_buffer(4096)
    caught = not bool(microbuffer.check(smashed))
    clean = bool(microbuffer.check(microbuffer.guard(
        jax.numpy.zeros((4096,), jax.numpy.uint32))))
    print(f"canary: overrun caught={caught}, clean buffer passes={clean}")
    assert caught and clean

    # false-positive check: a clean pool scrubs clean
    state, specs = common.state_of_bytes(256 * 1024, mesh)
    pool = Pool.open(state, specs, mesh=mesh,
                     config=ProtectConfig(mode="mlpc", block_words=1024),
                     donate=False)
    rep = pool.scrub()
    assert not rep.bad_locations and bool(rep.parity_ok)
    print("clean-pool scrub: no false positives")

    # dual parity: two simultaneous rank losses, P+Q Vandermonde solve
    double_rows = []
    for size in sizes:
        state, specs = common.state_of_bytes(size, mesh)
        pool2 = Pool.open(state, specs, mesh=mesh,
                          config=ProtectConfig(mode="mlpc", redundancy=2,
                                               block_words=1024),
                          donate=False)
        w0 = np.asarray(pool2.state["w"]).copy()
        pool2.prot, event = failure.inject_double_rank_loss(
            pool2.protector, pool2.prot, ranks=(1, 3))
        t0 = time.perf_counter()
        rep = pool2.recover(Fault.double_loss(*event.lost_ranks))
        jax.block_until_ready(jax.tree.leaves(pool2.state)[0])
        t_double = time.perf_counter() - t0
        over = pool2.overhead_report()
        double_rows.append({
            "state_B": size,
            "double_recover_ms": round(t_double * 1e3, 2),
            "double_exact": np.array_equal(np.asarray(pool2.state["w"]),
                                           w0),
            "double_verified": rep.verified,
            # syndrome bytes over ONE parity row = r; the legacy gate
            # key reads the extra (beyond-P) rows, historically <= 2
            "q_over_p": round(over["syndrome_bytes_per_rank"]
                              / max(over["parity_bytes_per_rank"], 1)
                              - 1.0, 4),
        })
    common.print_table("double loss (redundancy=2, P+Q)", double_rows,
                       ["state_B", "double_recover_ms", "double_exact",
                        "double_verified", "q_over_p"])
    assert all(r["double_exact"] and r["double_verified"]
               for r in double_rows)

    # generalized Reed-Solomon r-sweep: e = r losses at every stack
    # height on a pure 8-rank zone (r <= 4 needs G - 1 >= 4 survivable)
    mesh8 = common.get_mesh(8, 1)
    rs_rows = []
    rs_size = 256 * 1024
    for r in (1, 2, 3, 4):
        state, specs = common.state_of_bytes(rs_size, mesh8)
        pool_r = Pool.open(state, specs, mesh=mesh8,
                           config=ProtectConfig(mode="mlpc", redundancy=r,
                                                block_words=1024),
                           donate=False)
        w0 = np.asarray(pool_r.state["w"]).copy()
        dead = tuple(range(1, 1 + r))
        if r == 1:
            pool_r.prot, event = failure.inject_rank_loss(
                pool_r.protector, pool_r.prot, rank=dead[0])
        else:
            pool_r.prot, event = failure.inject_multi_rank_loss(
                pool_r.protector, pool_r.prot, dead)
        t0 = time.perf_counter()
        rep = pool_r.recover(Fault.from_event(event))
        jax.block_until_ready(jax.tree.leaves(pool_r.state)[0])
        t_rec = time.perf_counter() - t0
        over = pool_r.overhead_report()
        rs_rows.append({
            "r": r, "e": r, "state_B": rs_size,
            "recover_ms": round(t_rec * 1e3, 2),
            "exact": np.array_equal(np.asarray(pool_r.state["w"]), w0),
            "verified": rep.verified,
            "syndrome_r_over_p": round(
                over["syndrome_bytes_per_rank"]
                / max(over["parity_bytes_per_rank"], 1), 4),
            "storage_overhead_pct": round(
                100 * over["syndrome_fraction"], 3),
        })
    common.print_table("r-sweep: e = r losses per stack height (G=8)",
                       rs_rows,
                       ["r", "e", "state_B", "recover_ms", "exact",
                        "verified", "syndrome_r_over_p",
                        "storage_overhead_pct"])
    assert all(row["exact"] and row["verified"] for row in rs_rows)

    payload = {"rows": rows, "canary_caught": caught,
               "double_loss": double_rows, "rs": rs_rows}
    common.save_result("recovery", payload)
    return payload


if __name__ == "__main__":
    run()
