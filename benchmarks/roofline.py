"""Beyond-paper — roofline table from the compiled dry-run artifacts,
plus the commit-sweep achieved-bytes/s measurement (ISSUE 6).

Part 1 reads the dry-run JSON (produced by `python -m
repro.launch.dryrun`) and emits the three-term roofline per (arch x
workload x mesh): compute / memory / collective seconds, the binding
term, and the useful-FLOP ratio (6ND / HLO FLOPs).  This is the
§Roofline table of EXPERIMENTS.md.

Part 2 measures the commit sweep itself against the memory roofline:
the streamed single-dispatch syndrome pipeline
(`ops.fused_commit_s_stream` — all r weighted planes, checksums and the
row digest from ONE pass over the dirty row) against the flat baseline
cadence it replaced (delta+checksum sweep, then the stacked weighting
pass re-reading the delta, then the digest combine — three dispatches,
two extra delta-row trips).  Both paths are checked bit-identical, then
compared on

  * XLA compiled bytes accessed (deterministic — the streamed program
    must touch strictly fewer bytes than the flat cadence), and the
    bandwidth-efficiency fraction `useful_frac` = useful bytes / bytes
    accessed (useful bytes = the roofline minimum: read old+new once,
    write the r syndrome planes once) — the deterministic form of
    "fraction of the streamed bytes/s that is useful", which is what
    the gate compares (the streamed path is strictly higher: it never
    re-reads the dirty delta, whatever the redundancy);
  * interleaved wall time -> achieved useful bytes/s as a fraction of
    the device's `launch.hlo_analysis.PEAKS` HBM bandwidth, None on a
    device with no published peaks such as the CPU (EXPERIMENTS.md
    §Roofline; wall cells gate pathology-only, per the standing rule —
    at the 1 MB point the identical GF(2^32) clmul work dominates both
    paths, so wall margins sit inside ambient noise on a shared box).

On CPU the ops dispatch routes to the jnp oracles, so the A/B measures
the dispatch/fusion structure the streaming refactor targets; on TPU
the identical harness routes to the Pallas kernels.  Recorded as
BENCH_commit.json §roofline and gated by scripts/bench_gate.py
(record-presence, streamed-bytes <= flat, streamed useful_frac above
flat at the 1 MB pool, wall pathology).
"""
from __future__ import annotations

import json
import os
import time

from benchmarks import common

# the commit-sweep A/B runs at the full stack height the streamed kernel
# amortizes (r=3: P, Q and one higher Vandermonde row from one row read)
SWEEP_R = 3
SWEEP_BLOCK_WORDS = 1024            # 4 KB pages (paper default)
SWEEP_CHUNK_BLOCKS = 16             # 64 KB double-buffered chunks
SWEEP_SIZES = [256 * 1024, 1024 * 1024]

DEFAULT_PATHS = [
    os.path.join(os.path.dirname(__file__), "..", "scratch",
                 "dryrun_v2.json"),
    os.path.join(os.path.dirname(__file__), "..", "scratch",
                 "dryrun_all.json"),
    "dryrun_results.json",
]


def load_records(path: str | None = None) -> list:
    paths = [path] if path else DEFAULT_PATHS
    for p in paths:
        if p and os.path.exists(p):
            with open(p) as f:
                return json.load(f)
    return []


def _xla_bytes(jitted, *args) -> float:
    cost = jitted.lower(*args).compile().cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    return float(cost.get("bytes accessed", 0.0))


def commit_sweep_rows(quick: bool = False) -> list:
    """Streamed vs flat commit sweep against the HBM roofline."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import checksum as cksum
    from repro.core import gf
    from repro.kernels import ops
    from repro.launch.hlo_analysis import PEAKS

    kind = jax.devices()[0].device_kind
    hbm_bw = PEAKS[kind].hbm_bw if kind in PEAKS else None

    r, bw = SWEEP_R, SWEEP_BLOCK_WORDS
    reps = 30 if quick else 60
    coeffs = jnp.asarray([gf.pow_g_int(k * 3) for k in range(r)],
                         jnp.uint32)
    rows = []
    for size in SWEEP_SIZES:
        n = size // 4 // bw
        cb = max(1, min(SWEEP_CHUNK_BLOCKS, n))
        rng = np.random.default_rng(size)
        old = jnp.asarray(rng.integers(0, 2**32, (n, bw), dtype=np.uint32))
        new = jnp.asarray(rng.integers(0, 2**32, (n, bw), dtype=np.uint32))

        # flat baseline: the pre-streaming cadence — the delta+checksum
        # sweep materializes the delta, the stacked weighting pass
        # re-reads it, and the digest combines separately (three
        # dispatches, two extra delta-row trips)
        flat_commit = jax.jit(lambda o, nw: ops.fused_commit(o, nw))
        flat_scale = jax.jit(lambda d: ops.syndrome_scale(d, coeffs))
        flat_digest = jax.jit(lambda c: cksum.combine(c, bw))

        def run_flat():
            d, c = flat_commit(old, new)
            return flat_scale(d), c, flat_digest(c)

        # streamed pipeline: one dispatch emits every weighted plane,
        # the checksum terms AND the loop-carried digest from a single
        # pass over (old, new)
        stream = jax.jit(lambda o, nw: ops.fused_commit_s_stream(
            o, nw, coeffs, chunk_blocks=cb))

        def run_stream():
            return stream(old, new)

        # bit-identity before timing: both paths land the same planes,
        # checksums and digest
        sd_f, ck_f, dig_f = run_flat()
        sd_s, ck_s, dig_s = run_stream()
        np.testing.assert_array_equal(np.asarray(sd_f), np.asarray(sd_s))
        np.testing.assert_array_equal(np.asarray(ck_f), np.asarray(ck_s))
        np.testing.assert_array_equal(np.asarray(dig_f), np.asarray(dig_s))

        fns = {"flat": run_flat, "stream": run_stream}
        for fn in fns.values():
            for _ in range(3):
                jax.block_until_ready(fn())
        times = {name: [] for name in fns}
        for _ in range(reps):
            for name, fn in fns.items():
                t0 = time.perf_counter()
                jax.block_until_ready(fn())
                times[name].append(time.perf_counter() - t0)

        useful = (2 + r) * n * bw * 4     # read old+new, write r planes
        xla = {"flat": (_xla_bytes(flat_commit, old, new)
                        + _xla_bytes(flat_scale, sd_f[0])
                        + _xla_bytes(flat_digest, ck_f)),
               "stream": _xla_bytes(stream, old, new)}
        for name in fns:
            # min over interleaved reps: the noise-robust estimate of
            # the program's intrinsic time (ambient load only ever ADDS
            # time, so the minimum is the cleanest sample — medians on
            # this box still swing past the structural margin)
            wall = float(np.min(times[name]))
            achieved = useful / wall
            rows.append({
                "size_B": size, "path": name, "r": r,
                "wall_us": round(wall * 1e6, 1),
                "xla_MB": round(xla[name] / 2**20, 2),
                "useful_MB": round(useful / 2**20, 2),
                "useful_frac": round(useful / xla[name], 4),
                "achieved_GBps": round(achieved / 1e9, 2),
                "frac_of_peak": (round(achieved / hbm_bw, 5) if hbm_bw
                                 else None),
            })
    return rows


def run(quick: bool = False, path: str | None = None) -> dict:
    sweep = commit_sweep_rows(quick=quick)
    common.print_table(
        "commit-sweep roofline (streamed vs flat; interleaved reps; "
        "frac_of_peak = useful bytes/s over the device's HBM peak)",
        sweep, ["size_B", "path", "r", "wall_us", "xla_MB", "useful_MB",
                "useful_frac", "achieved_GBps", "frac_of_peak"])
    recs = load_records(path)
    if not recs:
        print("roofline: no dry-run results found — run "
              "`PYTHONPATH=src python -m repro.launch.dryrun` first")
        common.save_result("roofline", {"commit_sweep": sweep})
        return {"rows": [], "commit_sweep": sweep}
    rows = []
    for r in recs:
        if r.get("status") != "ok":
            continue
        ro = r["roofline"]
        dom = max(ro["compute_s"], ro["memory_s"], ro["collective_s"])
        rows.append({
            "arch": r["arch"], "workload": r["workload"], "mesh": r["mesh"],
            "compute_ms": round(ro["compute_s"] * 1e3, 2),
            "memory_ms": round(ro["memory_s"] * 1e3, 2),
            "coll_ms": round(ro["collective_s"] * 1e3, 2),
            "bound": ro["bound"],
            "roofline_frac": round(ro["compute_s"] / dom, 3) if dom else 0.0,
            "useful_ratio": round(ro.get("useful_ratio", 0.0), 3),
            "GiB_per_dev": round(
                r["memory"]["total_bytes_per_device"] / 2**30, 2),
        })
    rows.sort(key=lambda x: (x["workload"], x["arch"], x["mesh"]))
    common.print_table("roofline terms per cell (from compiled dry-run)",
                       rows, ["arch", "workload", "mesh", "compute_ms",
                              "memory_ms", "coll_ms", "bound",
                              "roofline_frac", "useful_ratio",
                              "GiB_per_dev"])
    common.save_result("roofline", {"rows": rows, "commit_sweep": sweep})
    return {"rows": rows, "commit_sweep": sweep}


if __name__ == "__main__":
    try:
        from benchmarks import _bootstrap  # noqa: F401
    except ImportError:
        import _bootstrap                  # noqa: F401
    run()
