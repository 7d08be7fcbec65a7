"""Pangolin-JAX chip benchmark: one run of one cell.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs the cell named in BENCHMARK.json on the chips it asks for and prints,
as the last line of stdout, one JSON object: `correct`, `attempted`,
`failed`, `metrics` (the cell's end-to-end metrics with --trace 0, its
per-layer metrics with --trace 1), `device`, with --trace 1 `breakdown`,
and last `checks`, every number the correctness comparison read beside
its limit.  Exits 3, printing no result, when JAX finds no accelerator or
fewer chips than the cell asks for; it never falls back to the CPU.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)

    harness.add_paths()
    cell = harness.load_cell(args.workload)
    try:
        devices = harness.chips_or_fail(cell.chips)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    harness.enable_compile_cache()
    drv = harness.kind_module(cell.traffic["kind"])
    result, checks = drv.run(cell, devices, args.seed, args.seconds,
                             bool(args.trace), T_PROCESS)
    harness.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
