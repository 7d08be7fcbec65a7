"""Readings that the correctness limits are set from; not part of a run.

    python bench/calibrate.py --workload <name> --seeds 1,2,... \
        [--fault-seeds 1,2,3]

For every seed, in one process on the cell's chips: the program's first
steps through `Trainer.run` against the plain reference (the lower
readings).  For every fault seed, the reference put in the program's
place under each fault the cell can have, against the sound reference:

  control      the program's own lower-precision path: its compute dtype
               set to fp8 (e4m3), the precision below the configuration's
               bfloat16;
  half_batch   the reference over the first half of each batch only, the
               mean over it;
  no_exchange  (more than one chip) the reference over the first chip's
               rows only, as a step whose gradients never leave their chip
               would read there.

A step that leaves the state unchanged reads 1 on update_gap by
definition and needs no run.  One JSON line per reading; the last line
holds the largest sound reading and the smallest reading of each fault.
"""
import argparse
import dataclasses
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--fault-seeds", default="")
    args = ap.parse_args(argv)
    harness.add_paths()
    cell = harness.load_cell(args.workload)
    try:
        devices = harness.chips_or_fail(cell.chips)
    except harness.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 3
    harness.enable_compile_cache()
    return calibrate(cell, devices, [int(s) for s in args.seeds.split(",")],
                     [int(s) for s in args.fault_seeds.split(",") if s])


def reference_faults(cell, chips: int) -> dict:
    b = cell.traffic["global_batch"]
    out = {"half_batch": slice(0, b // 2)}
    if chips > 1:
        out["no_exchange"] = slice(0, b // chips)
    return out


def control_cell(cell):
    """The cell with the program's compute dtype one step down."""
    return dataclasses.replace(
        cell, config=dict(cell.config, compute_dtype="float8_e4m3fn"))


def program_readings(train, state: dict, cell, devices, seed: int) -> dict:
    """The program's first steps on `seed`, one Trainer per cell reused
    across seeds; its state is dropped afterwards."""
    key = (cell.name, cell.config["compute_dtype"])
    if key not in state:
        state[key] = train.build(cell, devices, seed)
    trainer, init = state[key]
    train.start(trainer, init, cell, seed)
    out = train.first_steps(trainer, cell, seed)
    trainer.prot = None                 # the reference needs the memory
    gc.collect()
    return out


def calibrate(cell, devices, seeds, fault_seeds) -> int:
    import check
    train = harness.kind_module(cell.traffic["kind"])
    trainers: dict = {}
    worst, least = {}, {}

    def note(seed, name, got, ref):
        g = check.gaps(got, ref)
        if name == "program":
            for k, v in g.items():
                worst[k] = max(worst.get(k, 0.0), v)
        else:
            for k, v in g.items():
                least.setdefault(name, {})
                least[name][k] = min(least[name].get(k, float("inf")), v)
        print(json.dumps({"seed": seed, "reading": name, **g,
                          "worst_leaves": check.worst_leaves(got, ref)}),
              flush=True)

    for seed in seeds:
        t0 = time.perf_counter()
        prog = program_readings(train, trainers, cell, devices, seed)
        ref = train.reference(cell, seed)
        note(seed, "program", prog, ref)
        print(json.dumps({"seed": seed, "left_out": check.left_out(ref),
                          "seconds": time.perf_counter() - t0}), flush=True)
        if seed not in fault_seeds:
            continue
        note(seed, "control", program_readings(
            train, trainers, control_cell(cell), devices, seed), ref)
        for name, rows in reference_faults(cell, len(devices)).items():
            note(seed, name, train.reference(cell, seed, rows=rows), ref)
    print(json.dumps({"largest_sound": worst, "smallest_fault": least}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
