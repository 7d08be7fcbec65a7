"""Each cell driven end to end on the CPU at a tiny size, sound and with
its timed path broken underneath: a sound run reads correct, and every
fault the cell can have reads not correct.

The faults are planted in the program's train step as the window calls
it: a step that returns its state unchanged; a step that sees only the
first half of each batch and takes the mean over it; and, on a zone of
several chips, a step whose gradients never leave the chip, which reads
on the first chip as a step over that chip's rows alone.
"""
import json

import pytest

import harness
import tiny


def _faults(workload: dict) -> tuple:
    """The faults a cell can have: every cell's step can stand still or
    drop half its batch; only a zone of several chips exchanges."""
    base = ("stuck", "half_batch")
    return base + ("no_exchange",) if workload["chips"] > 1 else base


CELLS = {w["name"]: _faults(w) for w in json.loads(
    (harness.ROOT / "BENCHMARK.json").read_text())["workloads"]}
CASES = [(w, None) for w in CELLS] + [(w, f) for w, fs in CELLS.items()
                                      for f in fs]


def _plant(trainer, fault: str, batch: int, chips: int) -> None:
    import jax
    import jax.numpy as jnp
    step = trainer._train_step
    if fault == "stuck":
        def broken(state, b):
            return jax.tree.map(jnp.copy, state), step(state, b)[1]
    else:
        rows = batch // 2 if fault == "half_batch" else batch // chips

        def broken(state, b):
            return step(state, {"tokens": b["tokens"][:rows]})
    trainer._train_step = broken


def _run(workload: str, fault):
    import jax
    cell = tiny.cell(workload)
    drv = harness.kind_module("train")
    if fault is not None:
        build = drv.build

        def planted(*a, **k):
            trainer, init = build(*a, **k)
            _plant(trainer, fault, cell.traffic["global_batch"], cell.chips)
            return trainer, init
        drv.build = planted
    return drv.run(cell, jax.devices()[:cell.chips], 7, 0.3, False, 0.0)


@pytest.mark.parametrize("workload,fault", CASES)
def test_cell_on_cpu(workload, fault):
    result, checks = _run(workload, fault)
    if fault is None:
        assert result["correct"], checks
        assert result["failed"] == 0
        names = {m["name"] for m in harness.load_cell(workload).end_to_end}
        assert set(result["metrics"]) == names
    else:
        assert not result["correct"], (fault, checks)
