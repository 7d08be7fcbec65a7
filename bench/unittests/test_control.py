"""The control at a size the CPU holds: the program with its own
lower-precision path switched on (compute dtype fp8 e4m3, one step below
the configuration's bfloat16) reads not correct."""
import dataclasses

import pytest

import tiny


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_fp8_program_fails(seed):
    cell = tiny.cell("train.glm4-9b.ml")
    cell = dataclasses.replace(
        cell, config=dict(cell.config, compute_dtype="float8_e4m3fn"))
    result, checks = tiny.run(cell, seed=seed)
    assert not result["correct"], checks
