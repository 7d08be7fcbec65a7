"""The control at a size the CPU holds: the program with its own
lower-precision path switched on (compute dtype fp8 e4m3, one step below
the configuration's bfloat16) reads not correct."""
import dataclasses
import json

import pytest

import harness
import tiny

WORKLOADS = [w["name"] for w in json.loads(
    (harness.ROOT / "BENCHMARK.json").read_text())["workloads"]]


def _fp8(workload: str):
    cell = tiny.cell(workload)
    return dataclasses.replace(
        cell, config=dict(cell.config, compute_dtype="float8_e4m3fn"))


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_fp8_program_fails(seed):
    result, checks = tiny.run(_fp8("train.glm4-9b.ml"), seed=seed)
    assert not result["correct"], checks


@pytest.mark.parametrize("workload", WORKLOADS)
def test_fp8_program_fails_in_each_cell(workload):
    result, checks = tiny.run(_fp8(workload), seed=4)
    assert not result["correct"], checks
