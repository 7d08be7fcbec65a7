"""The yardstick's arithmetic: operation and byte counts, the peaks table,
the comparison, and the benchmark's files."""
import json
import math
import pathlib

import pytest

import check
import flops
import harness
import weights

ROOT = pathlib.Path(__file__).resolve().parents[2]


def config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


def test_glm4_operations_per_token():
    cfg = config("glm4-9b")
    per_layer = 4096 * 4096 * 2 + 2 * 4096 * 256 + 3 * 4096 * 13696
    assert flops.matmul_params(cfg) == 2 * per_layer + 4096 * 18944
    want = 6 * (2 * per_layer + 4096 * 18944) + 6 * 2 * 32 * 128 * 513
    assert flops.train_flops_per_token(cfg, 512) == want
    assert want * 2048 == pytest.approx(6.02e12, rel=0.01)


def test_qwen2_matmul_weights_count_the_tied_head_once():
    cfg = config("qwen2-0.5b")
    layer = 896 * 896 * 2 + 2 * 896 * 128 + 3 * 896 * 4864
    assert flops.matmul_params(cfg) == 24 * layer + 896 * 151936


def test_fletcher_bytes():
    assert flops.fletcher_bytes(1024 * 10, 1024) == 4 * 10240 + 80 + 8
    assert flops.fletcher_bytes(1025, 1024) == 4 * 1025 + 16 + 8


def test_peaks_table():
    p = harness.peaks("TPU v5 lite")
    assert p["flops_bf16"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]
    with pytest.raises(KeyError):
        harness.peaks("TPU v9 imaginary")


def test_layout_counts_published_parameters():
    n = sum(math.prod(s) for s, _ in
            weights.param_layout(config("qwen2-0.5b")).values())
    assert n == pytest.approx(494.0e6, rel=0.001)


def _readings(scale=1.0, loss=2.0):
    grad = {"a": 1.0, "b": 2.0, "c": 3.0, "bias": 1e-6}
    return {"losses": [loss, loss, loss],
            "grad": {k: v * scale for k, v in grad.items()},
            "update": {k: v * scale for k, v in grad.items()}}


def test_gaps_measure_against_leaf_or_median():
    ref = _readings()
    assert check.left_out(ref) == ["bias"]
    got = _readings(scale=1.1, loss=2.02)
    got["grad"]["bias"] = 5.0            # left out: nought to rounding
    g = check.gaps(got, ref)
    assert g["loss_gap"] == pytest.approx(0.01)
    assert g["grad_gap"] == pytest.approx(0.1)
    assert g["update_gap"] == pytest.approx(0.1)
    stuck = dict(ref, update={k: 0.0 for k in ref["update"]})
    assert check.gaps(stuck, ref)["update_gap"] == pytest.approx(1.0)


def test_judge():
    ok, checks = check.judge({"x": 0.5, "n": 0}, {"x": 1.0, "n": 0})
    assert ok and checks["x"] == {"value": 0.5, "limit": 1.0}
    assert not check.judge({"x": 1.5}, {"x": 1.0})[0]
    assert not check.judge({"x": float("nan")}, {"x": 1.0})[0]


def test_every_cell_finds_its_files():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.traffic["kind"] in ("train",)
        assert set(cell.limits) == {"loss_gap", "grad_gap", "update_gap"}
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "step_ms"}
        for m in cell.per_layer:
            assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()
    for c in spec["configs"]:
        f = json.loads((ROOT / c["file"]).read_text())
        assert f["name"] == c["name"] and f["reduced"] == c["reduced"]
        for k in f["reduced"]:
            assert f[k] != f["published"][f["source_keys"][k]]
        for k, src in f["source_keys"].items():
            if k not in f["reduced"]:
                assert f[k] == f["published"][src], k


def test_run_refuses_without_a_chip():
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "train.glm4-9b.ml", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 3 and p.stdout == ""
    assert "no accelerator" in p.stderr
