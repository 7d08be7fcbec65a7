"""The names the benchmark finds the programs and kernels by.

`bench/layers.json` maps the XLA programs in a profiler trace to layers
by module name (`jit_train_step`, `jit_commit`, `jit_scrub`,
`jit_recover...`), and the commit's kernel by the name its Pallas call
carries on the chip (the enclosing jitted function's, `fletcher_stream`).
Those names come from Python function names; a rename would leave
`commit_device_ms` or `fletcher_roofline` with nothing to read.  Nothing
here compiles: the programs are lowered, the kernels traced.
"""
import json
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ModelConfig, ProtectConfig, TrainConfig
from repro.kernels import ops as kops
from repro.runtime.trainer import Trainer

BENCH = pathlib.Path(__file__).resolve().parents[1] / "bench"
RULES = json.loads((BENCH / "layers.json").read_text())
FAULT_CELL = "train.qwen2-0.5b.mlpc-r2.faults"


def _module(lowered) -> str:
    return re.search(r"module @([\w.]+)", lowered.as_text()).group(1)


def _layer(name: str) -> str:
    for pat, layer in RULES["programs"].items():
        if re.fullmatch(pat, name):
            return layer
    return "other"


@pytest.fixture(scope="module")
def trainer(mesh42):
    cfg = ModelConfig(
        name="t_names", family="dense", n_layers=1, d_model=32, n_heads=4,
        n_kv=2, d_ff=64, vocab=128, param_dtype="float32",
        compute_dtype="float32")
    t = Trainer(cfg, TrainConfig(learning_rate=1e-3, warmup_steps=2,
                                 total_steps=100),
                ProtectConfig(mode="mlpc", redundancy=2, block_words=64),
                mesh42, seq_len=16, global_batch=8)
    t.initialize()
    return t


def test_program_names_match_layers_json(trainer):
    t = trainer
    batch = t.stream.device_batch(0, t._batch_shardings)
    prot = t.pool.protector
    names = {
        "train_step": _module(t._train_step.lower(t.prot.state, batch)),
        "commit": _module(t.pool.commit_program().lower(t.prot,
                                                        t.prot.state)),
        "scrub": _module(jax.jit(prot.make_scrub()).lower(t.prot)),
        "recover": _module(jax.jit(prot.make_recover_rank()).lower(
            t.prot, jnp.int32(1))),
    }
    assert re.fullmatch(RULES["model_step_programs"], names["train_step"])
    assert re.fullmatch(RULES["commit_programs"], names["commit"])
    for p in ("commit", "scrub"):
        assert re.fullmatch(RULES["collective_programs"], names[p]), names
    assert _layer(names["train_step"]) == "model step"
    for p in ("commit", "scrub", "recover"):
        assert _layer(names[p]) == "engines", names


def _kernel_names(jaxpr, enclosing=""):
    """The name of the innermost jitted function around each Pallas call
    in a jaxpr, nested calls included: the name the kernel carries in the
    chip's trace."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(enclosing)
            continue
        inner = enclosing
        if "name" in eqn.params and "jaxpr" in eqn.params:
            inner = eqn.params["name"]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _kernel_names(sub, inner)
    return out


def test_fault_cell_commit_kernels_match_layers_json(monkeypatch):
    """The MLPC r=2 commit at the fault cell's per-rank row, on four
    virtual devices, with the chip's kernel path taken (traced, not
    lowered)."""
    if str(BENCH) not in sys.path:
        sys.path.insert(0, str(BENCH))
    import harness
    cell = harness.load_cell(FAULT_CELL)
    build = harness.kind_module(cell.traffic["kind"]).build
    trainer, init = build(cell, jax.devices()[:cell.chips], 0)
    state = jax.eval_shape(init, jax.ShapeDtypeStruct((2,), jnp.uint32))
    prot = jax.eval_shape(trainer.pool.protector.init, state)
    # the kernels' dispatch as on the chip: the Pallas path, not the
    # CPU's jnp oracle
    monkeypatch.setattr(kops, "_pallas_path", lambda interpret: False)
    jaxpr = jax.make_jaxpr(trainer.pool.commit_program())(prot, state)
    kernels = _kernel_names(jaxpr.jaxpr)
    assert kernels, "the commit dispatched no Pallas kernel"
    for name in kernels:
        assert re.search(RULES["commit_kernels"], name), kernels
    assert "fletcher_stream" in kernels
