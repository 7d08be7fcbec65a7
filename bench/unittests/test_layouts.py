"""Architecture lives in `layouts/`: what the dense decoder's module gives
is pinned to values recorded from the code before it moved there, a
configuration has to name its layout, and no other module of the harness
reads the architecture's keys."""
import json
import pathlib
import re

import pytest

import flops
import harness
import tiny
import weights

ROOT = pathlib.Path(__file__).resolve().parents[2]
PINNED = json.loads((ROOT / "bench" / "unittests" / "data"
                     / "decoder_pinned.json").read_text())
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIG_FILES = {c["name"]: c["file"] for c in SPEC["configs"]}
ARCHITECTURE_KEYS = ("n_layers", "d_model", "n_heads", "n_kv", "head_dim",
                     "d_ff", "vocab", "qkv_bias", "tie_embeddings")


def _cell_of(config: str) -> str:
    return next(w["name"] for w in SPEC["workloads"] if w["config"] == config)


def _layout(cfg) -> list:
    return [[p, list(s), std] for p, (s, std) in
            weights.param_layout(cfg).items()]


@pytest.mark.parametrize("name", sorted(PINNED))
def test_decoder_layout_is_pinned(name):
    """Same leaves in the same order (so the same fold-in indices and the
    same weights from a seed), program fields and operation counts."""
    pin = PINNED[name]
    cfg = harness.load_config(CONFIG_FILES[name])
    assert cfg["layout"] == "decoder"
    assert _layout(cfg) == pin["layout"]
    assert cfg["program_config"] == pin["program_config"]
    assert harness.layout_module(cfg).program_overrides(cfg) \
        == pin["program_overrides"]
    assert flops.matmul_params(cfg) == pin["matmul_params"]
    assert flops.train_flops_per_token(cfg, 512) \
        == pin["train_flops_per_token_512"]
    assert harness.layout_module(cfg).vocab(cfg) \
        == pin["program_overrides"]["vocab"]


@pytest.mark.parametrize("name", sorted(PINNED))
def test_decoder_cpu_shrink_is_pinned(name):
    import jax
    pin = PINNED[name]
    small = tiny.cell(_cell_of(name)).config
    assert harness.layout_module(small).program_overrides(small) \
        == pin["tiny"]
    assert _layout(small) == pin["tiny_layout"]
    assert flops.train_flops_per_token(small, 32) \
        == pin["tiny_flops_per_token_32"]
    norms = jax.jit(lambda k: weights.leaf_norms(
        weights.make_params(small, k)))(weights.seed_key(2 ** 40 + 7))
    got = {k: float(v) for k, v in norms.items()}
    assert got == pytest.approx(pin["tiny_leaf_norms_seed_2p40_7"],
                                rel=1e-6)


def test_configuration_without_layout_is_refused(tmp_path):
    cfg = harness.load_config(CONFIG_FILES["qwen2-0.5b"])
    del cfg["layout"]
    file = tmp_path / "no-layout.json"
    file.write_text(json.dumps(cfg))
    spec = dict(SPEC, configs=[dict(c, file=str(file))
                               for c in SPEC["configs"]])
    spec_path = tmp_path / "BENCHMARK.json"
    spec_path.write_text(json.dumps(spec))
    with pytest.raises(ValueError, match="no-layout.json"):
        harness.load_cell("train.qwen2-0.5b.ml", spec_path)


def test_every_configuration_names_a_layout_that_exists():
    for name, file in CONFIG_FILES.items():
        mod = harness.layout_module(harness.load_config(file))
        for fn in ("param_layout", "program_overrides", "vocab",
                   "matmul_params", "train_flops_per_token", "tiny"):
            assert callable(getattr(mod, fn)), (name, fn)


def test_only_layouts_and_references_read_widths():
    """A new architecture enters as files: no module of the harness
    outside `layouts/` and `reference/` (and these tests) names a key of
    the dense decoder's architecture, its widths among them."""
    pattern = re.compile(r"""["'](%s)["']|\b(%s)\s*=""" % (
        "|".join(ARCHITECTURE_KEYS), "|".join(ARCHITECTURE_KEYS)))
    bench = ROOT / "bench"
    offenders = []
    for path in sorted(bench.rglob("*.py")):
        top = path.relative_to(bench).parts[0]
        if top in ("layouts", "reference", "unittests"):
            continue
        for n, line in enumerate(path.read_text().splitlines(), 1):
            if pattern.search(line):
                offenders.append(f"{path.relative_to(ROOT)}:{n}: {line}")
    assert not offenders, "\n".join(offenders)
