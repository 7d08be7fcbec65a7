"""The step path's profiler spans, read as the benchmark's readers read them.

`Trainer.run` runs a few steps under the JAX profiler on the CPU; the
perfetto trace must hold each of the step path's five spans once per
step, under the names `bench/spans.py` matches, nested as the readers
assume, with the step's data cursor as their `step` argument.  A pool
without a trace file keeps no per-commit event in memory.
"""
import collections
import gzip
import json
import pathlib
import sys
import types

import jax
import pytest

from repro.configs.base import ModelConfig, ProtectConfig, TrainConfig
from repro.pool import Pool
from repro.runtime.trainer import Trainer

from tests.conftest import small_state

BENCH = pathlib.Path(__file__).resolve().parents[1] / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import spans  # noqa: E402
import traceview  # noqa: E402

STEPS = 5
PHASES = ("trainer.dispatch", "trainer.train_step", "pool.commit_dispatch",
          "trainer.resolve", "pool.resolve")
# child -> the span it runs inside, for the same step
PARENT = {"trainer.train_step": "trainer.dispatch",
          "pool.commit_dispatch": "trainer.dispatch",
          "pool.resolve": "trainer.resolve"}


def _traced_run(mesh, tmp_path, depth):
    cfg = ModelConfig(
        name="t_spans", family="dense", n_layers=1, d_model=32, n_heads=4,
        n_kv=2, d_ff=64, vocab=128, param_dtype="float32",
        compute_dtype="float32")
    t = Trainer(cfg, TrainConfig(learning_rate=1e-3, warmup_steps=2,
                                 total_steps=100),
                ProtectConfig(mode="mlpc", block_words=64, scrub_period=4,
                              pipeline_depth=depth),
                mesh, seq_len=16, global_batch=8)
    t.initialize()
    t.run(2)                       # compile outside the traced window
    with jax.profiler.trace(str(tmp_path), create_perfetto_trace=True):
        outs = t.run(STEPS)
    assert all(o["committed"] for o in outs)
    path = next(tmp_path.rglob("perfetto_trace.json.gz"))
    with gzip.open(path, "rt") as f:
        doc = json.load(f)
    return doc, traceview.read(path)


@pytest.mark.parametrize("depth", [1, 4])
def test_step_path_spans_once_per_step_and_nested(mesh42, tmp_path, depth):
    doc, tv = _traced_run(mesh42, tmp_path, depth)
    events = doc["traceEvents"] if isinstance(doc, dict) else doc
    steps = collections.defaultdict(list)
    for e in events:
        name = spans.span_name(e.get("name", ""))
        if e.get("ph") == "X" and name in PHASES:
            steps[name].append(int(e["args"]["step"]))
    # the two warm-up steps had cursors 0 and 1
    want = list(range(2, 2 + STEPS))
    for name in PHASES:
        assert sorted(steps[name]) == want, (name, steps[name])

    got = spans.program_spans(types.SimpleNamespace(trace=tv, steps=STEPS))
    assert got is not None
    assert {s.name for s in got} >= set(PHASES) | {"pool.scrub"}
    by = collections.defaultdict(list)
    for s in got:
        by[s.name].append(s)
    for child, parent in PARENT.items():
        for c in by[child]:
            assert any(p.start <= c.start and c.end <= p.end + 1e-6
                       for p in by[parent]), (child, c)
    # the train step's launch ends before the commit's dispatch begins
    for a, b in zip(by["trainer.train_step"], by["pool.commit_dispatch"]):
        assert a.end <= b.start + 1e-6
    # a scrub on the cadence runs inside a step's resolution
    for s in by["pool.scrub"]:
        assert any(p.start <= s.start and s.end <= p.end + 1e-6
                   for p in by["trainer.resolve"])
    # what the readers build on: every self and wall time is real
    assert spans.self_s(got, {"trainer.dispatch", "trainer.resolve"}) > 0
    assert spans.wall_s(got, "pool.resolve") > 0


def test_pool_without_trace_file_keeps_no_commit_events(mesh42):
    state, specs, _ = small_state(mesh42)
    pool = Pool.open(state, specs, mesh=mesh42,
                     config=ProtectConfig(mode="mlpc", block_words=64,
                                          pipeline_depth=2),
                     donate=False)
    cur = state
    pool.commit_async(cur, data_cursor=0)
    pool.drain()
    held = len(pool.tracer.events)
    ids = []
    for i in range(1, 9):
        cur = jax.tree.map(lambda x: (x * 1.01).astype(x.dtype), cur)
        ids.append(pool.commit_async(cur, data_cursor=i).span_id)
    pool.drain()
    assert len(pool.tracer.events) == held
    # the exemplar ids still advance one a commit
    assert ids == list(range(ids[0], ids[0] + 8))
