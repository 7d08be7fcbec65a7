"""The trace reduction, on three steps of a recorded chip trace of
train.glm4-9b.ml (one TPU v5e; device ops, programs and host spans, the
Python tracer's events and most op arguments cut out)."""
import pathlib

import pytest

import traceview

DATA = pathlib.Path(__file__).parent / "data" / "glm4-9b.ml.3steps.json.gz"


@pytest.fixture(scope="module")
def tv():
    return traceview.read(DATA)


def test_finds_the_one_device(tv):
    assert list(tv.devices.values()) == ["/device:TPU:0"]


def test_programs_and_layers(tv):
    runs = [traceview.program_base(p.name)
            for p in next(iter(tv.programs.values()))]
    assert 2 <= runs.count("jit_train_step") <= 4
    assert 2 <= runs.count("jit_commit") <= 4
    assert tv.program_layer("jit_train_step(17189648392156925232)") \
        == "model step"
    assert tv.program_layer("jit_commit(1)") == "engines"
    assert tv.program_layer("jit_something_else(1)") == "other"


def test_busy_is_a_union_within_the_span(tv):
    ops = tv.ops[next(iter(tv.ops))]
    span = max(o.end for o in ops) - min(o.start for o in ops)
    assert 0 < tv.busy_s() <= span
    assert tv.busy_s() < sum(o.dur for o in ops)   # loops nest their ops


def test_program_time_holds_its_ops(tv):
    step = tv.program_s("jit_train_step")
    inside = traceview.union_length(
        [o for o in next(iter(tv.ops.values()))
         if traceview.program_base(o.program) == "jit_train_step"])
    assert 0 < inside <= step * (1 + 1e-6)


def test_one_chip_has_no_collective(tv):
    r = tv.rules
    assert tv.op_s(r["collective_ops"], r["collective_programs"]) == 0
    assert tv.op_count(r["collective_ops"]) == 0


def test_breakdown(tv):
    top = tv.top_ops(10)
    assert len(top) == 10
    assert top[0][0].startswith("model step: jit_train_step/")
    assert all(a[1] >= b[1] for a, b in zip(top, top[1:]))
    assert sum(s for _, s in tv.top_ops(10 ** 6)) \
        == pytest.approx(tv.busy_s(), rel=0.05)
    gaps = tv.idle_gaps(5)
    assert len(gaps) == 5 and all(isinstance(n, str) for n, _ in gaps)
    assert all(a[1] >= b[1] for a, b in zip(gaps, gaps[1:]))


def test_self_time_of_nested_ops():
    outer = traceview.Op("while.1", 0.0, 10.0, "p")
    inner = traceview.Op("fusion.2", 2.0, 3.0, "p")
    after = traceview.Op("copy.3", 11.0, 1.0, "p")
    got = {o.name: s for o, s in traceview.self_times([inner, outer, after])}
    assert got == {"while.1": 7.0, "fusion.2": 3.0, "copy.3": 1.0}
    assert traceview.union_length([outer, inner, after]) == 11.0


def test_names():
    assert traceview.program_base("jit_commit(17636905408283029609)") \
        == "jit_commit"
    assert traceview.op_base("fusion.753") == "fusion"
    assert traceview.op_base("all-to-all.2") == "all-to-all"


def test_a_trace_missing_executions_is_refused(tv):
    tv.require_programs("jit_train_step", 2)
    with pytest.raises(RuntimeError, match="dropped"):
        tv.require_programs("jit_train_step", 50)
