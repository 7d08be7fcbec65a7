"""The readers of the program's step spans, on a hand-made trace.

Two chips and one host thread over two steps, times in microseconds.
Chip 0 idles inside every step's spans; chip 1 is busy throughout.
Step 0: dispatch [0, 20] holding the train step's launch [2, 8] and the
commit's dispatch [10, 14]; resolve [20, 50] holding the wait [22, 44].
Step 1: dispatch [50, 60] holding [51, 55] and [56, 58]; resolve [60, 90]
holding the wait [61, 85] and a scrub [86, 89].  Chip 0 runs [5, 18],
[25, 48] (with a nested op), [52, 59], [65, 88].  A runtime span and the
benchmark's own span overlap them and are not the program's.
"""
import pathlib
import types

import pytest

import harness
import spans
import traceview

DATA = pathlib.Path(__file__).parent / "data" / "glm4-9b.ml.3steps.json.gz"
NEW = ("trainer_host_ms", "train_launch_ms", "resolve_wait_ms",
       "dispatch_idle_ms", "resolve_idle_ms")

HOST = [("bench.steps", 0, 100),
        ("trainer.dispatch#step=0#", 0, 20),
        ("trainer.train_step#step=0#", 2, 8),
        ("pool.commit_dispatch#step=0#", 10, 14),
        ("trainer.resolve#step=0#", 20, 50),
        ("pool.resolve#step=0#", 22, 44),
        ("trainer.dispatch#step=1#", 50, 60),
        ("trainer.train_step#step=1#", 51, 55),
        ("pool.commit_dispatch#step=1#", 56, 58),
        ("trainer.resolve#step=1#", 60, 90),
        ("pool.resolve#step=1#", 61, 85),
        ("pool.scrub#id=7#", 86, 89)]
CHIP0 = [(5, 18), (25, 48), (30, 40), (52, 59), (65, 88)]
CHIP1 = [(0, 20), (20, 50), (50, 60), (60, 90)]


def _doc(chips, host=HOST):
    ev = [{"ph": "M", "name": "process_name", "pid": 100,
           "args": {"name": "/host:CPU"}},
          {"ph": "M", "name": "thread_name", "pid": 100, "tid": 1,
           "args": {"name": "python"}},
          {"ph": "M", "name": "thread_name", "pid": 100, "tid": 2,
           "args": {"name": "runtime"}},
          {"ph": "X", "pid": 100, "tid": 2, "name": "CompleteCallbacks",
           "ts": 30, "dur": 5}]
    ev += [{"ph": "X", "pid": 100, "tid": 1, "name": n, "ts": a,
            "dur": b - a} for n, a, b in host]
    for i, ops in enumerate(chips):
        pid = i + 1
        ev += [{"ph": "M", "name": "process_name", "pid": pid,
                "args": {"name": f"/device:TPU:{i}"}},
               {"ph": "M", "name": "thread_name", "pid": pid, "tid": 1,
                "args": {"name": "XLA Modules"}},
               {"ph": "M", "name": "thread_name", "pid": pid, "tid": 2,
                "args": {"name": "XLA Ops"}},
               {"ph": "X", "pid": pid, "tid": 1, "name": "jit_train_step(1)",
                "ts": 0, "dur": 100}]
        ev += [{"ph": "X", "pid": pid, "tid": 2, "name": f"fusion.{k}",
                "ts": a, "dur": b - a} for k, (a, b) in enumerate(ops)]
    return {"traceEvents": ev}


def _read(doc, steps=2):
    ctx = types.SimpleNamespace(
        trace=traceview.parse(doc, traceview.load_rules()), steps=steps)
    return {m: harness.metric_reader(m).read(ctx) for m in NEW}


def test_readers_on_hand_placed_spans():
    got = _read(_doc([CHIP0, CHIP1]))
    # self time: dispatch 20-6-4 and 10-4-2, resolve 30-22 and 30-24-3
    # per step, in ms; idle: chip 0's gaps inside the spans, over 2 chips
    want = {"trainer_host_ms": (10 + 4 + 8 + 3) / 2e3,
            "train_launch_ms": (6 + 4) / 2e3,
            "resolve_wait_ms": (22 + 24) / 2e3,
            "dispatch_idle_ms": (7 + 3) / 2 / 2e3,
            "resolve_idle_ms": (7 + 7) / 2 / 2e3}
    assert got == pytest.approx(want, rel=1e-9, abs=1e-12)


def test_a_chip_busy_throughout_reads_zero_idle():
    got = _read(_doc([CHIP1]))
    assert got["dispatch_idle_ms"] == 0.0
    assert got["resolve_idle_ms"] == 0.0
    assert got["resolve_wait_ms"] == pytest.approx(0.023)


def test_program_spans_but_none_of_one_kind_read_zero():
    host = [h for h in HOST if not h[0].startswith("pool.resolve")]
    assert _read(_doc([CHIP0], host))["resolve_wait_ms"] == 0.0


def test_a_trace_without_device_ops_reads_no_idle():
    got = _read(_doc([]))
    assert got["dispatch_idle_ms"] is None and got["resolve_idle_ms"] is None
    assert got["train_launch_ms"] == pytest.approx(0.005)


def test_a_trace_without_program_spans_reads_nothing():
    host = [h for h in HOST if h[0] == "bench.steps"]
    assert set(_read(_doc([CHIP0], host)).values()) == {None}
    assert set(_read(_doc([CHIP0]), steps=0).values()) == {None}


def test_span_names_drop_their_arguments():
    assert spans.span_name("trainer.dispatch#step=5#") == "trainer.dispatch"
    assert spans.span_name("pool.resolve") == "pool.resolve"
    ctx = types.SimpleNamespace(
        trace=traceview.parse(_doc([CHIP0]), traceview.load_rules()),
        steps=2)
    names = {s.name for s in spans.program_spans(ctx)}
    assert names == {"trainer.dispatch", "trainer.train_step",
                     "pool.commit_dispatch", "trainer.resolve",
                     "pool.resolve", "pool.scrub"}


def test_recorded_trace_keeps_the_existing_readings():
    """The recorded glm4 trace (a program without step spans) parses as
    before: the existing readers read what they read, the new ones
    nothing."""
    tv = traceview.read(DATA)
    ops = tv.ops[next(iter(tv.ops))]
    window = max(o.end for o in ops) - min(o.start for o in ops)
    ctx = types.SimpleNamespace(trace=tv, steps=3, trace_window_s=window)
    read = {m: harness.metric_reader(m).read(ctx)
            for m in ("train_step_device_ms", "commit_device_ms",
                      "idle_share", "synd_collective_ms") + NEW}
    assert read["train_step_device_ms"] == pytest.approx(90.64832213533333)
    assert read["commit_device_ms"] == pytest.approx(40.664185364666665)
    assert read["idle_share"] == pytest.approx(8.341309542343234)
    assert read["synd_collective_ms"] is None
    assert all(read[m] is None for m in NEW)
