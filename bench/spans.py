"""The program's own host spans in the profiler trace, and the device
idle time that falls inside them.

The program opens a profiler span at each boundary of its step path:
`trainer.dispatch` (holding `trainer.train_step` and
`pool.commit_dispatch`), `trainer.resolve` (holding `pool.resolve`, the
host's wait on a commit verdict), and `pool.<kind>` around its scrubs,
recoveries and rescales.  A name may carry its arguments after a `#`
(`trainer.dispatch#step=5#`); the span's name is what comes before.
The benchmark's own `bench.*` spans and the runtime's are not the
program's.  A program that opens none of these leaves every reader that
builds on this module with nothing to read.
"""
from __future__ import annotations

import bisect
import dataclasses
import re

import traceview

PROGRAM = re.compile(r"(trainer|pool)\.[a-z_]+")


def span_name(name: str) -> str:
    """'trainer.dispatch#step=5#' -> 'trainer.dispatch'."""
    return name.split("#", 1)[0]


def program_spans(ctx):
    """The program's spans in the traced window, sorted by start, or
    None when there is no trace, no committed step, or no such span."""
    if ctx.trace is None or not ctx.steps:
        return None
    spans = sorted((dataclasses.replace(h, name=span_name(h.name))
                    for h in ctx.trace.host
                    if PROGRAM.fullmatch(span_name(h.name))),
                   key=lambda h: (h.start, -h.dur))
    return spans or None


def wall_s(spans, name: str) -> float:
    """Seconds inside the spans called `name`."""
    return sum(s.dur for s in spans if s.name == name)


def self_s(spans, names) -> float:
    """Seconds inside the spans called one of `names`, less the program
    spans nested in them."""
    return sum(t for s, t in traceview.self_times(spans) if s.name in names)


def busy_intervals(ops) -> list:
    """The union of operation intervals as sorted, disjoint (start, end)."""
    out = []
    for o in sorted(ops, key=lambda x: x.start):
        if out and o.start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], o.end)
        else:
            out.append([o.start, o.end])
    return out


def idle_s(trace, spans, name: str) -> float:
    """Device-idle seconds inside the spans called `name`: each span's
    length less the part of it some operation covers, summed over the
    spans and averaged over the devices; None for a trace without any
    device operation."""
    if not trace.ops:
        return None
    mine = [s for s in spans if s.name == name]
    total = 0.0
    for ops in trace.ops.values():
        busy = busy_intervals(ops)
        ends = [b for _, b in busy]
        for s in mine:
            covered = 0.0
            for a, b in busy[bisect.bisect_right(ends, s.start):]:
                if a >= s.end:
                    break
                covered += min(b, s.end) - max(a, s.start)
            total += s.dur - covered
    return total / len(trace.ops)
