"""Reduce the profiler's `perfetto_trace.json.gz` to what the metrics read.

The trace holds one process per device (`/device:TPU:<n>`) whose threads
carry the XLA programs ("XLA Modules") and the operations inside them
("XLA Ops"), and host processes whose threads carry the host's spans
(the benchmark's own `bench.*` annotations among them).  Times in the
file are microseconds; everything returned here is seconds.

`layers.json` maps program names and operation names to the benchmark's
layers, because the program names none of its programs or kernels yet.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import gzip
import json
import pathlib
import re

LAYERS = pathlib.Path(__file__).resolve().parent / "layers.json"


@dataclasses.dataclass
class Op:
    name: str
    start: float              # seconds
    dur: float
    program: str              # enclosing XLA program, "" if none

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    devices: dict                      # pid -> device process name
    ops: dict                          # pid -> [Op] sorted by start
    programs: dict                     # pid -> [Op] (program executions)
    host: list                         # [Op] host spans, all host threads
    rules: dict

    # -- device time ---------------------------------------------------------

    def busy_s(self) -> float:
        """Union of operation intervals, averaged over the devices."""
        if not self.ops:
            return 0.0
        return sum(union_length(ops) for ops in self.ops.values()) \
            / len(self.ops)

    def program_layer(self, program: str) -> str:
        base = program_base(program)
        for pat, layer in self.rules["programs"].items():
            if re.fullmatch(pat, base):
                return layer
        return "other"

    def program_s(self, pattern: str) -> float:
        """Device seconds of programs whose base name matches `pattern`,
        summed over executions and averaged over the devices."""
        if not self.programs:
            return 0.0
        rx = re.compile(pattern)
        tot = sum(p.dur for ops in self.programs.values() for p in ops
                  if rx.fullmatch(program_base(p.name)))
        return tot / len(self.programs)

    def require_programs(self, pattern: str, n: int) -> None:
        """Raise unless every device shows at least n executions of the
        programs matching `pattern`: the profiler stops recording a
        device's events past a budget, and a trace cut short would read
        low times and a high idle share."""
        rx = re.compile(pattern)
        for pid in self.devices:
            got = sum(1 for p in self.programs.get(pid, [])
                      if rx.fullmatch(program_base(p.name)))
            if got < n:
                raise RuntimeError(
                    f"the trace of {self.devices[pid]} holds {got} of {n} "
                    f"executions of {pattern}: events were dropped")

    def op_s(self, op_pattern: str, program_pattern: str = ".*") -> float:
        """Device seconds of operations matching `op_pattern` inside
        programs matching `program_pattern`, averaged over the devices."""
        if not self.ops:
            return 0.0
        ro, rp = re.compile(op_pattern), re.compile(program_pattern)
        tot = sum(o.dur for ops in self.ops.values() for o in ops
                  if ro.search(o.name) and rp.fullmatch(program_base(o.program)))
        return tot / len(self.ops)

    def op_count(self, op_pattern: str, program_pattern: str = ".*") -> float:
        """Executions of the operations `op_s` sums, per device."""
        if not self.ops:
            return 0.0
        ro, rp = re.compile(op_pattern), re.compile(program_pattern)
        n = sum(1 for ops in self.ops.values() for o in ops
                if ro.search(o.name) and rp.fullmatch(program_base(o.program)))
        return n / len(self.ops)

    # -- the breakdown -------------------------------------------------------

    def top_ops(self, n: int = 10) -> list:
        """[["layer: program/op", seconds per device]] of the costliest
        operations by self time (a loop's time less the operations nested
        in it), with numbered duplicates folded together."""
        acc = collections.Counter()
        for ops in self.ops.values():
            for o, self_s in self_times(ops):
                prog = program_base(o.program)
                acc[f"{self.program_layer(prog)}: {prog or '?'}/"
                    f"{op_base(o.name)}"] += self_s
        k = max(len(self.ops), 1)
        return [[name, s / k] for name, s in acc.most_common(n)]

    def idle_gaps(self, n: int = 10) -> list:
        """[[host activity, seconds]] of the longest gaps between device
        operations on the first device, each named by the shortest host
        span that covers at least half of it (else the one that overlaps
        it most)."""
        if not self.ops:
            return []
        pid = sorted(self.ops)[0]
        gaps = []
        end = None
        for o in self.ops[pid]:
            if end is not None and o.start > end:
                gaps.append((o.start - end, end, o.start))
            end = o.end if end is None else max(end, o.end)
        gaps.sort(reverse=True)
        hs = sorted(self.host, key=lambda h: h.start)
        starts = [h.start for h in hs]
        out = []
        for length, a, b in gaps[:n]:
            i = bisect.bisect_right(starts, b)
            over = [(min(b, h.end) - max(a, h.start), h)
                    for h in hs[max(0, i - 4000):i]]
            over = [(ov, h) for ov, h in over if ov > 0]
            # the innermost span that covers most of the gap names it
            most = [h for ov, h in over if ov >= 0.5 * length]
            if most:
                name = min(most, key=lambda h: h.dur).name
            else:
                name = max(over, key=lambda x: x[0])[1].name if over \
                    else "idle"
            out.append([name, length])
        return out


def program_base(name: str) -> str:
    """'jit_commit(12)' -> 'jit_commit'."""
    return re.sub(r"\(\d+\)$", "", name).strip()


def op_base(name: str) -> str:
    """'fusion.123' -> 'fusion'."""
    return re.sub(r"[.:]\d+$", "", name)


def self_times(ops) -> list:
    """[(op, seconds)] with each op's time less its nested ops' time."""
    out, stack = [], []
    for o in sorted(ops, key=lambda x: (x.start, -x.dur)):
        while stack and stack[-1][0].end <= o.start + 1e-12:
            stack.pop()
        if stack:
            stack[-1][1][0] -= o.dur
        entry = [o.dur]
        out.append((o, entry))
        stack.append((o, entry))
    return [(o, max(e[0], 0.0)) for o, e in out]


def union_length(ops) -> float:
    tot, cur_a, cur_b = 0.0, None, None
    for o in sorted(ops, key=lambda x: x.start):
        if cur_b is None or o.start > cur_b:
            if cur_b is not None:
                tot += cur_b - cur_a
            cur_a, cur_b = o.start, o.end
        else:
            cur_b = max(cur_b, o.end)
    if cur_b is not None:
        tot += cur_b - cur_a
    return tot


def load_rules(path: pathlib.Path = LAYERS) -> dict:
    return json.loads(pathlib.Path(path).read_text())


def read(path, rules: dict | None = None) -> Trace:
    """Parse a perfetto JSON trace (gzipped or not)."""
    path = pathlib.Path(path)
    opener = gzip.open if path.suffix == ".gz" else open
    with opener(path, "rt") as f:
        doc = json.load(f)
    return parse(doc, rules or load_rules())


def parse(doc, rules: dict) -> Trace:
    events = doc["traceEvents"] if isinstance(doc, dict) else doc
    pnames, tnames = {}, {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            pnames[e["pid"]] = e.get("args", {}).get("name", "")
        elif e.get("ph") == "M" and e.get("name") == "thread_name":
            tnames[(e["pid"], e.get("tid"))] = e.get("args", {}).get("name", "")
    dev_rx = re.compile(rules["device_process"])
    devices = {p: n for p, n in pnames.items() if dev_rx.search(n)}
    op_rx = re.compile(rules["op_thread"])
    prog_rx = re.compile(rules["program_thread"])
    ops, programs, host = (collections.defaultdict(list),
                           collections.defaultdict(list), [])
    for e in events:
        if e.get("ph") != "X" or "dur" not in e:
            continue
        pid = e["pid"]
        o = Op(e.get("name", ""), float(e["ts"]) * 1e-6,
               float(e["dur"]) * 1e-6, "")
        if pid in devices:
            tname = tnames.get((pid, e.get("tid")), "")
            if prog_rx.search(tname):
                programs[pid].append(o)
            elif op_rx.search(tname):
                ops[pid].append(o)
        else:
            host.append(o)
    for pid in ops:
        ops[pid].sort(key=lambda o: o.start)
        progs = sorted(programs.get(pid, []), key=lambda p: p.start)
        starts = [p.start for p in progs]
        for o in ops[pid]:
            i = bisect.bisect_right(starts, o.start) - 1
            if i >= 0 and o.start < progs[i].end + 1e-9:
                o.program = progs[i].name
    return Trace(devices=devices, ops=dict(ops),
                 programs={p: sorted(v, key=lambda x: x.start)
                           for p, v in programs.items()},
                 host=host, rules=rules)

