"""HLO-level analysis for the roofline: collective volume + cost terms.

`cost_analysis()` gives HLO FLOPs and bytes, but not collective traffic —
we parse the optimized HLO text, build an instruction-name -> shape map, and
sum wire bytes for every collective with the standard volume conventions:

    all-gather          (G-1)/G * result_bytes
    reduce-scatter      (G-1)/G * operand_bytes
    all-reduce          2 (G-1)/G * operand_bytes      (RS + AG)
    all-to-all          (G-1)/G * operand_bytes
    collective-permute  operand_bytes

Group size G is parsed from replica_groups when present.  The per-chip
peaks for the three roofline terms live here too, keyed by device kind.
"""
from __future__ import annotations

import dataclasses
import math
import re
from typing import Dict, Optional

# -- published per-chip peaks, keyed by jax `device_kind` -------------------


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_bf16: float           # FLOP/s
    hbm_bw: float               # B/s
    ici_bw: float               # B/s per link
    source: str


V5E = "TPU v5 lite"             # what jax reports for a TPU v5e chip

PEAKS = {
    # 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
    # interconnect over 4 ICI links = 50 GB/s per link
    V5E: Peaks(flops_bf16=197e12, hbm_bw=819e9, ici_bw=50e9,
               source='Google Cloud documentation, "TPU v5e"'),
}


def peaks(device_kind: str) -> Peaks:
    """The peaks of `device_kind`; a device not in PEAKS is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} (known: {sorted(PEAKS)})") from None

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

# `%name = dtype[dims]{layout} opcode(...)` — optimized HLO instruction line
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(?:\()?([a-z0-9]+)\[([0-9,]*)\]"
    r"[^\s]*\s+([a-z0-9\-]+)\(")
_TUPLE_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*\(")
_OPERAND_RE = re.compile(r"%?([\w.\-]+)")
_GROUPS_RE = re.compile(r"replica_groups=\{?\{([0-9, ]+)\}")
_GROUPS_V2_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims.strip():
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


@dataclasses.dataclass
class CollectiveStats:
    wire_bytes: Dict[str, float]          # per collective kind, per device
    counts: Dict[str, int]
    total_wire_bytes: float = 0.0

    def __post_init__(self):
        self.total_wire_bytes = sum(self.wire_bytes.values())


def parse_collectives(hlo_text: str) -> CollectiveStats:
    shapes: Dict[str, int] = {}
    wire = {k: 0.0 for k in COLLECTIVES}
    counts = {k: 0 for k in COLLECTIVES}

    pending = []  # (opcode, operand names, result bytes, group size, line)
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, dtype, dims, opcode = m.groups()
        nbytes = _shape_bytes(dtype, dims)
        shapes[name] = nbytes
        base = None
        for c in COLLECTIVES:
            # opcodes appear as all-gather / all-gather-start / -done etc.
            if opcode == c or opcode.startswith(c + "-"):
                base = c
                break
        if base is None or opcode.endswith("-done"):
            continue
        # operand list: text between the first '(' and matching ')'
        try:
            args_str = line.split("(", 1)[1]
        except IndexError:
            args_str = ""
        # cut at '), ' attributes boundary
        depth, end = 1, len(args_str)
        for i, ch in enumerate(args_str):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        operand_names = []
        for tok in args_str[:end].split(","):
            tok = tok.strip()
            mm = _OPERAND_RE.match(tok)
            if mm:
                operand_names.append(mm.group(1))
        g = None
        gm = _GROUPS_RE.search(line)
        if gm:
            g = len([x for x in gm.group(1).split(",") if x.strip()])
        else:
            gm2 = _GROUPS_V2_RE.search(line)
            if gm2:
                g = int(gm2.group(2))
        pending.append((base, operand_names, nbytes, g))
        counts[base] += 1

    for base, operand_names, result_bytes, g in pending:
        operand_bytes = sum(shapes.get(o, 0) for o in operand_names)
        if operand_bytes == 0:
            operand_bytes = result_bytes
        if g is None or g <= 1:
            frac = 1.0
        else:
            frac = (g - 1) / g
        if base == "all-gather":
            wire[base] += frac * result_bytes
        elif base == "all-reduce":
            wire[base] += 2.0 * frac * operand_bytes
        elif base == "reduce-scatter":
            wire[base] += frac * operand_bytes
        elif base == "all-to-all":
            wire[base] += frac * operand_bytes
        elif base == "collective-permute":
            wire[base] += operand_bytes
    return CollectiveStats(wire_bytes=wire, counts=counts)


@dataclasses.dataclass
class Roofline:
    flops: float                 # per device
    hbm_bytes: float             # per device
    wire_bytes: float            # per device
    compute_s: float
    memory_s: float
    collective_s: float
    bound: str
    model_flops: float = 0.0     # analytic 6ND (whole step, per device)
    useful_ratio: float = 0.0    # model_flops / hlo flops

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def roofline_terms(flops: float, hbm_bytes: float, wire_bytes: float,
                   model_flops: float = 0.0, *,
                   device_kind: str) -> Roofline:
    peak = peaks(device_kind)
    ct = flops / peak.flops_bf16
    mt = hbm_bytes / peak.hbm_bw
    lt = wire_bytes / peak.ici_bw
    bound = max((("compute", ct), ("memory", mt), ("collective", lt)),
                key=lambda kv: kv[1])[0]
    return Roofline(
        flops=flops, hbm_bytes=hbm_bytes, wire_bytes=wire_bytes,
        compute_s=ct, memory_s=mt, collective_s=lt, bound=bound,
        model_flops=model_flops,
        useful_ratio=(model_flops / flops) if flops else 0.0)
