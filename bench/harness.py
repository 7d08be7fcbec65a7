"""What every cell shares: finding its files by name, the device check,
the compile cache, and the result line.

A cell (`workloads` entry of BENCHMARK.json) names a configuration and a
traffic mix.  Each is a data file found by its name: the configuration in
`configs/<name>.json` (its `file` in BENCHMARK.json), the traffic in
`traffic/<traffic>.json`, the correctness limits in
`limits/<workload>.json`.  The traffic's `kind` names the module under
`kinds/` that runs it, the configuration's `layout` names the module
under `layouts/` that knows its architecture (its parameter tree, the
program's model fields, the vocabulary rows held, its operations per
token, its CPU shrink), its `reference` the plain reference under
`reference/`, and each per-layer metric is a reader in
`metrics/<metric>.py`.  Adding a cell, a configuration, an architecture
or a metric adds files under those directories and entries in
BENCHMARK.json; no file here changes.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import os
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    workload: dict
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list          # BENCHMARK.json metric entries of this cell
    per_layer: list

    @property
    def name(self) -> str:
        return self.workload["name"]

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])


def _reports(metric: dict, workload: str) -> bool:
    return workload in metric.get("workloads", [workload])


def load_cell(workload: str, spec_path: pathlib.Path = ROOT / "BENCHMARK.json"
              ) -> Cell:
    spec = json.loads(spec_path.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in {spec_path.name}; "
                       f"known: {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    e2e = [m for m in spec["end_to_end"] if _reports(m, workload)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if m["moves"] in moved and _reports(m, workload)]
    return Cell(
        workload=w,
        config=load_config(conf["file"]),
        traffic=json.loads(
            (BENCH / "traffic" / f"{w['traffic']}.json").read_text()),
        limits=json.loads(
            (BENCH / "limits" / f"{workload}.json").read_text()),
        end_to_end=e2e, per_layer=per_layer)


def load_config(file: str) -> dict:
    """A configuration file (relative to the checkout), which has to name
    its layout."""
    cfg = json.loads((ROOT / file).read_text())
    if "layout" not in cfg:
        raise ValueError(f"{file} names no \"layout\": the module of "
                         f"bench/layouts/ that lays out its parameters")
    return cfg


def load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def kind_module(kind: str):
    return load_module(BENCH / "kinds" / f"{kind}.py", f"kind_{kind}")


def layout_module(cfg: dict):
    """The module of `layouts/` that the configuration names."""
    return _layout(cfg["layout"])


@functools.cache
def _layout(name: str):
    return load_module(BENCH / "layouts" / f"{name}.py",
                       "layout_" + name.replace(".", "_"))


def metric_reader(name: str):
    return load_module(BENCH / "metrics" / f"{name}.py",
                       "metric_" + name.replace(".", "_"))


def peaks(device_kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"(known: {sorted(table)})")
    return table[device_kind]


def chips_or_fail(n: int) -> list:
    """The first n accelerator devices; raises NoChip otherwise."""
    import jax
    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise NoChip(f"JAX found no accelerator (platform "
                     f"{devices[0].platform!r})")
    if len(devices) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX found {len(devices)}")
    return devices[:n]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at the fixed `<checkout>/.jax_cache`,
    every program in it, whatever the environment names."""
    import jax
    path = str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def memory_peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def device_info(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def emit(result: dict, checks: dict) -> None:
    """Each compared number beside its limit as the last lines of stderr,
    then the result line (checks last) as the last line of stdout."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    result = dict(result, checks=checks)
    print(json.dumps(result), flush=True)


def add_paths() -> None:
    for p in (str(BENCH), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
