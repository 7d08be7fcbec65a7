"""Shared benchmark scaffolding: timing, mesh/state construction, reporting.

All benchmarks run on CPU host devices (8-way, set in benchmarks/run.py
before jax's first import).  Absolute times are CPU times — the paper's
*relative* claims (mode ladder ordering, parity-vs-replica ratio, hybrid
crossover) are the reproduction targets, as DESIGN.md §6 lays out.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.launch.mesh import make_mesh

RESULTS_DIR = os.environ.get(
    "BENCH_RESULTS", os.path.join(os.path.dirname(__file__), "results"))


def get_mesh(data: int = 4, model: int = 2) -> Mesh:
    return make_mesh((data, model))


def timeit(fn: Callable, *args, warmup: int = 2, reps: int = 10,
           **kw) -> dict:
    """Median wall time of fn(*args); blocks on all output leaves."""
    for _ in range(warmup):
        out = fn(*args, **kw)
        jax.block_until_ready(out)
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    ts = np.asarray(ts)
    return {"median_s": float(np.median(ts)), "p10_s": float(np.quantile(ts, .1)),
            "p90_s": float(np.quantile(ts, .9)), "reps": reps}


def state_of_bytes(n_bytes: int, mesh, dtype=jnp.float32) -> tuple:
    """A single-leaf state of ~n_bytes, FSDP-sharded over the data axis."""
    g = mesh.shape["data"]
    n = max(n_bytes // jnp.dtype(dtype).itemsize, g)
    n = (n + g - 1) // g * g
    specs = {"w": P("data")}
    state = {"w": (jnp.arange(n, dtype=jnp.uint32) % 1000).astype(dtype)}
    sh = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                      is_leaf=lambda x: isinstance(x, P))
    return jax.tree.map(jax.device_put, state, sh), specs


def save_result(name: str, payload) -> str:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, f"{name}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, default=float)
    return path


def print_table(title: str, rows: list, cols: list):
    print(f"\n== {title} ==")
    widths = [max(len(str(c)), max((len(str(r.get(c, ''))) for r in rows),
                                   default=0)) for c in cols]
    print("  ".join(str(c).ljust(w) for c, w in zip(cols, widths)))
    for r in rows:
        print("  ".join(str(r.get(c, "")).ljust(w)
                        for c, w in zip(cols, widths)))
