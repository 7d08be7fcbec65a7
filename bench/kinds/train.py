"""Training cells: the program's `Trainer` over its `Pool`, closed loop.

Set-up builds one `Trainer` on a mesh of the cell's chips (data axis =
the zone), gives its pool the train state made from the seed
(`weights.py`) and its data stream the seed's tokens, and drives it
through the first `reference_steps` steps with `Trainer.run`, the
window's own call; the readings the comparison needs are taken between
those steps.  A scrub, and in a cell with faults one whole fault sequence,
warm the remaining programs.  The window then runs steps back to back for
`--seconds` (a traced run: for the traffic's `trace_steps` steps, few
enough that the profiler keeps every event), `steps_per_call` steps to a
`Trainer.run` call: with the traffic's `pipeline_depth` above 1 the
program dispatches that many steps ahead of the one whose loss and verdict
it waits for, so the chip stays fed while the host stalls; each call
returns with all its steps resolved, and the window is read after the
call in which its time ran out.  In a cell with faults every `fault_every`-th step is followed
by the loss of a rank drawn from the seed, `Pool.recover`, `Pool.scrub`
and a look at health, and the lost rank's contents are compared with what
they held before the loss.  After the window: a last scrub, health, the
memory peak, then the program is freed and the reference runs.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import importlib
import math
import pathlib
import tempfile
import time
import types

import numpy as np

import check
import harness
import traceview
import weights


@dataclasses.dataclass
class Tokens:
    """The cell's token stream: a pure function of (seed, step cursor).

    Zipf-distributed ids (exponent `zipf`) over the configuration's
    vocabulary; every row of every step differs.  Stands in for the
    program's own data stream through the same `device_batch` call.
    """
    seed: int
    vocab: int
    batch: int
    seq: int
    zipf: float

    def batch_at(self, cursor: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed % (1 << 63), cursor])
        ranks = rng.zipf(self.zipf, size=(self.batch, self.seq))
        return ((ranks - 1) % self.vocab).astype(np.int32)

    def device_batch(self, cursor: int, shardings: dict) -> dict:
        import jax
        return {"tokens": jax.device_put(self.batch_at(cursor),
                                         shardings["tokens"])}


def model_config(cfg: dict):
    """The program's `ModelConfig`: its registry entry with the fields the
    configuration's layout sets."""
    from repro.configs.registry import get_config
    overrides = harness.layout_module(cfg).program_overrides(cfg)
    return dataclasses.replace(get_config(cfg["program_config"]), **overrides)


def build(cell, devices, seed: int):
    """A Trainer on the cell's chips holding the seed's train state."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs.base import ProtectConfig, TrainConfig
    from repro.launch.mesh import make_mesh
    from repro.models import api
    from repro.runtime.trainer import Trainer

    tr, cfg = cell.traffic, cell.config
    mesh = make_mesh((len(devices), 1), devices=devices)
    trainer = Trainer(model_config(cfg), TrainConfig(**tr["optimizer"]),
                      ProtectConfig(**tr["protect"]), mesh,
                      seq_len=tr["seq_len"], global_batch=tr["global_batch"],
                      seed=seed % (1 << 31))
    specs = api.train_state_specs(trainer.model, trainer.optimizer, mesh)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P))
    init = weights.init_state_fn(cfg, shardings)
    want = api.abstract_train_state(trainer.model, trainer.optimizer)
    got = jax.eval_shape(init, weights.seed_key(seed))
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("the benchmark's parameter layout is not the "
                         "program's for this configuration")
    return trainer, init


def tokens(cell, seed: int) -> Tokens:
    """The seed's token stream over the rows of the vocabulary that the
    configuration's layout holds."""
    tr, cfg = cell.traffic, cell.config
    return Tokens(seed, harness.layout_module(cfg).vocab(cfg),
                  tr["global_batch"], tr["seq_len"], tr["token_zipf"])


def start(trainer, init, cell, seed: int) -> None:
    """(Re)arm the pool with the seed's state and the stream at step 0."""
    trainer.stream = tokens(cell, seed)
    trainer.cursor = 0
    trainer.pool.init(init(weights.seed_key(seed)))


def first_steps(trainer, cell, seed: int) -> dict:
    """Run the first `reference_steps` steps through `Trainer.run` and read
    what the comparison needs, each before the next step replaces it."""
    import jax
    cfg, tr = cell.config, cell.traffic
    b1 = tr["optimizer"]["b1"]
    losses = [o["loss"] for o in trainer.run(1)]
    m = jax.device_get(jax.jit(weights.leaf_norms)(
        trainer.prot.state["opt"]["m"]))
    grad = {k: float(x) / (1.0 - b1) for k, x in m.items()}
    losses += [o["loss"] for o in trainer.run(tr["reference_steps"] - 1)]
    dp = jax.device_get(jax.jit(functools.partial(weights.delta_norms, cfg))(
        trainer.prot.state["params"], weights.seed_key(seed)))
    return {"losses": losses, "grad": grad,
            "update": {k: float(x) for k, x in dp.items()}}


def reference_batches(cell, seed: int, rows=None) -> np.ndarray:
    toks = tokens(cell, seed)
    out = np.stack([toks.batch_at(c)
                    for c in range(cell.traffic["reference_steps"])])
    return out if rows is None else out[:, rows]


def reference(cell, seed: int, *, rows=None) -> dict:
    """The plain reference's readings of the same steps."""
    ref = importlib.import_module(f"reference.{cell.config['reference']}")
    return ref.train_readings(cell.config, cell.traffic["optimizer"],
                              weights.seed_key(seed),
                              reference_batches(cell, seed, rows))


# -- faults ---------------------------------------------------------------------

def fault_tools(trainer):
    """(garble, fingerprint), each compiled once.

    garble(state, rank): every word the rank holds XORed with 0xA5A5A5A5,
    as `runtime/failure.inject_rank_loss` does, but as one program whose
    rank is an argument (that function builds a new program per call).
    fingerprint(state): a (ranks,) u32 hash of what each rank holds.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P
    prot = trainer.pool.protector
    mesh, ax, specs = prot.mesh, prot.data_axis, prot.state_specs

    def words(x):
        return lax.bitcast_convert_type(x, jnp.uint32)

    def unwords(w, like):
        return lax.bitcast_convert_type(w, like.dtype)

    def _garble(state, rank):
        me = lax.axis_index(ax)
        return jax.tree.map(
            lambda x: jnp.where(me == rank,
                                unwords(words(x) ^ jnp.uint32(0xA5A5A5A5), x),
                                x), state)

    def _fingerprint(state):
        h = jnp.uint32(0)
        for i, x in enumerate(jax.tree.leaves(state)):
            w = words(x).reshape(-1)
            pos = jnp.arange(w.shape[0], dtype=jnp.uint32)
            mix = (w ^ (pos * jnp.uint32(0x9E3779B1) + jnp.uint32(i))) \
                * jnp.uint32(0x85EBCA77)
            h = h * jnp.uint32(31) + jnp.sum(mix, dtype=jnp.uint32)
        return h[None]

    garble = jax.jit(jax.shard_map(_garble, mesh=mesh,
                                   in_specs=(specs, P()), out_specs=specs,
                                   check_vma=False))
    fingerprint = jax.jit(jax.shard_map(_fingerprint, mesh=mesh,
                                        in_specs=(specs,), out_specs=P(ax),
                                        check_vma=False))
    return garble, fingerprint


def lose_and_recover(trainer, rank: int, garble, fingerprint) -> tuple:
    """One rank loss -> recover -> scrub -> health.  Returns (ms from the
    loss to green health, whether the rank came back verified, scrubbed
    clean and as it was)."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation
    from repro.pool import Fault
    pool = trainer.pool
    before = fingerprint(pool.prot.state)
    with TraceAnnotation("bench.rank_loss"):
        pool.prot = dataclasses.replace(
            pool.prot, state=garble(pool.prot.state, jnp.int32(rank)))
        jax.block_until_ready(pool.prot.state)
    t0 = time.perf_counter()
    with TraceAnnotation("bench.recover"):
        rep = pool.recover(Fault.rank_loss(rank))
    with TraceAnnotation("bench.scrub"):
        scrub = pool.scrub()
    green = pool.health().status == "green"
    ms = (time.perf_counter() - t0) * 1e3
    same = np.array_equal(jax.device_get(before),
                          jax.device_get(fingerprint(pool.prot.state)))
    return ms, bool(rep.verified and rep.reverified and not scrub.suspect
                    and green and same)


def profile_options():
    """Device and host runtime spans; no Python call tracing, which would
    slow the host loop the window measures."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


# -- the run ----------------------------------------------------------------------

def run(cell, devices, seed: int, seconds: float, trace: bool,
        t_process: float) -> tuple:
    """Set-up, window, checks.  Returns (result without checks, checks)."""
    import jax
    from jax.profiler import TraceAnnotation

    tr = cell.traffic
    rng = np.random.default_rng([seed % (1 << 63), 0xFA17])
    chips = len(devices)
    trainer, init = build(cell, devices, seed)
    start(trainer, init, cell, seed)
    readings = first_steps(trainer, cell, seed)
    pool = trainer.pool
    pool.scrub()
    faults_ok, fault_ms = [], []
    garble = fingerprint = None
    if tr["fault_every"]:
        garble, fingerprint = fault_tools(trainer)
        faults_ok.append(lose_and_recover(trainer, int(rng.integers(chips)),
                                          garble, fingerprint)[1])
    trainer.run(1)
    jax.block_until_ready(trainer.prot.state)
    setup_s = time.perf_counter() - t_process

    dispatch0 = pool.stats()["commit_dispatch_ms"]
    steps = committed = 0
    finite = True
    tdir = tempfile.TemporaryDirectory(prefix="bench-trace-") if trace \
        else None
    prof = (jax.profiler.trace(tdir.name, create_perfetto_trace=True,
                               profiler_options=profile_options())
            if trace else contextlib.nullcontext())
    per_call = tr["steps_per_call"]
    if tr["fault_every"] % per_call:
        raise ValueError("fault_every must be a multiple of steps_per_call")
    with prof:
        t0 = time.perf_counter()
        while True:
            with TraceAnnotation("bench.steps"):
                outs = trainer.run(per_call)
            steps += len(outs)
            committed += sum(bool(o["committed"]) for o in outs)
            finite = finite and all(math.isfinite(o["loss"]) for o in outs)
            if tr["fault_every"] and steps % tr["fault_every"] == 0:
                ms, ok = lose_and_recover(trainer, int(rng.integers(chips)),
                                          garble, fingerprint)
                faults_ok.append(ok)
                fault_ms.append(ms)
            if (steps >= tr["trace_steps"] if trace
                    else time.perf_counter() - t0 >= seconds):
                break
        jax.block_until_ready(trainer.prot.state)
        window_s = time.perf_counter() - t0
    dispatch1 = pool.stats()["commit_dispatch_ms"]

    final = pool.scrub()
    protection_faults = int(final.suspect) \
        + int(pool.health().status != "green")
    peak = harness.memory_peak_bytes(devices)
    row_words = sum(math.prod(x.addressable_shards[0].data.shape)
                    for x in jax.tree.leaves(trainer.prot.state))
    block_words = trainer.protect_cfg.block_words
    del trainer, pool, outs, garble, fingerprint
    gc.collect()            # a Trainer and its Pool reference each other

    numbers = check.gaps(readings, reference(cell, seed))
    numbers["failed_steps"] = steps - committed + int(not finite)
    numbers["protection_faults"] = protection_faults
    if tr["fault_every"]:
        numbers["bad_recoveries"] = faults_ok.count(False)
    correct, checks = check.judge(numbers, dict(
        cell.limits, failed_steps=0, protection_faults=0, bad_recoveries=0))

    dev = dict(harness.device_info(devices), memory_peak_bytes=peak)
    result = {"correct": correct, "attempted": steps + len(fault_ms),
              "failed": steps - committed + faults_ok.count(False)}
    if not trace:
        values = {"step_ms": 1e3 * window_s / max(committed, 1),
                  "peak_hbm_gib": peak / 2 ** 30, "setup_s": setup_s}
        if fault_ms:
            values["recover_ms"] = sum(fault_ms) / len(fault_ms)
        metrics = cell.end_to_end
    else:
        tv = traceview.read(next(pathlib.Path(tdir.name)
                                 .rglob("perfetto_trace.json.gz")))
        tdir.cleanup()
        tv.require_programs(tv.rules["model_step_programs"], steps)
        ctx = types.SimpleNamespace(
            cell=cell, trace=tv, window_s=window_s, trace_window_s=window_s,
            steps=committed, chips=chips,
            tokens_per_step=tr["global_batch"] * tr["seq_len"],
            peaks=harness.peaks(dev["kind"]),
            dispatch=_dispatch_delta(dispatch0, dispatch1),
            row_words=row_words, block_words=block_words)
        values = {m["name"]: harness.metric_reader(m["name"]).read(ctx)
                  for m in cell.per_layer}
        metrics = cell.per_layer
        dev.update(busy_s=tv.busy_s(), window_s=window_s)
        result["breakdown"] = {"device_ops": tv.top_ops(10),
                               "idle_gaps": tv.idle_gaps(10)}
    result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                     "unit": m["unit"]}
                         for m in metrics if values.get(m["name"]) is not None}
    result["device"] = dev
    return result, checks


def _dispatch_delta(before: dict, after: dict) -> tuple:
    """(sum ms, count) of the commit dispatches between two summaries of
    the pool's `commit_dispatch_ms` histogram."""
    total = (after["mean"] or 0.0) * after["n"] \
        - (before["mean"] or 0.0) * before["n"]
    return total, after["n"] - before["n"]
