"""The chaos campaign: named scenarios over the ScenarioRunner.

Each builder returns (workload, schedule, n_steps); `run_scenario`
executes one and `campaign` runs the whole set, which is what
benchmarks/chaos.py records into BENCH_commit.json §chaos and
scripts/bench_gate.py gates.  Every scenario ends with the golden
bit-identity check — chaos may cost latency, never bytes.

All scenarios run on the 8 host devices the benchmarks/tests force;
meshes are (4, 2) or (8, 1) so both zone geometries (G=4, G=8) see
traffic.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Callable, Dict, Optional, Tuple

import jax

from repro.chaos.runner import ScenarioRunner
from repro.chaos.schedule import ChaosEvent, FaultSchedule
from repro.chaos.workload import PoolWorkload
from repro.configs.base import ProtectConfig
from repro.launch.mesh import make_mesh
from repro.obs import Tracer, validate_events

E = ChaosEvent.make


def _mesh(shape=(4, 2)):
    return make_mesh(shape)


def _cfg(**kw) -> ProtectConfig:
    base = dict(mode="mlpc", window=4, redundancy=2, scrub_period=0)
    base.update(kw)
    return ProtectConfig(**base)


# -- builders: name -> (workload, schedule, n_steps) --------------------------


def rescale_under_traffic(quick: bool, seed: int):
    """Elastic (4,2) -> (8,1) -> (4,2) while commits keep flowing, with
    a rank loss landing right after the first rescale settles."""
    n = 24 if quick else 60
    wl = PoolWorkload(_mesh((4, 2)), _cfg(), n_bytes=1 << 15, seed=seed)
    sched = FaultSchedule([
        E(n // 4, "rescale", shape=(8, 1)),
        E(n // 4 + 2, "rank_loss"),
        E(n // 2, "rescale", shape=(4, 2)),
    ], seed=seed)
    return wl, sched, n


def straggler(quick: bool, seed: int):
    """One replica runs 6x slow mid-run: the policy drops it, the
    adaptive window collapses while degraded, and regrows after the
    replica heals."""
    from repro.dist.straggler import StragglerPolicy
    n = 36 if quick else 80
    cfg = _cfg(window=8, straggler_threshold=2.0,
               window_growth_commits=4)
    mesh = _mesh((4, 2))
    policy = StragglerPolicy(mesh.shape["data"], threshold=2.0,
                             window=4)
    wl = PoolWorkload(mesh, cfg, n_bytes=1 << 15, seed=seed,
                      straggler_policy=policy)
    sched = FaultSchedule([
        E(n // 4, "straggler_start", rank=1, factor=6.0),
        E(n // 2, "straggler_stop"),
    ], seed=seed)
    return wl, sched, n


def midwindow_scribble_loss(quick: bool, seed: int):
    """A scribble on one rank concurrent with another rank's loss,
    both landing INSIDE an open window — the overlap single parity
    cannot untangle; the r=2 syndrome stack solves both as losses."""
    n = 20 if quick else 48
    wl = PoolWorkload(_mesh((4, 2)), _cfg(window=8), n_bytes=1 << 15,
                      seed=seed)
    sched = FaultSchedule([
        E(n // 2, "scribble", mid_window=True, rank=0, n_words=6),
        E(n // 2, "rank_loss", mid_window=True, rank=2),
    ], seed=seed)
    return wl, sched, n


def budget_exhaust_rearm(quick: bool, seed: int):
    """Back-to-back losses beyond the stack: e=2 on an r=1 pool raises
    the budget-exhausted error, the runner restores + replays from the
    snapshot tier, and a later single loss again recovers online."""
    n = 24 if quick else 48
    wl = PoolWorkload(_mesh((4, 2)), _cfg(redundancy=1, window=2),
                      n_bytes=1 << 15, seed=seed)
    sched = FaultSchedule([
        E(n // 4, "snapshot"),
        E(n // 3, "multi_loss", e=2),           # e > r: exhausted
        E(2 * n // 3, "rank_loss"),             # re-armed: online again
    ], seed=seed)
    return wl, sched, n


def crash_replay_storm(r: int, window: int):
    """One storm cell: an e=r loss (the stack's full budget) plus a
    mid-window single loss, at syndrome height r and window W."""
    def build(quick: bool, seed: int):
        n = 16 if quick else 40
        g = 8 if r >= 4 else 4          # r <= G - 1
        shape = (8, 1) if g == 8 else (4, 2)
        wl = PoolWorkload(_mesh(shape),
                          _cfg(redundancy=r, window=window),
                          n_bytes=1 << 15, seed=seed)
        events = [E(n // 3, "rank_loss", mid_window=(window > 1))]
        if r >= 2:
            events.append(E(2 * n // 3, "multi_loss", e=r))
        return wl, FaultSchedule(events, seed=seed), n
    return build


def multi_tenant_interference(quick: bool, seed: int,
                              trace_dir: Optional[str] = None) -> dict:
    """Interference under multi-tenancy: a PoolGroup of four same-cohort
    tenants commits batched waves while tenant 0 is scribbled, put
    through a quarantined recovery, and the shared scrub scheduler
    keeps one-pool-per-wave verification pressure on the whole group.
    The neighbors must (a) end bit-identical to a fault-free reference
    group run (chaos costs latency, never bytes — for ANY tenant) and
    (b) keep committing through the victim's quarantine window.  The
    result carries baseline-vs-interference wave latency for the
    benchmark tier; the golden check is what campaigns gate on.
    """
    import time as _time

    import numpy as np

    from repro.pool import Fault
    from repro.runtime import failure
    from repro.tenancy import PoolGroup

    n = 24 if quick else 60
    n_t = 4
    mesh = _mesh((4, 2))
    cfg = _cfg(window=1)                      # sync: one dispatch/wave
    step_fn = jax.jit(lambda s, c: {"w": s["w"] * 1.0000001 + c})

    def build_group(tracer=None):
        grp = PoolGroup(mesh, scrub_page_budget=0,
                        tracer=tracer if tracer is not None else None)
        states = {}
        for t in range(n_t):
            wl = PoolWorkload(mesh, cfg, n_bytes=1 << 14,
                              seed=seed + 13 * t)
            states[f"t{t}"] = wl.pool.state
            grp.admit(f"t{t}", wl.pool.state, wl.specs, config=cfg)
        return grp, states

    tracer = None
    if trace_dir:
        os.makedirs(trace_dir, exist_ok=True)
        tracer = Tracer(os.path.join(
            trace_dir, "multi_tenant_interference.trace.jsonl"))
    grp, states = build_group(tracer)
    ref, ref_states = build_group()

    def wave(g, st, i, interfere: bool) -> float:
        ups = {tid: step_fn(g[tid].pool.state,
                            jax.numpy.float32((i % 7) * 1e-6))
               for tid in st}
        t0 = _time.perf_counter()
        g.commit(ups, data_cursor=i)
        jax.block_until_ready(g["t1"].pool.prot.state)
        wall = (_time.perf_counter() - t0) * 1e3
        if interfere:
            budget = g["t0"].pool.scrubber.pool_pages
            g.scrub_tick(page_budget=budget)
        return wall

    base_ms, intf_ms, recoveries = [], [], []
    for i in range(n):
        interfere = n // 3 <= i < 2 * n // 3
        (intf_ms if interfere else base_ms).append(
            wave(grp, states, i, interfere))
        wave(ref, ref_states, i, False)
        if i == n // 3:
            # scribble t0 mid-campaign; the quarantined recovery runs
            # while the other three tenants' traffic keeps flowing
            grp["t0"].pool.inject(
                lambda p, pr: failure.inject_scribble(
                    p, pr, rank=1, word_offsets=range(6)))
            t_r = _time.perf_counter()
            rep = grp.recover("t0", Fault.scribble(1, [0]))
            recoveries.append({
                "kind": "scribble", "tenant": "t0",
                "verified": bool(rep.verified),
                "ms": (_time.perf_counter() - t_r) * 1e3})

    golden = True
    for tid in states:
        a = jax.device_get(grp[tid].pool.state)
        b = jax.device_get(ref[tid].pool.state)
        golden &= all(
            np.array_equal(np.asarray(x), np.asarray(y))
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))

    def _pct(xs, q):
        return float(np.percentile(np.asarray(xs), q)) if xs else None

    rec_ms = [r["ms"] for r in recoveries]
    # the campaign-standard result shape (benchmarks/chaos.py _row and
    # the §chaos gate consume it uniformly): clean = waves with no
    # scrub-storm pressure, during = waves inside the storm+quarantine
    # interference window
    out = {
        "scenario": "multi_tenant_interference",
        "golden_exact": bool(golden),
        "steps": n,
        "events": len(recoveries),
        "r": cfg.redundancy,
        "window": cfg.window,
        "tenants": n_t,
        "quarantined_during_run": True,
        "commit_ms": {
            "clean": {"p50_ms": _pct(base_ms, 50),
                      "p99_ms": _pct(base_ms, 99)},
            "during": {"p50_ms": _pct(intf_ms, 50),
                       "p99_ms": _pct(intf_ms, 99)}},
        "recovery_ms": {"p50_ms": _pct(rec_ms, 50),
                        "p99_ms": _pct(rec_ms, 99)},
        "recoveries": recoveries,
        "scheduler": grp.scheduler.stats(),
        "health": grp.health(),
    }
    if tracer is not None:
        out["trace"] = {"path": tracer.path,
                        "events": len(tracer.events),
                        "violations": validate_events(tracer.events)}
        tracer.close()
    return out


def fault_with_inflight_commits(quick: bool, seed: int,
                                trace_dir: Optional[str] = None) -> dict:
    """Faults landing while the commit ring holds unresolved tickets.

    The async pipeline (PR 10) dispatches commit t+k before commit t's
    verdict resolves; this scenario injects a rank loss with k=2
    tickets in flight and a scribble with k=depth (a full ring) in
    flight, on a deferred-window pool at pipeline_depth=4.  Recovery
    must (a) drain the ring deterministically — every in-flight ticket
    resolves, in dispatch order, before reconstruction touches the
    state — and (b) end golden-exact against a fault-free reference
    that resolved every commit synchronously: the pipeline may only
    ever reorder verdict *fetches*, never commit effects.
    """
    import time as _time

    import jax.numpy as jnp
    import numpy as np

    from repro.pool import Fault
    from repro.runtime import failure

    n = 24 if quick else 60
    depth = 4
    mesh = _mesh((4, 2))
    cfg = _cfg(window=4, pipeline_depth=depth)
    wl = PoolWorkload(mesh, cfg, n_bytes=1 << 15, seed=seed)
    ref = PoolWorkload(mesh, cfg, n_bytes=1 << 15, seed=seed)

    tracer = None
    if trace_dir:
        os.makedirs(trace_dir, exist_ok=True)
        tracer = Tracer(os.path.join(
            trace_dir, "fault_with_inflight_commits.trace.jsonl"))
        wl.pool.set_tracer(tracer)

    def dispatch_async(w) -> tuple:
        """One commit of traffic dispatched through the ring — the
        verdict stays unresolved (PoolWorkload.traffic_step's async
        twin; same state recurrence, so golden comparison holds)."""
        new_state = w._step_fn(w.pool.state, jnp.float32(w.bias(w.t)))
        t0 = _time.perf_counter()
        tkt = w.pool.commit_async(new_state, data_cursor=w.t)
        wall = (_time.perf_counter() - t0) * 1e3
        w.t += 1
        return tkt, wall

    # fault step -> (fault kind, tickets to leave unresolved at injection)
    inflight_at = {n // 3: ("rank_loss", 2),
                   2 * n // 3: ("scribble", depth)}
    tickets, recoveries = [], []
    base_ms, during_ms = [], []
    hot = set()                      # steps whose dispatch rode a recovery
    for f in inflight_at:
        hot.update(range(f, min(f + 3, n)))
    i = 0
    while i < n:
        if i in inflight_at:
            kind, k = inflight_at[i]
            # build EXACTLY k unresolved tickets: drain to empty, then
            # dispatch k commits without touching a verdict
            wl.pool.drain()
            burst = []
            for _ in range(k):
                tkt, wall = dispatch_async(wl)
                ref.traffic_step()
                burst.append(tkt)
                during_ms.append(wall)
                i += 1
            assert wl.pool.in_flight == k, (wl.pool.in_flight, k)
            if kind == "rank_loss":
                wl.pool.inject(lambda p, pr: failure.inject_rank_loss(
                    p, pr, rank=1))
                fault = Fault.rank_loss(1)
            else:
                wl.pool.inject(lambda p, pr: failure.inject_scribble(
                    p, pr, rank=2, word_offsets=range(6)))
                fault = Fault.scribble(2, [0])
            t_r = _time.perf_counter()
            rep = wl.pool.recover(fault)
            rec_wall = (_time.perf_counter() - t_r) * 1e3
            # the recovery boundary drained the ring: every ticket the
            # fault caught in flight resolved, deterministically True
            # (the commits themselves were clean — only the state was
            # corrupted afterwards)
            assert all(t.resolved for t in burst), \
                "recovery left tickets unresolved"
            assert all(t.result() for t in burst)
            assert wl.pool.in_flight == 0
            recoveries.append({
                "kind": kind, "inflight_at_fault": k,
                "verified": bool(rep.verified), "ms": rec_wall})
            tickets += burst
        else:
            tkt, wall = dispatch_async(wl)
            ref.traffic_step()
            (during_ms if i in hot else base_ms).append(wall)
            tickets.append(tkt)
            i += 1
    wl.pool.drain()
    wl.pool.flush()
    ref.pool.flush()
    assert all(t.resolved and t.result() for t in tickets)

    golden = all(
        np.array_equal(np.asarray(jax.device_get(a)),
                       np.asarray(jax.device_get(b)))
        for a, b in zip(jax.tree.leaves(wl.pool.state),
                        jax.tree.leaves(ref.pool.state)))

    def _pct(xs, q):
        return float(np.percentile(np.asarray(xs), q)) if xs else None

    rec_ms = [r["ms"] for r in recoveries]
    out = {
        "scenario": "fault_with_inflight_commits",
        "golden_exact": bool(golden),
        "steps": n,
        "events": len(recoveries),
        "r": cfg.redundancy,
        "window": cfg.window,
        "pipeline_depth": depth,
        "commit_ms": {
            "clean": {"p50_ms": _pct(base_ms, 50),
                      "p99_ms": _pct(base_ms, 99)},
            "during": {"p50_ms": _pct(during_ms, 50),
                       "p99_ms": _pct(during_ms, 99)}},
        "recovery_ms": {"p50_ms": _pct(rec_ms, 50),
                        "p99_ms": _pct(rec_ms, 99)},
        "recoveries": recoveries,
        "health": wl.pool.health().to_dict(),
    }
    if tracer is not None:
        out["trace"] = {"path": tracer.path,
                        "events": len(tracer.events),
                        "violations": validate_events(tracer.events)}
        tracer.close()
    return out


SCENARIOS: Dict[str, Callable] = {
    "rescale_under_traffic": rescale_under_traffic,
    "straggler": straggler,
    "midwindow_scribble_loss": midwindow_scribble_loss,
    "budget_exhaust_rearm": budget_exhaust_rearm,
}

# group scenarios run their own loop (a PoolGroup is not a single-pool
# workload) but return the same result-dict shape the campaign gates
GROUP_SCENARIOS: Dict[str, Callable] = {
    "multi_tenant_interference": multi_tenant_interference,
    "fault_with_inflight_commits": fault_with_inflight_commits,
}

# the storm matrix is bench-only by default (r x W cells); the four
# named scenarios above are the gated core set
STORM_CELLS: Tuple[Tuple[int, int], ...] = (
    (1, 1), (2, 16), (3, 16), (4, 16))


def _run(wl, sched, n: int, name: str,
         trace_dir: Optional[str]) -> dict:
    """Execute one built scenario, optionally with a file-backed trace.

    With `trace_dir`, the workload's pool emits every fault/recovery/
    scrub/rescale event into <trace_dir>/<name>.trace.jsonl, and the
    result carries the trace's validation verdict (obs.validate_events
    — the same check scripts/trace_check.py runs offline): a campaign
    whose trace does not link every fault to its recovery is reported
    broken right where it ran.
    """
    tracer = None
    if trace_dir:
        os.makedirs(trace_dir, exist_ok=True)
        tracer = Tracer(os.path.join(trace_dir,
                                     f"{name}.trace.jsonl"))
        wl.pool.set_tracer(tracer)
    out = ScenarioRunner(wl, sched).run(n)
    out["scenario"] = name
    if tracer is not None:
        out["trace"] = {"path": tracer.path,
                        "events": len(tracer.events),
                        "violations": validate_events(tracer.events)}
        tracer.close()
    return out


def run_scenario(name: str, *, quick: bool = True, seed: int = 0,
                 trace_dir: Optional[str] = None) -> dict:
    if name in GROUP_SCENARIOS:
        return GROUP_SCENARIOS[name](quick, seed, trace_dir)
    wl, sched, n = SCENARIOS[name](quick, seed)
    return _run(wl, sched, n, name, trace_dir)


def run_storm_cell(r: int, window: int, *, quick: bool = True,
                   seed: int = 0,
                   trace_dir: Optional[str] = None) -> dict:
    wl, sched, n = crash_replay_storm(r, window)(quick, seed)
    return _run(wl, sched, n, f"storm_r{r}_w{window}", trace_dir)


def campaign(*, quick: bool = True, seed: int = 0,
             storms: bool = True,
             trace_dir: Optional[str] = None) -> list:
    """The full campaign: the four core scenarios plus the storm
    matrix.  Raises if any scenario fails the golden bit-identity
    check — a chaos campaign whose end state drifted measured nothing —
    or (with `trace_dir`) emits a trace that fails validation.
    """
    results = [run_scenario(name, quick=quick, seed=seed,
                            trace_dir=trace_dir)
               for name in (*SCENARIOS, *GROUP_SCENARIOS)]
    if storms:
        cells = STORM_CELLS[:2] if quick else STORM_CELLS
        results += [run_storm_cell(r, w, quick=quick, seed=seed,
                                   trace_dir=trace_dir)
                    for r, w in cells]
    bad = [r["scenario"] for r in results if not r.get("golden_exact")]
    if bad:
        raise AssertionError(
            f"chaos scenarios ended non-golden: {bad} — recovered "
            "state must be bit-identical to the fault-free run")
    broken = [r["scenario"] for r in results
              if r.get("trace", {}).get("violations")]
    if broken:
        raise AssertionError(
            f"chaos traces failed validation: {broken} — every fault "
            "must link to the recovery span that resolved it")
    return results
