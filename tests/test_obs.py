"""The telemetry plane (repro/obs): registry semantics, span-trace
linkage through live pools on both engines, HealthReport transitions,
the Prometheus golden, and the zero-compiled-byte invariant.

Everything in repro.obs must stay jax-free (the commit path publishes
into it on every transaction); the final test pins that an instrumented
pool compiles the exact program a bare engine compiles.
"""
import numpy as np
import pytest

from repro import obs
from repro.configs.base import ProtectConfig
from repro.obs.export import prometheus_text, write_metrics
from repro.obs.health import CRITICAL, DEGRADED, GREEN, assess
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               default_buckets)
from repro.obs.trace import Tracer, load_jsonl, validate_events
from repro.pool import Fault, Pool
from tests.conftest import small_state


# -- registry / histogram semantics -------------------------------------------


def test_obs_is_jax_free():
    import sys
    import importlib
    for name in ("repro.obs", "repro.obs.metrics", "repro.obs.trace",
                 "repro.obs.health", "repro.obs.export"):
        mod = importlib.import_module(name)
        src = open(mod.__file__).read()
        assert "import jax" not in src, f"{name} imports jax"
    assert "repro.obs.metrics" in sys.modules


def test_histogram_percentile_tracks_numpy():
    """Bucket-interpolated percentiles within one bucket width (~15%,
    the default 8-per-decade spacing) of numpy's exact answer."""
    rng = np.random.default_rng(0)
    samples = rng.lognormal(mean=1.0, sigma=1.2, size=4000)
    h = Histogram()
    for s in samples:
        h.observe(float(s))
    for q in (50, 90, 99):
        exact = float(np.percentile(samples, q))
        est = h.percentile(q)
        assert abs(est - exact) / exact < 0.16, (q, est, exact)
    assert h.count == len(samples)
    assert h.min == pytest.approx(samples.min())
    assert h.max == pytest.approx(samples.max())
    assert h.mean == pytest.approx(samples.mean())


def test_histogram_tight_distribution_clamps_to_extrema():
    h = Histogram()
    for _ in range(10):
        h.observe(7.5)
    # every sample identical: percentiles must not smear across the
    # bucket — the observed-extrema clamp pins them exactly
    assert h.percentile(50) == 7.5
    assert h.percentile(99) == 7.5
    s = h.summary()
    assert s["n"] == 10 and s["min"] == s["max"] == 7.5


def test_histogram_empty_returns_none():
    h = Histogram()
    assert h.percentile(50) is None
    assert h.summary()["p50"] is None and h.summary()["n"] == 0


def test_default_buckets_span_and_spacing():
    edges = default_buckets()
    assert edges[0] == pytest.approx(1e-3)
    assert edges[-1] == pytest.approx(1e5)
    ratios = [b / a for a, b in zip(edges, edges[1:])]
    assert all(r == pytest.approx(10 ** 0.125) for r in ratios)


def test_registry_label_children_and_idempotence():
    reg = MetricsRegistry()
    full = reg.counter("scrub_runs_total", kind="full")
    pre = reg.counter("scrub_runs_total", kind="precheck")
    full.inc(3)
    pre.inc()
    assert full is not pre
    assert reg.counter("scrub_runs_total", kind="full") is full
    snap = reg.snapshot()
    assert snap["scrub_runs_total"] == {"kind=full": 3.0,
                                        "kind=precheck": 1.0}
    with pytest.raises(AssertionError):
        reg.gauge("scrub_runs_total", kind="full")   # type collision
    with pytest.raises(AssertionError):
        full.inc(-1)                                 # monotone


# -- Prometheus exposition -----------------------------------------------------


def test_prometheus_text_golden():
    reg = MetricsRegistry()
    reg.counter("pool_commits_total").inc(42)
    reg.gauge("pool_window", engine="deferred").set(4)
    h = reg.histogram("wall_ms", buckets=[1.0, 10.0], kind="full")
    for v in (0.5, 2.0, 3.0, 99.0):
        h.observe(v)
    assert prometheus_text(reg) == (
        "# TYPE pool_commits_total counter\n"
        "pool_commits_total 42\n"
        "# TYPE pool_window gauge\n"
        'pool_window{engine="deferred"} 4\n'
        "# TYPE wall_ms histogram\n"
        'wall_ms_bucket{kind="full",le="1"} 1\n'
        'wall_ms_bucket{kind="full",le="10"} 3\n'
        'wall_ms_bucket{kind="full",le="+Inf"} 4\n'
        'wall_ms_sum{kind="full"} 104.5\n'
        'wall_ms_count{kind="full"} 4\n')


def test_write_metrics_files(tmp_path):
    reg = MetricsRegistry()
    reg.counter("c").inc()
    paths = write_metrics(reg, str(tmp_path), prefix="pool",
                          stats={"mode": "mlpc"})
    assert open(paths["prom"]).read().endswith("c 1\n")
    import json
    assert json.load(open(paths["stats"]))["mode"] == "mlpc"


# -- tracer / validation -------------------------------------------------------


def test_tracer_span_linkage_and_jsonl(tmp_path):
    tr = Tracer(str(tmp_path / "t.jsonl"))
    fid = tr.emit("fault", fault_kind="rank_loss", lost_rank=2)
    with tr.span("recovery", faults=[fid]) as sp:
        sp.annotate(verified=True)
    assert validate_events(tr.events) == []
    tr.close()
    disk = load_jsonl(str(tmp_path / "t.jsonl"))
    assert disk == tr.events
    assert disk[1]["faults"] == [fid] and disk[2]["verified"] is True
    assert [e["ev"] for e in disk] == ["point", "begin", "end"]


def test_validate_events_catches_violations():
    tr = Tracer()
    tr.emit("fault")                      # id 0, never linked
    tr.begin("recovery", faults=[7])      # orphan link + dangling span
    bad = validate_events(tr.events)
    assert any("never linked" in b for b in bad)
    assert any("never ended" in b for b in bad)
    assert any("orphan" in b for b in bad)


def test_span_exception_records_error():
    tr = Tracer()
    with pytest.raises(ValueError):
        with tr.span("recovery", faults=[]):
            raise ValueError("boom")
    assert tr.events[-1]["error"] == "ValueError"
    assert validate_events(tr.events) == []


def test_tracer_profiler_sink_and_hot_events(tmp_path):
    opened = []

    class Mark:
        def __init__(self, name, **ids):
            self.name, self.ids = name, ids

        def __enter__(self):
            opened.append(("enter", self.name, self.ids))

        def __exit__(self, *exc):
            opened.append(("exit", self.name, self.ids))

    bare = Tracer()
    with bare.phase("trainer.dispatch", step=3):
        pass                            # no factory: a no-op
    assert bare.events == [] and opened == []
    assert bare.emit_hot("commit_dispatch", seq=0) == 0
    assert bare.emit_hot("commit_dispatch", seq=1) == 1
    assert bare.events == []            # ids taken, nothing kept
    assert bare.emit("fault") == 2

    tr = Tracer(str(tmp_path / "t.jsonl"), annotation=Mark)
    with tr.phase("trainer.dispatch", step=3):
        pass
    assert tr.events == []              # a phase writes no event
    with tr.span("scrub", scope="full"):
        pass
    sid = tr.events[0]["id"]
    assert opened == [("enter", "trainer.dispatch", {"step": 3}),
                      ("exit", "trainer.dispatch", {"step": 3}),
                      ("enter", "pool.scrub", {"id": sid}),
                      ("exit", "pool.scrub", {"id": sid})]
    hot = tr.emit_hot("commit_dispatch", seq=0)
    tr.close()
    assert load_jsonl(str(tmp_path / "t.jsonl"))[-1]["id"] == hot
    assert validate_events(tr.events) == []


# -- live pools: trace linkage on both engines x stack heights ----------------


@pytest.mark.parametrize("window,red", [(1, 1), (1, 3), (4, 1), (4, 3)])
def test_pool_trace_links_fault_to_recovery(mesh42, window, red):
    import jax
    from repro.runtime import failure
    state, specs, _ = small_state(mesh42)
    pool = Pool.open(state, specs, mesh=mesh42,
                     config=ProtectConfig(mode="mlpc", redundancy=red,
                                          window=window, block_words=64),
                     donate=False)
    cur = state
    for i in range(3):
        cur = jax.tree.map(lambda x: (x * 1.01).astype(x.dtype), cur)
        pool.commit(cur, rng_key=jax.random.PRNGKey(i))
    ev = pool.inject(lambda p, prot: failure.seeded_rank_loss(
        p, prot, seed=0, rank=1))
    rep = pool.recover(Fault.from_event(ev))
    assert rep.verified and rep.reverified
    assert rep.solve_ms >= 0 and rep.total_ms >= rep.solve_ms
    events = pool.tracer.events
    assert validate_events(events) == []
    faults = [e for e in events if e.get("kind") == "fault"]
    spans = [e for e in events
             if e["ev"] == "begin" and e["kind"] == "recovery"]
    assert len(faults) == 1 and len(spans) == 1
    assert spans[0]["faults"] == [faults[0]["id"]]
    end = [e for e in events
           if e["ev"] == "end" and e["id"] == spans[0]["id"]][0]
    assert end["recovery_kind"] == "rank_loss" and end["verified"]
    st = pool.stats()
    assert st["commits"] == 3 and st["recoveries"] == 1
    assert st["commit_dispatch_ms"]["n"] == 3
    assert st["metrics"]["pool_recoveries_total"]["kind=rank_loss"] == 1


# -- scrub coverage accounting (the satellite fix) ----------------------------


def test_scrub_coverage_exact_across_precheck_only_cycles(mesh42):
    state, specs, _ = small_state(mesh42)
    pool = Pool.open(state, specs, mesh=mesh42,
                     config=ProtectConfig(mode="mlpc", window=1,
                                          block_words=64),
                     donate=False)
    sc = pool.scrubber
    pages = sc.pool_pages
    assert pages > 0
    pool.precheck()
    pool.precheck()
    pool.scrub()
    cov = sc.coverage()
    # exact accounting: 2 prechecks (digest pass over every page) + 1
    # full scrub (syndrome verify over every page)
    assert cov["prechecks"] == 2 and cov["full_scrubs"] == 1
    assert cov["pages_checked"] == 3 * pages
    assert cov["pages_syndrome_verified"] == pages
    assert cov["full_fraction"] == pytest.approx(1 / 3)
    assert pool.stats()["scrub"] == cov
    assert pool.health().status == GREEN


# -- HealthReport transitions --------------------------------------------------


def _base_signals(**over):
    kw = dict(window=4, max_window=4, dropped_replicas=[], suspect=False,
              redundancy=2, budget_exhausted=False, scrub_coverage=None,
              unrepaired_pages=0, reverify_failed=False, recoveries=0,
              recovery_followups=0)
    kw.update(over)
    return kw


def test_assess_transitions_pure():
    assert assess(**_base_signals()).status == GREEN
    r = assess(**_base_signals(dropped_replicas=[2]))
    assert r.status == DEGRADED and "straggler" in r.reasons[0]
    assert assess(**_base_signals(window=1)).status == DEGRADED
    assert assess(**_base_signals(suspect=True)).status == DEGRADED
    r = assess(**_base_signals(budget_exhausted=True))
    assert r.status == CRITICAL and r.budget_remaining == 0
    assert assess(**_base_signals(reverify_failed=True)).status == CRITICAL
    assert assess(**_base_signals(unrepaired_pages=3)).status == CRITICAL
    # critical outranks degraded when both fire
    r = assess(**_base_signals(dropped_replicas=[1],
                               budget_exhausted=True))
    assert r.status == CRITICAL and len(r.reasons) == 2
    assert r.to_dict()["status"] == CRITICAL


def test_pool_health_straggler_drop_and_heal(mesh42):
    import jax
    from repro.dist.straggler import StragglerPolicy
    state, specs, _ = small_state(mesh42)
    pool = Pool.open(state, specs, mesh=mesh42,
                     config=ProtectConfig(mode="mlpc", window=4,
                                          block_words=64),
                     donate=False,
                     straggler_policy=StragglerPolicy(4, threshold=2.0,
                                                      window=2))
    assert pool.health().status == GREEN
    slow = [0.01, 0.06, 0.01, 0.01]
    for _ in range(2):
        pool.commit(state, rng_key=jax.random.PRNGKey(0))
        pool.observe_commit_times(slow)
    rep = pool.health()
    assert rep.status == DEGRADED
    assert rep.dropped_replicas == [1]
    assert any("straggler" in r for r in rep.reasons)
    assert pool.stats()["metrics"]["pool_straggler_drop_total"][""] == 1
    # heal: normal observations push the slow samples out of the window
    for _ in range(2):
        pool.observe_commit_times([0.01] * 4)
    assert pool.health().dropped_replicas == []
    assert pool.stats()["metrics"]["pool_straggler_heal_total"][""] == 1


def test_pool_health_budget_exhaust_and_rearm(mesh42):
    import jax
    from repro.runtime import failure
    state, specs, _ = small_state(mesh42)
    pool = Pool.open(state, specs, mesh=mesh42,
                     config=ProtectConfig(mode="mlpc", redundancy=1,
                                          window=1, block_words=64),
                     donate=False)
    ev = pool.inject(lambda p, prot: failure.seeded_multi_rank_loss(
        p, prot, seed=0, e=2))
    with pytest.raises(RuntimeError, match="syndrome budget exhausted"):
        pool.recover(Fault.from_event(ev))
    rep = pool.health()
    assert rep.status == CRITICAL and rep.budget_exhausted
    assert rep.budget_remaining == 0
    assert any("budget" in r for r in rep.reasons)
    # the raise happened inside the recovery span: trace stays valid and
    # the fault ids are still linked (begin carries them)
    assert validate_events(pool.tracer.events) == []
    assert pool.tracer.events[-1]["error"] == "RuntimeError"
    # re-arm (checkpoint-tier restore path): fresh protection clears it
    pool.init(state)
    assert pool.health().status == GREEN
    assert pool.stats()["metrics"]["pool_budget_exhausted_total"][""] == 1


def test_pool_recovery_then_clean_scrub_heals_suspicion(mesh42):
    import jax
    from repro.runtime import failure
    state, specs, _ = small_state(mesh42)
    pool = Pool.open(state, specs, mesh=mesh42,
                     config=ProtectConfig(mode="mlpc", window=1,
                                          block_words=64),
                     donate=False)
    ev = pool.inject(lambda p, prot: failure.seeded_rank_loss(
        p, prot, seed=0, rank=2))
    pool.recover(Fault.from_event(ev))
    rep = pool.health()
    assert rep.status == DEGRADED and rep.suspect
    report = pool.scrub()
    assert report.checked and not report.suspect
    assert pool.health().status == GREEN


# -- the zero-compiled-byte invariant -----------------------------------------


def test_instrumented_pool_compiles_identical_bytes(mesh42):
    """A wired registry/tracer must not change the commit program: the
    facade-routed program and the bare protector's compile to the same
    XLA bytes accessed (the benchmark gates this for both engines; the
    sync engine's check is cheap enough to pin in tier-1)."""
    import jax
    state, specs, _ = small_state(mesh42)
    pool = Pool.open(state, specs, mesh=mesh42,
                     config=ProtectConfig(mode="mlpc", window=1,
                                          block_words=64),
                     donate=False)
    new_state = jax.tree.map(lambda x: (x * 1.01).astype(x.dtype), state)
    key = jax.random.PRNGKey(0)

    def bytes_of(fn):
        cost = fn.lower(pool.prot, new_state,
                        rng_key=key).compile().cost_analysis()
        if isinstance(cost, list):
            cost = cost[0]
        return float(cost.get("bytes accessed", 0.0))

    instr = bytes_of(pool.commit_program())
    bare = bytes_of(jax.jit(pool.protector.make_commit()))
    assert instr == bare


# -- public surface ------------------------------------------------------------


def test_obs_reexports():
    assert obs.MetricsRegistry is MetricsRegistry
    assert obs.Tracer is Tracer
    assert obs.validate_events is validate_events
    assert {obs.GREEN, obs.DEGRADED, obs.CRITICAL} == {
        "green", "degraded", "critical"}
    import repro
    assert repro.MetricsRegistry is MetricsRegistry
    assert repro.HealthReport is obs.HealthReport
