"""`Pool` — the Pangolin-style front door to the whole protection stack.

Pangolin's value proposition is a *small* persistent-object API
(`pgl_open` / `pgl_tx_begin` / `pgl_tx_commit`) that hides checksums,
parity, micro-buffering and recovery behind three calls.  This module is
that surface for the reproduction: one facade that owns the engine
choice (synchronous single-sweep vs deferred-epoch), the scrubber
pressure loop, window-meta replication, and every recovery path, so
callers never touch `Protector` / `DeferredProtector` / `Scrubber`
plumbing directly.

pgl -> Pool mapping (paper §3, Listing 2):

    ================  =============================================
    Pangolin          this library
    ================  =============================================
    pgl_open          Pool.open(state, specs, mesh=..., config=...)
    pgl_begin/commit  with pool.transaction() as tx: tx.stage(new)
                      (or pool.commit(new, ...) directly)
    pgl_tx_abort      canary mismatch / exception inside the context
    scrubbing thread  pool.maybe_scrub() on the commit cadence
                      (pool.scrub() forces one)
    SIGBUS handler    pool.recover(Fault.rank_loss(r))
    corruption repair pool.recover(Fault.scribble(rank, pages))
    (beyond paper)    pool.recover(Fault.multi_loss(*ranks)) — any
                      e <= redundancy simultaneous losses via the
                      Reed-Solomon syndrome stack
    pool resize       pool.rescale(new_mesh)
    ================  =============================================

Protection-mode ladder (paper Table 2), selected by `ProtectConfig`:
`none < ml < mlp < mlpc` plus `replica` (2x baseline); `redundancy`
r ∈ {1..4} stacks r Reed-Solomon syndromes onto the parity modes
(the legacy `mlp2`/`mlpc2` names alias redundancy=2).
`config.window` selects the engine: 1 = the
synchronous single-sweep commit, W>1 = the deferred-epoch engine whose
parity/checksum refresh amortizes over W commits.  The facade routes
both through the same jit caches as direct engine use, so a
`Pool`-routed commit is bit-identical to — and compiles the very same
program as — a hand-wired one (asserted in tests/test_pool.py).

`Protector` and `DeferredProtector` stay importable as the low-level
layer; `Pool` is the contract new subsystems plug into.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ProtectConfig
from repro.core import microbuffer
from repro.core import recovery as recovery_mod
from repro.core.epoch import DeferredProtector, EngineHost
from repro.core.pipeline import CommitRing, CommitTicket
from repro.core.scrub import ScrubReport, Scrubber
from repro.core.txn import Mode, ProtectedState, Protector
from repro.kernels import ops as kops
from repro.dist import elastic
from repro.dist.straggler import StragglerPolicy
from repro.obs import health as obs_health
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import Tracer

PyTree = Any


def _is_abstract(state: PyTree) -> bool:
    leaves = jax.tree.leaves(state)
    return bool(leaves) and all(
        isinstance(l, jax.ShapeDtypeStruct) for l in leaves)


@dataclasses.dataclass(frozen=True)
class Fault:
    """One recovery request — the argument to `Pool.recover`.

    Constructors mirror the failure taxonomy (runtime/failure.py):

        Fault.rank_loss(r)         one data-rank's row lost (media error)
        Fault.multi_loss(*ranks)   e ranks lost at once (needs
                                   redundancy >= e syndromes)
        Fault.double_loss(a, b)    the e=2 alias
        Fault.scribble(rank, pages) silent corruption at (rank, page)s
        Fault.from_event(event)    adapt a runtime FailureEvent
    """
    kind: str                                   # rank_loss | multi_loss
                                                # | scribble
    rank: Optional[int] = None                  # rank_loss
    ranks: Optional[Tuple[int, ...]] = None     # multi_loss
    locations: Optional[Tuple[Tuple[int, int], ...]] = None  # scribble

    @staticmethod
    def rank_loss(rank: int) -> "Fault":
        return Fault("rank_loss", rank=int(rank))

    @staticmethod
    def multi_loss(*ranks: int) -> "Fault":
        dead = tuple(sorted(int(r) for r in ranks))
        if len(set(dead)) != len(dead) or len(dead) < 2:
            raise ValueError(
                f"multi loss needs >= 2 distinct ranks, got {ranks}")
        return Fault("multi_loss", ranks=dead)

    @staticmethod
    def double_loss(a: int, b: int) -> "Fault":
        return Fault.multi_loss(a, b)

    @staticmethod
    def scribble(rank: int, pages: Sequence[int]) -> "Fault":
        return Fault("scribble",
                     locations=tuple((int(rank), int(p)) for p in pages))

    @classmethod
    def from_event(cls, event) -> "Fault":
        """Adapt a runtime/failure.py FailureEvent (duck-typed)."""
        if event.kind == "rank_loss":
            return cls.rank_loss(event.lost_rank)
        if event.kind in ("multi_loss", "double_loss"):
            return cls.multi_loss(*event.lost_ranks)
        if event.kind == "scribble":
            return cls("scribble",
                       locations=tuple((int(r), int(p))
                                       for r, p in event.locations))
        raise ValueError(f"no recovery path for fault kind {event.kind!r}")


class Transaction:
    """`pgl_tx_begin .. pgl_tx_commit` as a context manager.

    Stage the micro-buffered update with `stage(new_state)`; register
    canary-guarded staging buffers (microbuffer.guard/guard_nd) with
    `watch(...)`.  On exit the canaries are verified host-side and the
    staged state commits through the pool — a smashed canary (or an
    explicit `abort()`) aborts the transaction without touching
    protected state, exactly like `commit(..., canary_ok=False)`.  An
    exception inside the block also aborts (nothing is committed) and
    propagates.
    """

    def __init__(self, pool: "Pool", *, data_cursor=0, rng_key=None,
                 pages: Optional[Sequence[int]] = None):
        self._pool = pool
        self._data_cursor = data_cursor
        self._rng_key = rng_key
        # the page footprint declared at pool.transaction(pages=...) —
        # the merge-group conflict-check currency (None = whole state)
        self.pages = (None if pages is None
                      else tuple(int(p) for p in pages))
        self._staged: Optional[PyTree] = None
        self._commit_kw: dict = {}
        self._guarded: list = []          # (buffer, nd) pairs
        self._aborted = False
        self._ok = None                   # device bool after commit

    # -- staging ---------------------------------------------------------------

    def stage(self, new_state: PyTree, *, dirty_pages=None,
              dirty_words=None, verify_old: bool = False) -> None:
        """Stage the transaction's result (the micro-buffer contents)."""
        self._staged = new_state
        self._commit_kw = {"dirty_pages": dirty_pages,
                           "dirty_words": dirty_words,
                           "verify_old": verify_old}

    def watch(self, guarded: jax.Array, *, nd: bool = False) -> jax.Array:
        """Register a canary-guarded staging buffer for verification at
        commit; returns the buffer unchanged for chaining."""
        self._guarded.append((guarded, nd))
        return guarded

    def guard(self, row: jax.Array) -> jax.Array:
        """Append a canary page to a 1-D u32 staging buffer and watch it.

        Functional staging: if kernels produce a *new* buffer from this
        one, `watch` the final buffer too — the canary travels with it.
        """
        return self.watch(microbuffer.guard(row))

    def abort(self) -> None:
        """Abort explicitly: nothing commits when the block exits."""
        self._aborted = True

    # -- verdicts ---------------------------------------------------------------

    @property
    def canary_ok(self) -> bool:
        """Host verdict over every watched guard page (True if none)."""
        checks = [microbuffer.check_nd(b) if nd else microbuffer.check(b)
                  for b, nd in self._guarded]
        return all(bool(jax.device_get(c)) for c in checks)

    def canary_device(self) -> jax.Array:
        """The staged DEVICE verdict over every watched guard page — one
        unfetched bool scalar (`kernels.ops.stage_verdict`).  The async
        pipeline's canary form: feed it to
        `pool.commit_async(canary_ok=tx.canary_device())` and the abort
        select rides inside the commit program, so dispatch never blocks
        on the host the way `canary_ok` (a per-buffer device_get) does.
        """
        checks = [microbuffer.check_nd(b) if nd else microbuffer.check(b)
                  for b, nd in self._guarded]
        return kops.stage_verdict(checks)

    @property
    def aborted(self) -> bool:
        return self._aborted

    @property
    def ok(self) -> bool:
        """Did the commit land?  (Syncs on the commit program's verdict.)"""
        if self._aborted or self._ok is None:
            return False
        return bool(jax.device_get(self._ok))

    @property
    def committed(self) -> bool:
        """Alias of `ok` — True only when the commit actually landed,
        including device-side verdicts (a verify-at-open failure aborts
        on device after the host canary passed)."""
        return self.ok

    # -- context protocol -------------------------------------------------------

    def __enter__(self) -> "Transaction":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc_type is not None:
            self._aborted = True          # exception == pgl_tx_abort
            return False                  # propagate
        if self._staged is None:
            return False                  # nothing staged: a no-op tx
        canary_ok = (not self._aborted) and self.canary_ok
        self._ok = self._pool.commit(
            self._staged, data_cursor=self._data_cursor,
            rng_key=self._rng_key, canary_ok=canary_ok, **self._commit_kw)
        if not canary_ok:
            self._aborted = True
        return False


class Pool(EngineHost):
    """The single public entry point over one protected state layout.

    Construction wires the whole stack from `ProtectConfig` (the single
    source of truth for mode / redundancy / window / scrub cadence):
    the `Protector` for the zone layout, the `DeferredProtector` when
    `config.window > 1`, the `Scrubber` with its adaptive-window
    pressure loop, and window-meta replication for bulk engines.  The
    protected snapshot itself (`ProtectedState` vs `EpochState`) is an
    internal detail — callers see `pool.state` and `pool.step`.

    Low-level escape hatches (`pool.protector`, `pool.engine`,
    `pool.scrubber`) stay public for benchmarks and tests, but nothing
    outside pool.py should *construct* those classes for a protected
    runtime.
    """

    def __init__(self, mesh, abstract_state: PyTree, state_specs: PyTree,
                 config: Optional[ProtectConfig] = None, *,
                 data_axis: str = "data",
                 dirty_leaf_idx: Optional[Sequence[int]] = None,
                 dirty_capacity: Optional[int] = None,
                 donate: bool = True,
                 replicate_meta: Optional[bool] = None,
                 on_freeze: Optional[Callable] = None,
                 on_resume: Optional[Callable] = None,
                 straggler_policy: Optional[StragglerPolicy] = None,
                 metrics: Optional[MetricsRegistry] = None,
                 tracer: Optional[Tracer] = None,
                 protector: Optional[Protector] = None):
        self.config = config if config is not None else ProtectConfig()
        self.mesh = mesh
        self.abstract_state = abstract_state
        self.state_specs = state_specs
        self.donate = bool(donate)
        self.on_freeze = on_freeze
        self.on_resume = on_resume
        # telemetry plane (repro.obs) — every pool owns a registry and a
        # tracer; a caller-supplied pair survives rescale (threaded
        # through _open_kw below) so one campaign is one metric namespace
        # and one connected trace.  Publication is host-side only:
        # commit-path instrumentation is perf_counter + dict hits, never
        # a device fetch or a jit wrapper, so a wired pool compiles
        # byte-identical programs (benchmarks/obs_overhead.py asserts).
        self.metrics = (metrics if metrics is not None
                        else MetricsRegistry())
        self.tracer = tracer if tracer is not None else Tracer()
        self.tracer.annotation = TraceAnnotation
        self._open_kw = dict(data_axis=data_axis,
                             dirty_leaf_idx=dirty_leaf_idx,
                             dirty_capacity=dirty_capacity,
                             donate=donate, replicate_meta=replicate_meta,
                             straggler_policy=straggler_policy,
                             metrics=self.metrics, tracer=self.tracer)
        mode = self.config.resolved_mode
        if protector is not None:
            # cohort sharing (repro.tenancy): same-shape pools on the
            # same mesh+config hand in one Protector so they share its
            # layout and `_jit_cache` — N tenants compile each commit /
            # scrub / recovery program once, not N times.  The caller
            # owns the compatibility claim; the cheap invariants are
            # asserted.
            assert protector.mesh is mesh, \
                "shared protector must be built on this pool's mesh"
            assert protector.mode is mode and \
                protector.redundancy == self.config.resolved_redundancy, \
                "shared protector's mode/redundancy must match config"
            self.protector = protector
        else:
            self.protector = Protector(
                mesh, abstract_state, state_specs, data_axis=data_axis,
                mode=mode, redundancy=self.config.resolved_redundancy,
                block_words=self.config.block_words,
                hybrid_threshold=self.config.hybrid_threshold,
                log_capacity=self.config.log_capacity,
                stream_threshold_words=self.config.stream_threshold_words,
                stream_chunk_words=self.config.stream_chunk_words)
        self._due_scrubs = 0          # full_scrub_every cadence counter
        # footprint arguments may be callables of the built zone layout
        # (e.g. lambda lo: range(len(lo.slots))) so callers need not
        # construct the layout twice just to size the deferred engine.
        # _open_kw keeps the UNresolved forms: rescale re-resolves them
        # against the new mesh's layout (zone geometry changes with G).
        if callable(dirty_leaf_idx):
            dirty_leaf_idx = dirty_leaf_idx(self.protector.layout)
        if callable(dirty_capacity):
            dirty_capacity = dirty_capacity(self.protector.layout)
        self._engine: Optional[DeferredProtector] = None
        self._est = None
        self._prot: Optional[ProtectedState] = None
        if self.config.window > 1:
            # ProtectConfig.__post_init__ guarantees a parity/checksum
            # mode whenever window > 1, so the engine always exists here.
            # Bulk engines (whole state dirty per commit — training)
            # replicate the window's dirty mask + digest across the pod
            # so survivors of a mid-window loss can bound it; patch
            # engines (decode) default it off, matching the runtimes.
            if replicate_meta is None:
                replicate_meta = dirty_leaf_idx is None
            self._engine = DeferredProtector(
                self.protector, window=self.config.window,
                dirty_capacity=dirty_capacity,
                dirty_leaf_idx=dirty_leaf_idx, donate=donate,
                replicate_meta=replicate_meta)
        self.scrubber = Scrubber(
            self.protector, period=self.config.scrub_period,
            engine=self._engine,
            growth_commits=self.config.window_growth_commits)
        self.scrubber.metrics = self.metrics
        if self._engine is not None:
            self._engine.metrics = self.metrics
        r_armed = (self.protector.redundancy
                   if self.protector.mode.has_parity else 0)
        self.metrics.gauge("pool_window").set(
            self._engine.window if self._engine is not None else 1)
        self.metrics.gauge("pool_redundancy").set(r_armed)
        self.metrics.gauge("pool_budget_remaining").set(r_armed)
        # hot-path handles: commit() publishes through these cached
        # objects (no registry lookup per transaction)
        self._m_commits = self.metrics.counter("pool_commits_total")
        self._m_donated_new = self.metrics.counter(
            "pool_commits_donated_new_total")
        self._m_aborted = self.metrics.counter(
            "pool_commit_aborted_total")
        self._m_commit_ms = self.metrics.histogram(
            "pool_commit_dispatch_ms")
        # async commit pipeline (core/pipeline.py): the N-deep in-flight
        # ring behind commit_async; resolve latency carries the dispatch
        # span id as a histogram exemplar so a p99 sample links back to
        # its trace event
        self._m_resolve_ms = self.metrics.histogram(
            "pool_commit_resolve_ms")
        self._m_inflight = self.metrics.gauge("pool_inflight_depth")
        self._ring = CommitRing(self.config.pipeline_depth,
                                on_depth=self._m_inflight.set)
        self._ticket_seq = 0
        self._staged_sel = None       # cached jitted sync canary select
        # merged-window bookkeeping (lock-free dirty-union semantics):
        # the page-footprint union of every transaction opened since the
        # last flush; a conflicting footprint seals the group (drain +
        # flush) before the new transaction joins a fresh one, so
        # conflicting txns serialize and disjoint txns coalesce into ONE
        # telescoped flush
        self._merge_open = False
        self._merge_all = False
        self._merge_pages: set = set()
        self._m_txn_serialized = self.metrics.counter(
            "pool_txn_serialized_total")
        self._m_txn_coalesced = self.metrics.counter(
            "pool_txn_coalesced_total")
        # health bookkeeping (host flags; pool.health() folds these)
        self._n_recoveries = 0
        self._n_followups = 0
        self._suspect = False
        self._budget_exhausted = False
        self._last_reverify_ok: Optional[bool] = None
        self._unrepaired_pages = 0
        # fault ids noted (note_fault / inject) and not yet consumed by
        # the recovery/repair span that resolves them — the trace-linkage
        # currency (obs/trace.validate_events)
        self._open_fault_ids: list = []
        # straggler mitigation (ProtectConfig.straggler_threshold > 0):
        # the policy tracks per-replica commit-loop durations and drops
        # replicas past threshold x the fleet median; while ANY replica
        # is dropped the pool runs degraded — the adaptive window stays
        # collapsed at 1 so redundancy lag never piles up behind a rank
        # that cannot keep the flush cadence.  `straggler_policy`
        # overrides the default-built policy (tests/chaos tune the
        # observation window).
        self.straggler: Optional[StragglerPolicy] = None
        if straggler_policy is not None:
            self.straggler = straggler_policy
        elif self.config.straggler_threshold > 0:
            self.straggler = StragglerPolicy(
                self.protector.group_size,
                threshold=self.config.straggler_threshold)
        self._dropped: set = set()
        # async-safe recovery re-entry: faults arriving while a recovery
        # is already in flight (freeze/resume callbacks, chaos schedule
        # hooks) queue here and drain sequentially — never two
        # interleaved reconstructions over one pool
        self._recovering = False
        self._pending_faults: list = []
        self._arrival_fn: Optional[Callable] = None

    # -- open -------------------------------------------------------------------

    @classmethod
    def open(cls, state: PyTree, specs: PyTree, *, mesh,
             config: Optional[ProtectConfig] = None,
             **kw) -> "Pool":
        """The `pgl_open` analogue: protect `state` and return the pool.

        `state` may be concrete (protection is built immediately) or a
        ShapeDtypeStruct pytree (a *cold* pool: the layout and compiled
        programs exist, call `pool.init(state)` to attach real state —
        how the runtimes and the dry-run lowering use it).
        """
        abstract = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), state)
        pool = cls(mesh, abstract, specs, config, **kw)
        if not _is_abstract(state):
            pool.init(state)
        return pool

    def init(self, state: PyTree) -> "Pool":
        """Build parity/checksums/row for `state` (fresh protection).

        Also the re-arm point after a budget-exhausted storm: fresh
        protection clears the exhaust/corruption health flags and
        restores the full syndrome budget.
        """
        # re-protection supersedes any commit still in flight: void the
        # tickets deterministically (verdict False, device untrusted)
        # rather than resolving against buffers the re-arm replaced
        self._ring.void_all()
        self.prot = self.protector.init(state)
        self._budget_exhausted = False
        self._unrepaired_pages = 0
        self._last_reverify_ok = None
        self._suspect = False
        self.metrics.gauge("pool_budget_remaining").set(
            self.protector.redundancy
            if self.protector.mode.has_parity else 0)
        return self

    # -- introspection ----------------------------------------------------------

    @property
    def mode(self) -> Mode:
        return self.protector.mode

    @property
    def redundancy(self) -> int:
        """Syndrome stack height r — simultaneous rank losses survived."""
        return self.protector.redundancy

    @property
    def engine(self) -> Optional[DeferredProtector]:
        """The deferred-epoch engine, or None on the synchronous cadence."""
        return self._engine

    @property
    def state(self) -> Optional[PyTree]:
        """The live protected state pytree."""
        prot = self.prot
        return None if prot is None else prot.state

    @property
    def step(self) -> int:
        """Committed transaction count (host value)."""
        return int(jax.device_get(self.prot.step))

    def overhead_report(self) -> dict:
        rep = self.protector.overhead_report()
        rep["window"] = (self._engine.window if self._engine is not None
                         else 1)
        return rep

    # -- telemetry surface -------------------------------------------------------

    def stats(self) -> dict:
        """One host-side snapshot of the pool's telemetry: commit
        counters and dispatch-wall summary, window state, exact scrub
        coverage, recovery history, degradation flags, and the full
        metric registry.  Never touches the device — poll it at any
        cadence (the step counter stays a device value; fetch
        `pool.step` explicitly when you want it)."""
        eng = self._engine
        return {
            "mode": self.mode.value,
            "redundancy": self.redundancy,
            "engine": "deferred" if eng is not None else "sync",
            "window": eng.window if eng is not None else 1,
            "max_window": eng.max_window if eng is not None else 1,
            "commits": int(self._m_commits.value),
            "aborted_commits": int(self._m_aborted.value),
            "commit_dispatch_ms": self._m_commit_ms.summary(),
            "pipeline_depth": self.config.pipeline_depth,
            "in_flight": len(self._ring),
            "commit_resolve_ms": self._m_resolve_ms.summary(),
            "scrub": self.scrubber.coverage(),
            "recoveries": self._n_recoveries,
            "recovery_followups": self._n_followups,
            "dropped_replicas": self.dropped_replicas,
            "suspect": self._suspect,
            "budget_exhausted": self._budget_exhausted,
            "metrics": self.metrics.snapshot(),
        }

    def health(self) -> obs_health.HealthReport:
        """Green / degraded / critical with named reasons — host state
        only, see obs/health.py for the exact semantics."""
        eng = self._engine
        return obs_health.assess(
            window=eng.window if eng is not None else 1,
            max_window=eng.max_window if eng is not None else 1,
            dropped_replicas=self._dropped,
            suspect=self._suspect,
            redundancy=(self.redundancy
                        if self.mode.has_parity else 0),
            budget_exhausted=self._budget_exhausted,
            scrub_coverage=self.scrubber.coverage(),
            unrepaired_pages=self._unrepaired_pages,
            reverify_failed=self._last_reverify_ok is False,
            recoveries=self._n_recoveries,
            recovery_followups=self._n_followups)

    def commit_program(self, *, dirty_pages=None, verify_old: bool = False):
        """The compiled synchronous-commit program the facade routes
        through (for benchmarks asserting facade == direct bytes)."""
        return self.protector.commit_program(
            dirty_pages=dirty_pages, verify_old=verify_old,
            donate=self.donate)

    # -- commit -----------------------------------------------------------------

    def commit(self, state_new: PyTree, *, dirty_pages=None,
               dirty_words=None, data_cursor=0, rng_key=None,
               canary_ok: bool = True, verify_old: bool = False,
               donate_new: bool = False):
        """One transactional update; returns the commit verdict (device
        bool — fetch it lazily to keep protection off the critical
        path).

        Routing is the facade's whole job: the deferred engine takes
        `dirty_words` (per-leaf word indices, position-independent
        shapes) and ignores `dirty_pages` — its page footprint is the
        static `dirty_leaf_idx` from construction; the synchronous
        engine takes `dirty_pages` (a static page set keying its own
        compiled commit).  Callers pass whichever they know; the pool
        feeds the right one to the engine it built.

        `donate_new=True` hands `state_new` over to the commit (see
        `Protector.commit`): the caller must not read it afterwards.  The
        synchronous engine then keeps the committed state in
        `state_new`'s buffers instead of copying it; the deferred engine
        ignores the flag.
        """
        assert self.prot is not None, "Pool.commit before init()"
        with self.tracer.phase("pool.commit_dispatch", step=data_cursor):
            t0 = time.perf_counter()
            if self._engine is not None:
                assert not verify_old, \
                    "verify_old is a synchronous-engine feature (window=1)"
                self._est, ok = self._engine.commit(
                    self._est, state_new, dirty_words=dirty_words,
                    data_cursor=data_cursor, rng_key=rng_key,
                    canary_ok=canary_ok)
            else:
                self._prot, ok = self.protector.commit(
                    self._prot, state_new, dirty_pages=dirty_pages,
                    verify_old=verify_old, donate=self.donate,
                    donate_new=donate_new, data_cursor=data_cursor,
                    rng_key=rng_key, canary_ok=canary_ok)
                if donate_new:
                    self._m_donated_new.inc()
                if self._arrival_fn is not None:
                    # synchronous cadence: every commit is its own
                    # window boundary, so the arrival point is right
                    # after it
                    new = self._arrival_fn(self._prot, 1, True)
                    if new is not None:
                        self._prot = new
            # the scrub cadence + clean-streak window growth ride on the
            # host-known canary verdict (no device sync on the hot path)
            self.scrubber.on_commit(clean=bool(canary_ok))
            # telemetry: the observed wall is DISPATCH wall — commits
            # return a device verdict unfetched, so this measures the host
            # cost of launching the program, which is exactly what
            # instrumentation could perturb (the device-side cost is the
            # benchmarks' job)
            self._m_commits.inc()
            if not canary_ok:
                self._m_aborted.inc()
            self._m_commit_ms.observe((time.perf_counter() - t0) * 1e3)
        return ok

    # -- async commit pipeline ---------------------------------------------------

    def _staged_sel_fn(self):
        """Cached jitted select for the synchronous engine's device
        canary: `Protector.commit` keys its canary statically (the jit
        cache's `static_argnames`), so a traced verdict cannot ride the
        existing program — instead the all-clear commit runs without
        donation and this select gates the WHOLE new protected state on
        the device canary per leaf.  A False canary yields the old state
        bit-identically (the static abort path's result)."""
        if self._staged_sel is None:
            def _sel(v, ok, new, old):
                v = jnp.asarray(v, bool).reshape(())
                sel = jax.tree.map(lambda n, o: jnp.where(v, n, o),
                                   new, old)
                return sel, jnp.logical_and(v, ok)
            self._staged_sel = jax.jit(_sel)
        return self._staged_sel

    def commit_async(self, state_new: PyTree, *, dirty_pages=None,
                     dirty_words=None, data_cursor=0, rng_key=None,
                     canary_ok=True, verify_old: bool = False,
                     donate_new: bool = False,
                     extras: Optional[dict] = None) -> CommitTicket:
        """One transactional update as a future: dispatches the commit
        and returns a `CommitTicket` carrying the UNfetched device
        verdict, the dispatch timestamp, and the trace span id.  Up to
        `ProtectConfig.pipeline_depth` tickets stay in flight (the ring
        force-resolves the oldest past that); tickets resolve as their
        device scalars land — `ticket.result()`, `pool.poll()` out of
        dispatch order, or `pool.drain()` at a boundary.

        `canary_ok` accepts either the host bool the synchronous
        `commit` takes, or an UNfetched device bool (e.g.
        `tx.canary_device()` / `kernels.ops.stage_verdict`) — the
        staged form: the abort select rides inside the program, the
        verdict can't be host-known at dispatch, and abort bookkeeping
        (abort counter, scrub clean-streak) defers to resolution.
        Routing (`dirty_pages` vs `dirty_words`) and `donate_new` match
        `commit`; the staged form never consumes `state_new` (the old
        state stays the select's False arm).
        """
        assert self.prot is not None, "Pool.commit_async before init()"
        with self.tracer.phase("pool.commit_dispatch", step=data_cursor):
            t0 = time.perf_counter()
            staged = not isinstance(canary_ok, (bool, np.bool_))
            if self._engine is not None:
                assert not verify_old, \
                    "verify_old is a synchronous-engine feature (window=1)"
                if staged:
                    self._est, ok = self._engine.commit_staged(
                        self._est, state_new, canary=canary_ok,
                        dirty_words=dirty_words, data_cursor=data_cursor,
                        rng_key=rng_key)
                else:
                    self._est, ok = self._engine.commit(
                        self._est, state_new, dirty_words=dirty_words,
                        data_cursor=data_cursor, rng_key=rng_key,
                        canary_ok=bool(canary_ok))
            else:
                if staged:
                    # no donation: the old state is the select's False arm
                    prot_old = self._prot
                    prot_new, ok_c = self.protector.commit(
                        prot_old, state_new, dirty_pages=dirty_pages,
                        verify_old=verify_old, donate=False,
                        data_cursor=data_cursor, rng_key=rng_key,
                        canary_ok=True)
                    self._prot, ok = self._staged_sel_fn()(
                        canary_ok, ok_c, prot_new, prot_old)
                else:
                    self._prot, ok = self.protector.commit(
                        self._prot, state_new, dirty_pages=dirty_pages,
                        verify_old=verify_old, donate=self.donate,
                        donate_new=donate_new, data_cursor=data_cursor,
                        rng_key=rng_key, canary_ok=bool(canary_ok))
                    if donate_new:
                        self._m_donated_new.inc()
                if self._arrival_fn is not None:
                    new = self._arrival_fn(self._prot, 1, True)
                    if new is not None:
                        self._prot = new
            seq = self._ticket_seq
            self._ticket_seq += 1
            span_id = self.tracer.emit_hot("commit_dispatch", seq=seq,
                                           staged=bool(staged))
            if not staged:
                # host-known canary: dispatch-time bookkeeping identical
                # to the synchronous commit path
                self.scrubber.on_commit(clean=bool(canary_ok))
                if not canary_ok:
                    self._m_aborted.inc()
            self._m_commits.inc()
            self._m_commit_ms.observe((time.perf_counter() - t0) * 1e3)
        return self._ring.submit(CommitTicket(
            seq, ok, dispatched_at=t0, span_id=span_id, extras=extras,
            staged=staged, on_resolve=self._on_ticket_resolved,
            tracer=self.tracer, step=data_cursor))

    def _on_ticket_resolved(self, ticket: CommitTicket) -> None:
        """Resolution bookkeeping (fires exactly once per ticket): the
        resolve-latency histogram carries the ticket's trace span id as
        an exemplar, and staged canaries settle their abort accounting
        now that the verdict is host-known."""
        lat = ticket.resolve_latency_ms
        if lat is not None:
            self._m_resolve_ms.observe(lat, exemplar=ticket.span_id)
        if ticket.staged:
            v = bool(ticket.result())
            self.scrubber.on_commit(clean=v)
            if not v:
                self._m_aborted.inc()

    def poll(self) -> list:
        """Resolve any in-flight tickets whose device verdicts already
        landed (out of dispatch order); returns them."""
        return self._ring.poll()

    def drain(self) -> list:
        """Resolve EVERY in-flight ticket (dispatch order) — the
        deterministic pipeline boundary.  `flush`, scrub, recovery and
        rescale all drain first, so a pipeline interrupted anywhere
        lands exactly where synchronous resolution would."""
        return self._ring.drain()

    @property
    def in_flight(self) -> int:
        """Unresolved commit tickets currently in the ring."""
        return len(self._ring)

    def flush(self) -> None:
        """Bring deferred redundancy current (no-op when synchronous);
        resolves the commit pipeline first and closes any open
        transaction merge group — a flush is the deterministic boundary
        every coalesced window telescopes into."""
        self.drain()
        if self._engine is not None and self._est is not None:
            self._est = self._engine.flush_if_pending(self._est)
        self._merge_open = False
        self._merge_all = False
        self._merge_pages = set()

    # -- transactions (merged-window protocol) -----------------------------------

    def _enter_footprint(self, pages) -> bool:
        """The page-granular conflict check at `transaction()` entry
        (lock-free dirty-union semantics): a footprint disjoint from the
        open merge group joins it — its commits coalesce into the SAME
        deferred window, one telescoped flush for all of them; a
        conflicting footprint (overlap, or either side whole-state)
        seals the group first (drain + flush), so conflicting
        transactions serialize across windows.  Returns True when this
        entry serialized."""
        whole = pages is None
        fp = set() if whole else set(int(p) for p in pages)
        if not self._merge_open:
            self._merge_open = True
            self._merge_all = whole
            self._merge_pages = fp
            return False
        conflict = self._merge_all or whole or bool(
            self._merge_pages & fp)
        if conflict:
            self._m_txn_serialized.inc()
            self.flush()              # seal: drain + telescoped flush
            self._merge_open = True
            self._merge_all = whole
            self._merge_pages = fp
            return True
        self._m_txn_coalesced.inc()
        self._merge_pages |= fp
        return False

    def transaction(self, *, data_cursor=0, rng_key=None,
                    pages: Optional[Sequence[int]] = None) -> Transaction:
        """`pgl_tx_begin`: returns the staging context manager.

        `pages` declares the transaction's page footprint for the
        merged-window protocol (`_enter_footprint`): concurrent open
        transactions with DISJOINT footprints coalesce into one deferred
        window (one telescoped flush); overlapping footprints — or any
        transaction that declares none (None = whole state) — serialize
        behind a seal.  Omitting `pages` preserves the classic
        serial-transaction behavior exactly.
        """
        self._enter_footprint(pages)
        return Transaction(self, data_cursor=data_cursor,
                           rng_key=rng_key, pages=pages)

    # -- fault-arrival hook (chaos harness) -------------------------------------

    def set_arrival_hook(self, fn: Optional[Callable]) -> None:
        """Register `fn(prot, since, at_boundary) -> Optional[ProtectedState]`
        at the commit loop's fault-arrival point.

        Deferred engine: the hook fires inside `commit`, between
        in-window commits and BEFORE any boundary flush (the
        `DeferredProtector.arrival_hook` point) — a returned
        ProtectedState replaces the window's, modeling corruption landing
        concurrent with traffic.  Synchronous engine: the hook fires
        right after each commit (every commit is its own boundary).
        Pass None to clear.
        """
        self._arrival_fn = fn
        if self._engine is not None:
            if fn is None:
                self._engine.arrival_hook = None
            else:
                def _hook(est, since, at_boundary):
                    new = fn(est.prot, since, at_boundary)
                    return (None if new is None
                            else dataclasses.replace(est, prot=new))
                self._engine.arrival_hook = _hook

    def note_fault(self, kind: str, **fields) -> int:
        """Record a fault's arrival in the telemetry plane; returns the
        trace id.  The id stays "open" until the next recovery (or
        repairing scrub) span consumes it into its `faults` list — the
        linkage `validate_events` / scripts/trace_check.py enforce.
        Injectors routed through `inject` are noted automatically; a
        harness that corrupts state by other means (e.g. an arrival-hook
        scribble inside an open window) must call this itself so the
        trace stays connected.
        """
        self.metrics.counter("pool_faults_total", kind=str(kind)).inc()
        fid = self.tracer.emit("fault", fault_kind=str(kind), **fields)
        self._open_fault_ids.append(fid)
        return fid

    def note_event(self, event) -> int:
        """`note_fault` from a FailureEvent (duck-typed) — what a
        harness calls when it corrupted state without going through
        `inject` (e.g. inside an arrival hook)."""
        fields = {}
        if getattr(event, "lost_rank", None) is not None:
            fields["lost_rank"] = int(event.lost_rank)
        if getattr(event, "lost_ranks", None):
            fields["lost_ranks"] = [int(r) for r in event.lost_ranks]
        if getattr(event, "locations", None):
            fields["pages"] = [[int(r), int(p)]
                               for r, p in event.locations]
        return self.note_fault(getattr(event, "kind", "inject"),
                               **fields)

    def set_tracer(self, tracer: Tracer) -> None:
        """Swap the trace sink (e.g. for a file-backed tracer after the
        pool was built) — threaded through `_open_kw` so pools built by
        `rescale` keep emitting into the new sink."""
        tracer.annotation = TraceAnnotation
        self.tracer = tracer
        self._open_kw["tracer"] = tracer

    def inject(self, fn: Callable):
        """Apply a failure injector `fn(protector, prot) -> (prot, event)`
        to the live protected state IN PLACE, preserving any open
        window's bookkeeping (the `prot` setter would wrap a fresh
        window, silently discarding the accumulator a later flush
        needs).  Returns the injector's FailureEvent — the chaos
        harness's between-commit corruption point.  The event is noted
        as a fault in the trace (see `note_fault`).
        """
        assert self.prot is not None, "Pool.inject before init()"
        new_prot, event = fn(self.protector, self.prot)
        if self._engine is not None:
            self._est = dataclasses.replace(self._est, prot=new_prot)
        else:
            self._prot = new_prot
        self.note_event(event)
        return event

    # -- straggler degradation path ---------------------------------------------

    @property
    def dropped_replicas(self) -> list:
        """Data ranks currently dropped by the straggler policy."""
        return sorted(self._dropped)

    def observe_commit_times(self, durations) -> np.ndarray:
        """Feed per-replica commit-loop durations (seconds, one entry per
        data rank) into the straggler policy; returns the participation
        mask.

        This is the pool-side degradation path: while any replica is
        dropped the deferred window is held collapsed at 1 (each
        observation re-collapses it, so clean-commit growth cannot
        outpace a live straggler) and the scrub clean-streak resets —
        the pool runs on the synchronous cadence until the fleet is
        healthy again, then the adaptive window regrows through the
        usual clean-scrub / clean-commit signals.
        """
        assert self.straggler is not None, (
            "no straggler policy on this pool — set "
            "ProtectConfig.straggler_threshold > 0 (or pass "
            "straggler_policy=) to enable mitigation")
        for rank, dur in enumerate(durations):
            self.straggler.observe(rank, float(dur))
        mask = self.straggler.replica_mask()
        before = self._dropped
        self._dropped = set(int(r) for r in np.flatnonzero(~mask))
        if self._dropped:
            if self._engine is not None:
                self._engine.report_pressure(True)
            self.scrubber.note_suspect()
        newly, healed = self._dropped - before, before - self._dropped
        if newly:
            self.metrics.counter(
                "pool_straggler_drop_total").inc(len(newly))
            self.tracer.emit("straggler_drop",
                             replicas=sorted(int(r) for r in newly))
        if healed:
            self.metrics.counter(
                "pool_straggler_heal_total").inc(len(healed))
        self.metrics.gauge("pool_dropped_replicas").set(
            len(self._dropped))
        return mask

    # -- scrub ------------------------------------------------------------------

    def scrub(self) -> ScrubReport:
        """Force one global scrub (flushing any open window first);
        repairs detected scribbles in place and feeds the adaptive
        window."""
        assert self.prot is not None
        self.flush()                 # scrub must see current redundancy
        with self.tracer.span("scrub", scope="full") as span:
            prot, report = self.scrubber.run(
                self.prot, freeze=self._freeze, resume=self._resume)
            self.prot = prot
            span.annotate(suspect=bool(report.suspect),
                          bad_pages=len(report.bad_locations),
                          repaired=bool(report.repaired))
            # a scrub whose repair actually fixed pages resolves any
            # open fault ids — link them here exactly like a recovery
            # span would (note_fault docs the contract)
            if report.repaired and self._open_fault_ids:
                fault_ids, self._open_fault_ids = \
                    self._open_fault_ids, []
                span.annotate(faults=fault_ids)
        self._fold_scrub_health(report)
        repaired_ok = report.repaired and bool(report.repair_ok)
        if report.bad_locations and not repaired_ok:
            self._unrepaired_pages = len(report.bad_locations)
        else:
            self._unrepaired_pages = 0
        return report

    def precheck(self) -> ScrubReport:
        """The rank-local syndrome scrub (flushing any open window
        first): state blocks vs checksums, row-cache coherence, and the
        folded-syndrome compare — no full-row collective."""
        assert self.prot is not None
        self.flush()
        with self.tracer.span("scrub", scope="precheck") as span:
            report = self.scrubber.precheck(self.prot)
            span.annotate(suspect=bool(report.suspect))
        self._fold_scrub_health(report)
        return report

    def _fold_scrub_health(self, report: ScrubReport) -> None:
        """Scrub verdict -> health flags: suspicion follows the latest
        checked pass (clean clears it, symmetric with the adaptive
        window's pressure loop); a clean pass also retires a stale
        reverify-failed flag (the residual corruption it warned about
        no longer exists)."""
        if not report.checked:
            return
        self._suspect = bool(report.suspect)
        if not report.suspect:
            self._last_reverify_ok = None

    def maybe_scrub(self) -> Optional[ScrubReport]:
        """Run a scrub iff the cadence says one is due.

        With `config.full_scrub_every = N > 1`, a due scrub first runs
        the rank-local pre-check; only every Nth due scrub — or any
        pre-check that flags the pool suspect — pays for the global
        syndrome collectives (and their repair path).  N = 1 keeps the
        classic always-global cadence.
        """
        if not self.scrubber.due():
            return None
        n = self.config.full_scrub_every
        self._due_scrubs += 1
        if n > 1 and self._due_scrubs % n:
            report = self.precheck()
            if not report.suspect:
                # clean local pass counts toward the cadence; the next
                # global scrub still lands on the full_scrub_every beat
                self.scrubber.mark_checked()
                return report
        return self.scrub()

    # -- recovery ---------------------------------------------------------------

    def recover(self, fault: Fault, *,
                reverify: bool = True
                ) -> Optional[recovery_mod.RecoveryReport]:
        """One recovery path for every fault (the SIGBUS-handler
        analogue).  Flushes any open window first — the cached row is a
        separate buffer the fault never touched, so the flushed
        redundancy describes intended values and online reconstruction
        proceeds exactly as in the synchronous engine.  Stacks with
        redundancy >= e additionally solve `Fault.multi_loss` of e
        ranks; e > r raises the budget-exhausted error (naming the dead
        ranks and the available r) instead of attempting a solve the
        stack cannot carry.  After recovery
        the deferred window collapses toward 1 (failure suspicion) and,
        when window metadata was replicated, the report carries the
        survivors' window bound.

        `reverify=True` (default) re-runs the full syndrome/checksum
        verification AFTER reconstruction — `report.synd_ok` carries the
        per-syndrome verdicts and `report.reverified` the overall one,
        so residual corruption (a scribble outstanding elsewhere while a
        rank was being rebuilt) is surfaced instead of trusted.

        Re-entry is async-safe: a fault arriving while a recovery is
        already in flight (from a freeze/resume callback or a chaos
        schedule hook) is queued and drained sequentially after the
        running reconstruction completes — that call returns None and
        the outer call's report counts it in `followups`.
        """
        assert self.prot is not None
        if not isinstance(fault, Fault):
            fault = Fault.from_event(fault)   # accept raw FailureEvents
        if self._recovering:
            self._pending_faults.append((fault, time.perf_counter()))
            self.metrics.counter("pool_recovery_queued_total").inc()
            return None
        self._recovering = True
        try:
            rep = self._recover_one(fault, reverify=reverify)
            drained = 0
            while self._pending_faults:
                qfault, t_enq = self._pending_faults.pop(0)
                self._recover_one(
                    qfault, reverify=reverify,
                    queue_wait_ms=(time.perf_counter() - t_enq) * 1e3)
                drained += 1
            rep.followups = drained
            self._n_followups += drained
            return rep
        finally:
            self._recovering = False
            self._pending_faults.clear()

    def _recover_one(self, fault: Fault, *, reverify: bool,
                     queue_wait_ms: Optional[float] = None
                     ) -> recovery_mod.RecoveryReport:
        t_total = time.perf_counter()
        # consume every fault id noted since the last resolving span:
        # THIS recovery is what resolves them (a drained follow-up grabs
        # ids noted while the outer recovery ran, so the linkage stays
        # exact across the re-entry queue)
        fault_ids, self._open_fault_ids = self._open_fault_ids, []
        with self.tracer.span("recovery", fault_kind=fault.kind,
                              faults=fault_ids) as span:
            if fault.kind == "multi_loss":
                # refuse an over-budget solve up front, before the flush
                # touches anything — the actionable form of "e > r".
                # The health surface latches critical here (cleared by
                # the pool.init re-arm) and the span ends with the error
                # attached, still linking its fault ids.
                e = len(fault.ranks)
                r = (self.protector.redundancy
                     if self.protector.mode.has_parity else 0)
                if e > r:
                    self._budget_exhausted = True
                    self.metrics.counter(
                        "pool_budget_exhausted_total").inc()
                    self.metrics.gauge("pool_budget_remaining").set(0)
                    raise RuntimeError(
                        f"syndrome budget exhausted: ranks "
                        f"{list(fault.ranks)} are lost simultaneously "
                        f"(e={e}) but this pool holds redundancy={r} "
                        "syndrome row(s) — at most r losses solve "
                        "online.  Restore from the checkpoint + "
                        "redo-log tier and re-arm the stack by "
                        "re-protecting (pool.init), or raise "
                        f"ProtectConfig.redundancy>={e} (<= 4) before "
                        "the next storm")
            # survivors' copy of the window metadata, captured BEFORE
            # the flush mutates the window
            meta = (self._engine.window_meta
                    if self._engine is not None else None)
            self.flush()
            if fault.kind == "rank_loss":
                prot, rep = recovery_mod.recover_from_rank_loss(
                    self.protector, self.prot, fault.rank,
                    freeze=self._freeze, resume=self._resume)
            elif fault.kind == "multi_loss":
                prot, rep = recovery_mod.recover_from_e_loss(
                    self.protector, self.prot, fault.ranks,
                    freeze=self._freeze, resume=self._resume)
            elif fault.kind == "scribble":
                prot, rep = recovery_mod.recover_from_scribble(
                    self.protector, self.prot, fault.locations,
                    freeze=self._freeze, resume=self._resume)
            else:
                raise ValueError(
                    f"no recovery path for fault {fault.kind!r}")
            self.prot = prot
            if reverify:
                t_rv = time.perf_counter()
                self._reverify(rep)
                rep.reverify_ms = (time.perf_counter() - t_rv) * 1e3
            if self._engine is not None:
                self._engine.report_pressure(True)
                self.scrubber.note_suspect()
                if meta is not None:
                    rep.window_bound = {
                        "pending": meta["pending"],
                        "dirty_pages": meta["dirty_pages"],
                        "digest_verified":
                            self._engine.verify_window_bound(self._est),
                    }
            rep.queue_wait_ms = queue_wait_ms
            rep.total_ms = (time.perf_counter() - t_total) * 1e3
            self._publish_recovery(rep)
            ev = rep.to_event()
            # the span's own `kind` ("recovery") wins; the report's kind
            # (rank_loss/multi_loss/scribble) rides as recovery_kind
            ev["recovery_kind"] = ev.pop("kind")
            span.annotate(**ev)
            return rep

    def _publish_recovery(self,
                          rep: recovery_mod.RecoveryReport) -> None:
        self._suspect = True                  # until the next clean scrub
        self._n_recoveries += 1
        self._last_reverify_ok = rep.reverified
        reg = self.metrics
        reg.counter("pool_recoveries_total", kind=rep.kind).inc()
        for name, v in (("pool_recovery_solve_ms", rep.solve_ms),
                        ("pool_recovery_reverify_ms", rep.reverify_ms),
                        ("pool_recovery_queue_wait_ms",
                         rep.queue_wait_ms),
                        ("pool_recovery_total_ms", rep.total_ms)):
            if v is not None:
                reg.histogram(name).observe(v)
        if rep.reverified is False:
            reg.counter("pool_reverify_failed_total").inc()

    def _reverify(self, rep: recovery_mod.RecoveryReport) -> None:
        """Re-run verify_syndromes (+ checksums + row cache) after a
        reconstruction; folds the verdict into the report."""
        mode = self.protector.mode
        if not (mode.has_parity or mode.has_cksums):
            return
        out = jax.device_get(self.protector.scrub(self.prot))
        ok = True
        if "synd_ok" in out:
            rep.synd_ok = [bool(v) for v in np.asarray(out["synd_ok"])]
            ok = ok and all(rep.synd_ok)
        if "bad_pages" in out:
            ok = ok and not bool(np.asarray(out["bad_pages"]).any())
        if "row_cache_ok" in out:
            ok = ok and bool(out["row_cache_ok"])
        rep.reverified = ok
        rep.verified = bool(rep.verified) and ok

    # -- rescale ----------------------------------------------------------------

    def rescale(self, new_mesh, *, into: Optional["Pool"] = None) -> "Pool":
        """Move the pool to `new_mesh` (elastic resize), returning the
        new pool.

        Flush-before-rescale lands any open window, then the state
        reshards bit-exactly through the host and protection is rebuilt
        for the new zone geometry (G changes the row padding, the
        page->owner map, and every syndrome's Vandermonde coefficients
        g^(k·i), so no plane of the stack can move with the state).
        `into`
        reuses a cold pool already built for the new mesh (a runtime's
        own); otherwise a fresh pool with this one's config is built.
        """
        assert self.prot is not None
        self.flush()
        with self.tracer.span("rescale") as span:
            if into is None:
                # _open_kw carries metrics= and tracer=, so the new pool
                # publishes into this one's registry and trace — one
                # campaign stays one metric namespace across resizes
                into = Pool(new_mesh, self.abstract_state,
                            self.state_specs, self.config,
                            **self._open_kw)
            # elastic.rescale owns the reshard -> rebuild -> step-carry
            # sequence; the facade adds flush-before-rescale and wiring
            _, prot_new = elastic.rescale(
                self.protector, self.prot, lambda _m: into.protector,
                new_mesh)
            into.prot = prot_new
            span.annotate(
                groups=(self.protector.group_size,
                        into.protector.group_size))
        self.metrics.counter("pool_rescales_total").inc()
        return into

    # -- freeze/resume hooks ----------------------------------------------------

    def _freeze(self):
        """Paper's pool freeze: drain outstanding work before repair."""
        if self.on_freeze is not None:
            self.on_freeze()
        elif self.prot is not None:
            jax.block_until_ready(jax.tree.leaves(self.prot.state)[0])

    def _resume(self):
        if self.on_resume is not None:
            self.on_resume()


class PoolHost:
    """Mixin for runtimes that own `self.pool` (possibly None — an
    unprotected runtime).  Delegates the low-level handles tests and
    benchmarks poke (`protector`, `scrubber`, `prot`, `_engine`,
    `_est`) plus `flush()`, so every host exposes the same surface
    without re-implementing the shim."""

    pool: Optional[Pool] = None

    @property
    def protector(self):
        return self.pool.protector if self.pool is not None else None

    @property
    def scrubber(self):
        return self.pool.scrubber if self.pool is not None else None

    @property
    def prot(self):
        return self.pool.prot if self.pool is not None else None

    @prot.setter
    def prot(self, value):
        if self.pool is not None:
            self.pool.prot = value
        else:
            assert value is None, "unprotected host holds no prot"

    @property
    def _engine(self):
        return self.pool.engine if self.pool is not None else None

    @property
    def _est(self):
        return self.pool._est if self.pool is not None else None

    @_est.setter
    def _est(self, value):
        self.pool._est = value

    def flush(self) -> None:
        """Bring deferred redundancy current (no-op when synchronous)."""
        if self.pool is not None:
            self.pool.flush()
