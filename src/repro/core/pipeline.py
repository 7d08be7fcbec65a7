"""Async commit pipeline: CommitTicket futures + the N-deep CommitRing.

Pangolin's micro-buffered transactions already keep redundancy work off
the application's critical path *per commit*; this module removes the
remaining host serialization *across* commits (FliT, arXiv:2108.04202:
persistent-object throughput hinges on many cheap in-flight operations).
`Pool.commit_async` dispatches a commit and returns a `CommitTicket` —
a future over the commit program's device verdict — instead of the raw
device bool.  Tickets queue in a `CommitRing` of
`ProtectConfig.pipeline_depth` slots: commit t+k dispatches before
commit t resolves, and verdicts resolve OUT OF DISPATCH ORDER — `poll`
resolves whichever device scalars have landed, not the oldest first —
so one slow commit never head-of-line-blocks the verdicts behind it.

Nothing here touches protection math: a ticket is bookkeeping around a
device scalar the commit program already produced, so a pipeline
drained at any boundary is bit-identical to resolving every commit
synchronously (tests/test_pipeline.py asserts this across engines,
redundancy levels and depths).  The ring is plain host state — no jit,
no collective — and publishes through callbacks the Pool wires
(in-flight depth gauge, resolve-latency histogram with trace-span
exemplars).
"""
from __future__ import annotations

import contextlib
import time
from typing import Any, Callable, List, Optional

import jax

_NO_SPAN = contextlib.nullcontext()


def _scalar_ready(ok: Any) -> bool:
    """Non-blocking readiness of a device scalar (host values are
    always ready; jax.Array exposes is_ready())."""
    fn = getattr(ok, "is_ready", None)
    return True if fn is None else bool(fn())


class CommitTicket:
    """One in-flight commit: the verdict future `commit_async` returns.

    Carries the unfetched device verdict (`ok`), the dispatch/resolve
    wall-clock timestamps, the trace span id of the dispatch event, and
    optional `extras` (e.g. per-tenant verdict scalars for a tenancy
    wave).  `result()` fetches the verdict — blocking unless the scalar
    already landed — and fires the pool's resolve callback exactly
    once; `ready()` polls without blocking.  With a `tracer`, the fetch
    is its `pool.resolve` phase, carrying `step` (the commit's data
    cursor): whichever path resolves the ticket (its caller, the
    ring's back-pressure, a drain), the host's wait shows on the
    profiler's clock.  `void()` resolves the ticket deterministically
    WITHOUT trusting the device value (the recovery path's option for
    tickets whose commit a re-arm superseded).
    """

    __slots__ = ("seq", "ok", "dispatched_at", "resolved_at", "span_id",
                 "extras", "staged", "voided", "step", "_verdict",
                 "_on_resolve", "_tracer")

    def __init__(self, seq: int, ok: Any, *,
                 dispatched_at: Optional[float] = None,
                 span_id: Optional[int] = None,
                 extras: Optional[dict] = None,
                 staged: bool = False,
                 on_resolve: Optional[Callable[["CommitTicket"], None]]
                 = None,
                 tracer: Any = None, step: Any = None):
        self.seq = int(seq)
        self.ok = ok
        self.dispatched_at = (time.perf_counter() if dispatched_at is None
                              else float(dispatched_at))
        self.resolved_at: Optional[float] = None
        self.span_id = span_id
        self.extras = extras
        # staged = the verdict includes a device-side canary the host
        # could not know at dispatch (Pool defers abort bookkeeping to
        # resolution for these)
        self.staged = bool(staged)
        self.voided = False
        self.step = step
        self._verdict: Optional[bool] = None
        self._on_resolve = on_resolve
        self._tracer = tracer

    # -- state -----------------------------------------------------------------

    @property
    def resolved(self) -> bool:
        return self.resolved_at is not None

    @property
    def resolve_latency_ms(self) -> Optional[float]:
        """Dispatch-to-resolve wall (None while in flight)."""
        if self.resolved_at is None:
            return None
        return (self.resolved_at - self.dispatched_at) * 1e3

    def ready(self) -> bool:
        """True iff `result()` would not block (resolved, or the device
        scalar has landed)."""
        return self.resolved or _scalar_ready(self.ok)

    # -- resolution ------------------------------------------------------------

    def result(self, block: bool = True) -> Optional[bool]:
        """The commit verdict.  Returns None when `block=False` and the
        device scalar has not landed yet; otherwise fetches (blocking at
        most once — resolution is idempotent) and returns the bool."""
        if self.resolved:
            return self._verdict
        if not block and not _scalar_ready(self.ok):
            return None
        with (_NO_SPAN if self._tracer is None else
              self._tracer.phase("pool.resolve", step=self.step)):
            verdict = bool(jax.device_get(self.ok))
        self._finish(verdict)
        return self._verdict

    def void(self, verdict: bool = False) -> bool:
        """Resolve without consulting the device (deterministic verdict
        for a superseded commit); no-op if already resolved."""
        if not self.resolved:
            self.voided = True
            self._finish(bool(verdict))
        return bool(self._verdict)

    def _finish(self, verdict: bool) -> None:
        self._verdict = verdict
        self.resolved_at = time.perf_counter()
        if self._on_resolve is not None:
            cb, self._on_resolve = self._on_resolve, None
            cb(self)

    def __repr__(self) -> str:  # debugging aid, not a stable format
        state = ("voided" if self.voided else
                 repr(self._verdict) if self.resolved else "in-flight")
        return f"CommitTicket(seq={self.seq}, {state})"


class CommitRing:
    """The N-deep in-flight window (`ProtectConfig.pipeline_depth`).

    `submit` enqueues a fresh ticket, first force-resolving the OLDEST
    one when the ring is full (back-pressure: the pipeline never holds
    more than `depth` unresolved commits).  `poll` resolves every
    ticket whose scalar has landed — out of dispatch order — and
    `drain` resolves all of them (dispatch order, the deterministic
    boundary recovery/flush/scrub use).  `on_depth` fires with the new
    in-flight count whenever it changes (the Pool's depth gauge).
    """

    def __init__(self, depth: int = 1, *,
                 on_depth: Optional[Callable[[int], None]] = None):
        assert depth >= 1, f"pipeline depth must be >= 1, got {depth}"
        self.depth = int(depth)
        self._inflight: List[CommitTicket] = []
        self._on_depth = on_depth

    def __len__(self) -> int:
        return len(self._inflight)

    @property
    def in_flight(self) -> List[CommitTicket]:
        """The unresolved tickets, oldest first (a copy)."""
        return list(self._inflight)

    def _note_depth(self) -> None:
        if self._on_depth is not None:
            self._on_depth(len(self._inflight))

    def submit(self, ticket: CommitTicket) -> CommitTicket:
        """Enqueue; force-resolves the oldest ticket when full."""
        while len(self._inflight) >= self.depth:
            self._inflight.pop(0).result()
        self._inflight.append(ticket)
        self._note_depth()
        return ticket

    def poll(self) -> List[CommitTicket]:
        """Resolve every ticket whose device scalar already landed —
        out of dispatch order — and return them (possibly empty)."""
        done = [t for t in self._inflight if t.ready()]
        if done:
            self._inflight = [t for t in self._inflight
                              if not t.ready()]
            for t in done:
                t.result()
            self._note_depth()
        return done

    def drain(self) -> List[CommitTicket]:
        """Resolve ALL in-flight tickets (dispatch order) — the
        deterministic boundary before a flush/scrub/recovery."""
        done, self._inflight = self._inflight, []
        for t in done:
            t.result()
        self._note_depth()
        return done

    def void_all(self, verdict: bool = False) -> List[CommitTicket]:
        """Void every in-flight ticket (see CommitTicket.void) — for
        boundaries where the device verdicts were superseded (re-arm
        after a budget-exhausted storm)."""
        done, self._inflight = self._inflight, []
        for t in done:
            t.void(verdict)
        self._note_depth()
        return done
