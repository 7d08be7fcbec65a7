"""Fault-tolerant training loop: Pangolin transactions around train steps.

Per step:  batch <- deterministic pipeline(cursor)
           micro-buffer   = train_step(state, batch)      (pure staging)
           commit         = canary check -> redo record -> protection ->
                            functional swap
           scrub every N commits; online recovery on failure events;
           async disk checkpoints as the backstop tier.

All protection plumbing lives in the `Pool` facade (repro/pool.py): the
trainer builds one cold pool from its `ProtectConfig` and routes every
commit / scrub / recovery through it.  The config's `window` selects the
engine (1 = synchronous single-sweep, W>1 = deferred epochs whose redo
log still persists per step and covers the window for crash replay);
`scrub_period` drives `pool.maybe_scrub()`; faults funnel through
`pool.recover(Fault...)`, which flushes any open window first.

The step path's boundaries are profiler spans on the pool's tracer
(`Tracer.phase`): `trainer.dispatch` around a step's launch, holding
`trainer.train_step` and the pool's `pool.commit_dispatch`, and
`trainer.resolve` around its resolution, holding the pool's
`pool.resolve` wait on the verdict; each carries `step=<cursor>`.

`overlap_commit` keeps protection off the critical path: step t+1's
compute is dispatched before step t's commit (and, at an epoch boundary,
its flush) is awaited — the programs are independent, so the async
runtime overlaps the parity reduce-scatter with forward compute.  `run`
resolves commits one step behind; an explicit `step()` stays fully
synchronous.

Crash recovery (paper §3.6): restore the newest checkpoint, then replay
the redo log's marked records — the deterministic pipeline regenerates
each logged batch from its cursor, and the row digest verifies each
replayed step landed bit-identically (the deferred engine keeps the
digest current per step precisely so every log record stays
replay-verifiable mid-window).
"""
from __future__ import annotations

import dataclasses
import functools
import os
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.configs.base import ModelConfig, ProtectConfig, TrainConfig
from repro.core import redolog
from repro.data.synthetic import batch_for
from repro.models import api
from repro.models.transformer import build_model
from repro.optim import build_optimizer
from repro.pool import Fault, Pool, PoolHost


class Trainer(PoolHost):
    def __init__(self, cfg: ModelConfig, train_cfg: TrainConfig,
                 protect_cfg: ProtectConfig, mesh, *,
                 seq_len: int = 128, global_batch: int = 8,
                 checkpoint_dir: Optional[str] = None, seed: int = 0,
                 metrics_dir: Optional[str] = None,
                 trace_dir: Optional[str] = None,
                 metrics_every: int = 25):
        self.cfg = cfg
        self.train_cfg = train_cfg
        self.protect_cfg = protect_cfg
        self.mesh = mesh
        self.seq_len = seq_len
        self.global_batch = global_batch
        self.seed = seed
        self.overlap_commit = bool(protect_cfg.overlap_commit)
        self.window = int(protect_cfg.window)
        # overlap_commit is the legacy one-behind pipeline; fold it into
        # the commit ring as an effective depth of 2 (dispatch t+1
        # before awaiting t) so `run` has exactly one pipelining
        # mechanism — the N-deep ring
        depth = int(protect_cfg.pipeline_depth)
        if self.overlap_commit and depth < 2:
            depth = 2
            protect_cfg = dataclasses.replace(protect_cfg,
                                              pipeline_depth=depth)
        self.pipeline_depth = depth
        self.protect_cfg = protect_cfg

        self.model = build_model(cfg, mesh)
        self.optimizer = build_optimizer(train_cfg, cfg)
        self.stream = batch_for(cfg, seq_len, global_batch, seed)

        abstract_state = api.abstract_train_state(self.model, self.optimizer)
        state_specs = api.train_state_specs(self.model, self.optimizer, mesh)
        # telemetry surfaces (repro.obs): --trace-dir gives the pool a
        # file-backed tracer; --metrics-dir makes the step loop publish
        # the registry + stats snapshot every `metrics_every` resolved
        # steps (publication is host-side; see pool.stats())
        self.metrics_dir = metrics_dir
        self.metrics_every = max(1, int(metrics_every))
        tracer = None
        if trace_dir:
            os.makedirs(trace_dir, exist_ok=True)
            tracer = obs.Tracer(
                os.path.join(trace_dir, "trainer.trace.jsonl"))
        # one cold pool: engine selection, scrub pressure loop and
        # window-meta replication all wired from the ProtectConfig
        self.pool = Pool(mesh, abstract_state, state_specs, protect_cfg,
                         on_freeze=self.freeze, on_resume=self.resume,
                         tracer=tracer)

        self._train_step = jax.jit(api.make_train_step(
            self.model, self.optimizer, train_cfg))

        def train_step(state, batch, into):
            del into            # a retired state: only its buffers are used
            # read at trace time: one train step whichever buffers it fills
            return self._train_step(state, batch)

        # `run` writes each step's micro-buffer into the buffers of the
        # state the previous commit retired, so two states stay live and
        # no launch waits for the allocator to free one
        self._train_step_into = jax.jit(train_step, donate_argnums=(2,),
                                        keep_unused=True)
        self._batch_shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s), api.batch_specs(cfg, mesh),
            is_leaf=lambda x: isinstance(x, P))

        self.checkpoint_dir = checkpoint_dir
        self._ckpt_mgr = None
        if checkpoint_dir:
            from repro.checkpoint.manager import CheckpointManager
            self._ckpt_mgr = CheckpointManager(checkpoint_dir, mesh,
                                               state_specs)
        self.cursor = 0
        self.history: list = []
        self._frozen = False
        self._host_step = 0
        # chaos/observability: hooks fired after every resolved step with
        # the step's summary dict (schedule attachment, tracing)
        self._step_hooks: list = []
        # per-replica step-time dilation fed to the straggler policy when
        # ProtectConfig.straggler_threshold wires one into the pool; the
        # chaos runner (and tests) dilate entries to simulate a slow
        # replica without actually sleeping per rank
        self.replica_slowdown = np.ones(self.pool.protector.group_size)
        # verify-at-open (paper's default policy): checksums of the old
        # state verified inside every synchronous commit, abort on
        # mismatch — a window=1 engine feature
        self.verify_old = False

    # pool delegation (protector / scrubber / prot / flush) comes from
    # repro.pool.PoolHost

    # -- lifecycle ---------------------------------------------------------------

    def initialize(self, key=None) -> None:
        key = key if key is not None else jax.random.PRNGKey(self.seed)
        # created sharded in one program: no device stages the whole state
        shardings = jax.tree.map(
            lambda s: NamedSharding(self.mesh, s),
            api.train_state_specs(self.model, self.optimizer, self.mesh),
            is_leaf=lambda x: isinstance(x, P))
        state = jax.jit(functools.partial(api.init_train_state, self.model,
                                          self.optimizer),
                        out_shardings=shardings)(key)
        self.pool.init(state)
        self._host_step = 0

    def freeze(self):
        """Paper's pool freeze: drain outstanding work before recovery."""
        self._frozen = True
        if self.prot is not None:
            jax.block_until_ready(jax.tree.leaves(self.prot.state)[0])

    def resume(self):
        self._frozen = False

    # -- stepping ----------------------------------------------------------------

    def _dispatch_step(self, *, canary_ok: bool = True,
                       into=None) -> dict:
        """Dispatch compute + commit without any host synchronization.

        Returns the pending record `_resolve_step` finishes later; only
        values that survive buffer donation are captured (ok / metrics
        are fresh program outputs, never donated operands).  `into`, a
        state retired by an earlier commit, is donated to receive the
        micro-buffer; the record's `retired` is the state this step's
        commit retired (None if the commit did not consume the
        micro-buffer) — pop it before the record waits in the ring.
        """
        with self.pool.tracer.phase("trainer.dispatch", step=self.cursor):
            return self._dispatch(canary_ok, into)

    def _dispatch(self, canary_ok, into) -> dict:
        assert self.prot is not None and not self._frozen
        t0 = time.perf_counter()
        batch = self.stream.device_batch(self.cursor, self._batch_shardings)
        if self.pool.dropped_replicas:
            # straggler mitigation: zero the dropped replicas' examples
            # out of the loss (replica-major layout, data-axis sharded)
            mask = self.pool.straggler.loss_mask(self.global_batch)
            batch["loss_mask"] = jax.device_put(
                jnp.asarray(mask), NamedSharding(self.mesh, P("data")))
        rng = jax.random.fold_in(jax.random.PRNGKey(self.seed), self.cursor)
        cursor_before = self.cursor
        state = self.prot.state
        with self.pool.tracer.phase("trainer.train_step", step=self.cursor):
            if into is None:
                new_state, metrics = self._train_step(state, batch)
            else:
                new_state, metrics = self._train_step_into(state, batch,
                                                           into)
        # new_state is the step's micro-buffer, held by nothing else: the
        # commit takes it over as the committed state, no copy
        ticket = self.pool.commit_async(new_state, data_cursor=self.cursor,
                                        rng_key=rng, canary_ok=canary_ok,
                                        verify_old=self.verify_old,
                                        donate_new=True)
        # a donating pool drops the old state at commit (`Pool.donate`);
        # once the commit consumed the micro-buffer nothing else reads it
        retired = state if (self.pool.donate and jax.tree.leaves(
            new_state)[0].is_deleted()) else None
        self.cursor += 1          # optimistic; rolled back on late abort
        return {"ticket": ticket, "loss": metrics["loss"],
                "cursor_before": cursor_before, "t0": t0,
                "retired": retired}

    def _resolve_step(self, pending: dict) -> dict:
        """Await a dispatched step's commit; bookkeeping + scrub cadence."""
        with self.pool.tracer.phase("trainer.resolve",
                                    step=pending["cursor_before"]):
            return self._resolve(pending)

    def _resolve(self, pending: dict) -> dict:
        committed = bool(pending["ticket"].result())
        if committed:
            self._host_step += 1
        else:
            self.cursor = pending["cursor_before"]
        out = {"step": self._host_step,
               "loss": float(jax.device_get(pending["loss"])),
               "committed": committed}
        if self.pool.straggler is not None:
            # one wall-clock measurement per step, dilated per replica —
            # a real fleet reports each replica's own duration; here the
            # dilation vector stands in for the slow ranks
            dt = time.perf_counter() - pending["t0"]
            dropped = self.pool.observe_commit_times(
                dt * self.replica_slowdown)
            if not dropped.all():
                out["dropped_replicas"] = sorted(self.pool.dropped_replicas)
        self.history.append(out)
        report = self.pool.maybe_scrub()
        if report is not None:
            out["scrub"] = dataclasses.asdict(report)
        # step-loop publication: the loss/verdict were already fetched
        # above, so folding them into the registry costs no extra sync
        reg = self.pool.metrics
        reg.counter("trainer_steps_total").inc()
        if not committed:
            reg.counter("trainer_aborted_steps_total").inc()
        reg.gauge("trainer_loss").set(out["loss"])
        if (self.metrics_dir
                and self._host_step % self.metrics_every == 0):
            obs.write_metrics(reg, self.metrics_dir, prefix="trainer",
                              stats=self.pool.stats())
        for hook in list(self._step_hooks):
            hook(self, out)
        return out

    def add_step_hook(self, fn) -> None:
        """Register `fn(trainer, out_dict)`, fired after every resolved
        step — the chaos campaign's schedule attachment point."""
        self._step_hooks.append(fn)

    def step(self, *, canary_ok: bool = True) -> dict:
        return self._resolve_step(self._dispatch_step(canary_ok=canary_ok))

    def run(self, n_steps: int, checkpoint_every: int = 0) -> list:
        """The training loop on the commit ring: up to
        `pipeline_depth` steps stay dispatched-but-unresolved (compute
        t+k launches before commit t's verdict is fetched), so the
        async runtime overlaps parity reduce-scatters and flushes with
        forward compute across the whole ring, not just one step
        behind.  Depth 1 resolves every step inline (the synchronous
        loop); the trailing in-flight steps drain at the end, so a
        `run` boundary is always fully resolved.  From the second step
        on, each step's micro-buffer is written into the buffers of the
        state the step before it retired.
        """
        def maybe_checkpoint():
            if (outs and checkpoint_every and self._ckpt_mgr
                    and outs[-1]["step"] % checkpoint_every == 0
                    and outs[-1]["committed"]):
                self.save_checkpoint()

        outs = []
        pending: list = []
        retired = None      # dropped with the run: no third state at rest
        for _ in range(n_steps):
            rec = self._dispatch_step(into=retired)
            retired = rec.pop("retired")
            if self.pipeline_depth > 1:
                pending.append(rec)
                if len(pending) >= self.pipeline_depth:
                    outs.append(self._resolve_step(pending.pop(0)))
            else:
                outs.append(self._resolve_step(rec))
            maybe_checkpoint()
        while pending:
            # the trailing pipelined steps get the same checkpoint
            # cadence the synchronous path would give them
            outs.append(self._resolve_step(pending.pop(0)))
            maybe_checkpoint()
        return outs

    # -- fault handling -----------------------------------------------------------

    def on_failure(self, event) -> dict:
        """Online recovery entry point (the SIGBUS-handler analogue).

        A thin adapter now: `Pool.recover` owns the whole sequence —
        capture the survivors' replicated window metadata, flush any
        open window (the cached row is a separate buffer the failure's
        state corruption never touched, so the refreshed redundancy
        describes intended values), dispatch the right reconstruction,
        collapse the adaptive window, and bound the lost window from the
        replicated mask + digest.  (A full machine loss that also takes
        the cache and accumulator down falls back to checkpoint +
        redo-log replay — see EXPERIMENTS.md §Perf, window-loss
        semantics.)
        """
        assert self.prot is not None
        rep = self.pool.recover(Fault.from_event(event))
        if rep is None:
            # a recovery was already in flight; this fault was queued and
            # will drain right after it (async-safe re-entry)
            return {"queued": True}
        return dataclasses.asdict(rep)

    # -- checkpoint / crash recovery ------------------------------------------------

    def save_checkpoint(self, wait: bool = False) -> None:
        assert self._ckpt_mgr is not None and self.prot is not None
        self._ckpt_mgr.save(int(jax.device_get(self.prot.step)),
                            self.prot.state,
                            extra={"cursor": self.cursor,
                                   "log": jax.device_get(self.prot.log)
                                   if self.prot.log is not None else None})
        if wait:
            self._ckpt_mgr.wait()

    def restore_from_checkpoint(self, replay: bool = True) -> dict:
        """Crash recovery: newest checkpoint + redo-log replay (§3.6).

        Replay works identically for both cadences: deferred commits keep
        the row digest current per step, so every marked record's digest
        is checkable even when the crash hit mid-window.
        """
        assert self._ckpt_mgr is not None
        self._ckpt_mgr.wait()
        step, state, extra = self._ckpt_mgr.restore_latest()
        prot = self.protector.init(state)
        self.prot = dataclasses.replace(
            prot, step=jnp.asarray(step, jnp.uint32))
        self._host_step = int(step)
        self.cursor = int(extra.get("cursor", step))
        replayed = []
        if replay and extra.get("log") is not None:
            log = extra["log"]
            if isinstance(log, dict):
                # manifest round-trip: pytrees serialize as
                # {"__pytree__": name, "children": [...]} with ndarray
                # children as {"__ndarray__": ..., "dtype": ..., "shape": ...}
                def _arr(c):
                    if isinstance(c, dict) and "__ndarray__" in c:
                        return jnp.asarray(np.asarray(
                            c["__ndarray__"], dtype=c["dtype"]
                        ).reshape(c["shape"]))
                    return jnp.asarray(c)
                log = redolog.RedoLog(*[_arr(c) for c in log["children"]])
            else:
                log = redolog.RedoLog(*[jnp.asarray(x) for x in
                                        (log.step, log.data_cursor, log.rng,
                                         log.digest, log.mark)])
            for s in redolog.replayable_steps(log, step):
                rec = redolog.lookup(log, s)
                self.cursor = int(jax.device_get(rec["data_cursor"]))
                out = self.step()
                replayed.append(out["step"])
                # verify the replayed step reproduced the logged digest
                if self.prot.digest is not None:
                    dig = np.asarray(jax.device_get(
                        self.prot.digest)).reshape(-1, 2)[0]
                    want = np.asarray(jax.device_get(rec["digest"]))
                    if not np.array_equal(dig, want):
                        raise RuntimeError(
                            f"replay digest mismatch at step {s}")
        return {"restored_step": step, "replayed": replayed}
