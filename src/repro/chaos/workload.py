"""Deterministic synthetic traffic over a Pool.

The golden-run bit-identity check constrains the traffic generator
hard: the state trajectory must be (a) a pure function of (seed, step),
(b) bit-identical across mesh shapes (rescale under traffic must land
on the same bytes), and (c) cheap enough that per-commit latency is
dominated by the protection stack, not the "model".  An elementwise
f32 recurrence satisfies all three — elementwise ops have no
cross-shard reduction order to vary with sharding, so resharding the
state mid-run cannot perturb a single ulp.

The trainer/server runtimes are exercised by the schedule-attachment
path (runner.attach_schedule) instead: their loss-masked gradients are
deliberately NOT bit-identical under straggler drops, so they get
liveness + recovery assertions rather than golden diffs.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ProtectConfig
from repro.launch.mesh import make_mesh
from repro.pool import Pool

# the traffic recurrence: w <- w * GAIN + (step % PERIOD) * STEP_BIAS.
# GAIN keeps magnitudes stable over hundreds of steps; the bias term
# makes every step's output distinct (a stuck commit is visible).
GAIN = np.float32(1.0000001)
STEP_BIAS = np.float32(1e-6)
PERIOD = 7


def _initial_host_state(n_words: int, seed: int) -> np.ndarray:
    # Weyl-style integer mix — deterministic, seed-sensitive, no RNG
    # state to carry
    idx = np.arange(n_words, dtype=np.uint64)
    mixed = (idx * np.uint64(2654435761) + np.uint64(seed * 97 + 1))
    return ((mixed % np.uint64(1000003)).astype(np.float32)
            / np.float32(1000.0))


class PoolWorkload:
    """Sustained synthetic commit traffic against one protected pool."""

    def __init__(self, mesh, config: ProtectConfig, *,
                 n_bytes: int = 1 << 16, seed: int = 0,
                 straggler_policy=None):
        self.mesh = mesh
        self._mesh0 = mesh         # golden runs on the pre-rescale mesh
        self.config = config
        self.seed = int(seed)
        g = mesh.shape["data"]
        n = max(n_bytes // 4, g)
        self.n_words = (n + g - 1) // g * g
        self.specs = {"w": P("data")}
        host = {"w": _initial_host_state(self.n_words, self.seed)}
        state = self._put(host, mesh)
        # donate=False: the traffic step re-reads pool.state every
        # commit, and scenarios snapshot/restore freely
        self.pool = Pool.open(state, self.specs, mesh=mesh,
                              config=config, donate=False,
                              straggler_policy=straggler_policy)
        self.t = 0
        self._step_fn = jax.jit(
            lambda s, c: {"w": s["w"] * GAIN + c})

    def _put(self, host_state, mesh):
        sh = jax.tree.map(lambda s: NamedSharding(mesh, s), self.specs,
                          is_leaf=lambda x: isinstance(x, P))
        return jax.tree.map(
            lambda a, s: jax.device_put(jnp.asarray(a), s),
            host_state, sh)

    # -- traffic ----------------------------------------------------------------

    def bias(self, t: int) -> np.float32:
        return np.float32(t % PERIOD) * STEP_BIAS

    def traffic_step(self) -> bool:
        """One commit of traffic; blocks (latency measurements want the
        full commit on the clock) and returns the commit verdict."""
        new_state = self._step_fn(self.pool.state,
                                  jnp.float32(self.bias(self.t)))
        ok = self.pool.commit(new_state, data_cursor=self.t)
        jax.block_until_ready(self.pool.prot.state)
        self.t += 1
        return bool(jax.device_get(ok))

    # -- snapshot / restore / rescale -------------------------------------------

    def snapshot(self) -> dict:
        """Host copy of (state, t) — the checkpoint-tier stand-in."""
        self.pool.flush()
        return {"t": self.t,
                "state": jax.device_get(self.pool.state)}

    def restore(self, snap: dict) -> None:
        """Re-arm from a snapshot: fresh protection over restored bytes
        (the budget-exhausted path's checkpoint + re-protect)."""
        self.t = int(snap["t"])
        self.pool.init(self._put(snap["state"], self.mesh))

    def replay_to(self, t_target: int) -> None:
        """Deterministically re-run traffic up to step `t_target`."""
        while self.t < t_target:
            self.traffic_step()

    def rescale(self, shape) -> None:
        """Elastic resize under traffic: (data, model) mesh shape."""
        new_mesh = make_mesh(shape)
        self.pool = self.pool.rescale(new_mesh)
        self.mesh = new_mesh

    # -- endings ----------------------------------------------------------------

    def final_host(self) -> dict:
        """Flushed host copy of the state (the golden-diff operand)."""
        self.pool.flush()
        return jax.device_get(self.pool.state)

    def golden(self, n_steps: int) -> dict:
        """The fault-free reference: same seed, same steps, no chaos —
        run on a fresh pool so nothing of this run leaks in."""
        ref = PoolWorkload(self._mesh0, self.config,
                           n_bytes=self.n_words * 4, seed=self.seed)
        for _ in range(n_steps):
            ref.traffic_step()
        return ref.final_host()
