"""Quickstart: protect any sharded JAX state with Pangolin-JAX.

    PYTHONPATH=src python examples/quickstart.py

The whole public surface is the `Pool` facade — the analogue of
Pangolin's three-call API (paper Listing 2):

    pgl_open            ->  Pool.open(state, specs, mesh=..., config=...)
    pgl_tx_begin/commit ->  with pool.transaction() as tx: tx.stage(new)
    pgl_tx_abort        ->  canary mismatch inside the context
    async commit (FliT) ->  pool.commit_async(new) -> CommitTicket;
                            pool.drain() at any boundary
    SIGBUS handler      ->  pool.recover(Fault.rank_loss(r))
    scrubbing thread    ->  pool.scrub() / pool.maybe_scrub()

`ProtectConfig` is the single knob: mode ladder (none < ml < mlp < mlpc,
plus replica), the Reed-Solomon syndrome stack height (redundancy r in
1..4 — any e <= r simultaneous rank losses reconstruct), the deferred
window W, and the scrub cadence.  This demo: build a pool over a sharded
pytree, commit a transactional update, lose a rank, recover it online,
scribble a page, scrub-detect + repair it, and abort a transaction whose
staging buffer smashed its canary.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import Fault, Pool, ProtectConfig
from repro.launch.mesh import make_mesh
from repro.runtime import failure

# 1. a sharded state pytree: FSDP weights, TP weights, a replicated scalar
mesh = make_mesh((4, 2))
specs = {"w_fsdp": P("data", "model"), "w_tp": P(None, "model"),
         "scale": P()}
state = {
    "w_fsdp": jnp.arange(16 * 64, dtype=jnp.float32).reshape(16, 64) * .01,
    "w_tp": jnp.ones((8, 32), jnp.bfloat16),
    "scale": jnp.float32(1.0),
}
state = jax.tree.map(
    lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), state, specs)

# 2. pgl_open: checksums detect corruption, XOR parity across the 4-rank
#    zone repairs it, at 1/4 storage overhead (1/G; 1% at G=100)
pool = Pool.open(state, specs, mesh=mesh,
                 config=ProtectConfig(mode="mlpc", block_words=64))
print("protected:", pool.overhead_report())

# 3. transactional update (open -> mutate the micro-buffer -> commit)
new_state = jax.tree.map(lambda x: (x * 2).astype(x.dtype), state)
with pool.transaction(rng_key=jax.random.PRNGKey(0)) as tx:
    tx.stage(new_state)
print(f"commit ok={tx.ok} step={pool.step}")

# 4. media error: lose data-rank 2 entirely; rebuild online from parity
want = np.asarray(pool.state["w_fsdp"]).copy()
pool.prot, event = failure.inject_rank_loss(pool.protector, pool.prot,
                                            rank=2)
rep = pool.recover(Fault.rank_loss(event.lost_rank))
assert rep.verified
assert np.array_equal(np.asarray(pool.state["w_fsdp"]), want)
print("rank-loss recovery: bit-exact")

# 5. silent scribble: flip bits, detect by scrub, repair the page
pool.prot, event = failure.inject_scribble(pool.protector, pool.prot,
                                           rank=1, word_offsets=[7])
report = pool.scrub()
print("scrub found corrupted (rank, page):", report.bad_locations)
assert report.repaired and report.repair_ok
assert np.array_equal(np.asarray(pool.state["w_fsdp"]), want)
print("scribble repair: bit-exact")

# 6. canary: a staged buffer overrun aborts the commit, state untouched
step_before = pool.step
with pool.transaction() as tx:
    tx.watch(failure.smashed_canary_buffer(4096))   # overrun staging buf
    tx.stage(jax.tree.map(jnp.zeros_like, new_state))
assert tx.aborted and not tx.ok and pool.step == step_before
assert np.array_equal(np.asarray(pool.state["w_fsdp"]), want)
print("canary abort: state untouched")

# 7. telemetry: every pool publishes into a host-side metrics registry
#    (zero compiled-byte overhead — benchmarks/obs_overhead.py proves
#    it) and folds its degradation signals into a HealthReport.  The
#    same surface backs the --metrics-dir / --trace-dir launch flags
#    (repro.launch.train / repro.launch.serve) and a Prometheus scrape.
stats = pool.stats()                    # host-only snapshot, no device sync
print(f"stats: commits={stats['commits']} recoveries="
      f"{stats['recoveries']} scrub_coverage="
      f"{stats['scrub']['full_fraction']:.2f}")
health = pool.health()                  # green | degraded | critical
print(f"health: {health.status} {health.reasons}")
assert health.status == "degraded"      # the repairing scrub left
assert health.suspect                   # failure suspicion outstanding
pool.scrub()                            # ...which a clean scrub heals
print(f"health after clean scrub: {pool.health().status}")
assert pool.health().status == "green"
assert stats["recoveries"] == 1 and stats["aborted_commits"] == 1
from repro.obs import prometheus_text   # the scrape-endpoint text format
assert "pool_commits_total" in prometheus_text(pool.metrics)
print("telemetry surface live")

# 8. multi-tenant: a PoolGroup hosts many pools at once.  Same-shape
#    same-config tenants share one cohort — one Protector, one compiled
#    program — and a commit wave lands them in ONE batched dispatch,
#    bit-identical to N separate pool.commit calls; a shared scrub
#    scheduler spreads verification over tenants under a page budget,
#    and QoS presets (GOLD/SILVER/BRONZE) pick protection + scrub weight.
from repro.tenancy import GOLD, PoolGroup


def make_state(k):                      # fresh buffers per tenant (the
    st = {                              # earlier steps donated `state`)
        "w_fsdp": jnp.arange(16 * 64, dtype=jnp.float32)
        .reshape(16, 64) * (.01 * k),
        "w_tp": jnp.ones((8, 32), jnp.bfloat16) * k,
        "scale": jnp.float32(k),
    }
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), st, specs)


grp = PoolGroup(mesh)
for k, tid in enumerate(("alice", "bob"), start=1):
    grp.admit(tid, make_state(k), specs, qos=GOLD)
updates = {tid: make_state(k + 10)
           for k, tid in enumerate(("alice", "bob"), start=1)}
verdicts = grp.commit(updates)          # ONE batched dispatch
assert all(bool(v) for v in verdicts.values())
grp.scrub_tick()                        # shared-scheduler scrub pass
assert grp.health()["status"] == "green"
assert np.array_equal(
    np.asarray(grp["alice"].pool.state["w_fsdp"]),
    np.asarray(updates["alice"]["w_fsdp"]))
print(f"pool group: {len(grp)} tenants, 1 cohort, batched commit ok")

# 9. async commit pipeline: `commit_async` returns a CommitTicket — a
#    future over the commit program's device verdict — and up to
#    `ProtectConfig.pipeline_depth` commits stay in flight at once, so
#    the host dispatches commit t+k while the device still runs commit
#    t.  Verdicts resolve out of dispatch order (`poll`), and `drain()`
#    at any boundary lands the pipeline bit-identical to synchronous
#    commits (flush / scrub / recover all drain first, automatically).
apool = Pool.open(make_state(5), specs, mesh=mesh,
                  config=ProtectConfig(mode="mlpc", block_words=64,
                                       pipeline_depth=4))
tickets = []
cur = make_state(5)
for i in range(4):
    cur = jax.tree.map(lambda x: (x * 1.01).astype(x.dtype), cur)
    tickets.append(apool.commit_async(cur, data_cursor=i))
print(f"async: {apool.in_flight} commits in flight")
apool.drain()
assert all(t.result() for t in tickets)          # every verdict landed
lat = apool.stats()["commit_resolve_ms"]
print(f"async: drained, resolve p99={lat['p99']:.2f} ms "
      f"(span id of last dispatch: {tickets[-1].span_id})")
print("all quickstart checks passed")
