"""A commit that takes ownership of its new state (`donate_new`).

The trainer hands each step's micro-buffer to the commit and never reads
it again, so the commit may keep the committed state in that buffer
instead of copying it into the old state's.  These tests pin the
mechanism (the compiled program aliases the whole state and copies none
of it), the ownership contract (the caller's buffer survives by default
and is consumed with the flag), and that nothing observable changes:
state, log, digest and verdicts bit-identical with the flag on and off,
an abort included.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.conftest import small_state
from repro import Pool, ProtectConfig
from repro.configs.base import ModelConfig, TrainConfig
from repro.core.txn import Protector
from repro.runtime.trainer import Trainer

MODES = ("ml", "mlpc")
DEPTHS = (1, 4)
COUNTER = "pool_commits_donated_new_total"

CFG = ModelConfig(
    name="t_own", family="dense", n_layers=1, d_model=32, n_heads=4,
    n_kv=2, d_ff=64, vocab=128, param_dtype="float32",
    compute_dtype="float32")


def _trainer(mesh, mode, depth, seed=0):
    t = Trainer(CFG, TrainConfig(learning_rate=1e-3, warmup_steps=2,
                                 total_steps=100),
                ProtectConfig(mode=mode, block_words=64,
                              pipeline_depth=depth),
                mesh, seq_len=16, global_batch=8, seed=seed)
    t.initialize()
    return t


def _copy_shapes(hlo: str) -> list:
    """The result shape of every `copy` in a compiled module's text."""
    return re.findall(r"= \w+\[([\d,]*)\][^=\n]* copy\(", hlo)


def _host(tree):
    return [np.asarray(x) for x in jax.device_get(jax.tree.leaves(tree))]


@pytest.mark.parametrize("depth", DEPTHS)
@pytest.mark.parametrize("mode", MODES)
def test_consuming_commit_aliases_the_state(mesh42, mode, depth):
    """The consuming program aliases at least the state's bytes and
    copies no leaf of it; the default donating program (the one the
    benchmark's AOT compile lowers) still copies every leaf."""
    t = _trainer(mesh42, mode, depth)
    p = t.pool.protector
    leaves = jax.tree.leaves(t.prot.state)
    state_bytes = sum(x.addressable_shards[0].data.nbytes for x in leaves)
    state_shapes = {",".join(map(str, x.addressable_shards[0].data.shape))
                    for x in leaves if x.ndim}

    def compiled(donate_new):
        prog = p.commit_program(donate=True, donate_new=donate_new)
        assert prog.__name__ == "commit"      # traces as jit_commit
        return prog.lower(*Protector.commit_operands(
            t.prot, t.prot.state, donate_new=donate_new)).compile()

    own = compiled(True)
    assert own.memory_analysis().alias_size_in_bytes >= state_bytes
    assert not state_shapes & set(_copy_shapes(own.as_text()))
    old = compiled(False)
    assert state_shapes <= set(_copy_shapes(old.as_text()))


@pytest.mark.parametrize("depth", DEPTHS)
@pytest.mark.parametrize("mode", MODES)
def test_trainer_bit_identical_with_and_without_ownership(mesh42, mode,
                                                          depth):
    """Several steps with a static canary abort in the middle: state,
    log, digest, row, step and verdicts agree bit for bit whether the
    commit consumes the micro-buffer (and `run` recycles the retired
    state) or copies it, and the abort keeps the old state."""
    runs = {}
    for own in (True, False):
        t = _trainer(mesh42, mode, depth)
        if not own:
            commit_async = t.pool.commit_async
            t.pool.commit_async = lambda *a, **k: commit_async(
                *a, **{**k, "donate_new": False})
        outs = t.run(3)
        before = _host(t.prot.state)
        aborted = t.step(canary_ok=False)
        assert not aborted["committed"]
        for a, b in zip(before, _host(t.prot.state)):
            np.testing.assert_array_equal(a, b)
        outs += [aborted] + t.run(3)
        assert t.pool.metrics.counter(COUNTER).value == (7 if own else 0)
        runs[own] = (outs, _host(t.prot))
    (outs_a, prot_a), (outs_b, prot_b) = runs[True], runs[False]
    assert [(o["committed"], o["loss"]) for o in outs_a] == \
        [(o["committed"], o["loss"]) for o in outs_b]
    assert len(prot_a) == len(prot_b)
    for a, b in zip(prot_a, prot_b):
        np.testing.assert_array_equal(a, b)


def _pointers(tree):
    return [x.addressable_shards[0].data.unsafe_buffer_pointer()
            for x in jax.tree.leaves(tree)]


@pytest.mark.parametrize("depth", DEPTHS)
@pytest.mark.parametrize("mode", MODES)
def test_run_writes_each_step_into_the_retired_state(mesh42, mode, depth):
    """Inside `Trainer.run`, every step after the first writes its
    micro-buffer into the buffers of the state the step before retired:
    no state-sized allocation per step, no new compile once warm."""
    t = _trainer(mesh42, mode, depth)
    t.run(2)
    into = t._train_step_into
    warm = (t._train_step._cache_size(), into._cache_size())
    calls = []

    def spy(state, batch, retired):
        before = _pointers(retired)
        out = into(state, batch, retired)
        calls.append(before == _pointers(out[0]))
        return out

    t._train_step_into = spy
    t.run(5)
    assert calls == [True] * 4
    assert (t._train_step._cache_size(), into._cache_size()) == warm


def _evolve(state, k):
    return jax.tree.map(lambda x: (x * 2 + k).astype(x.dtype), state)


@pytest.mark.parametrize("depth", DEPTHS)
@pytest.mark.parametrize("mode", MODES)
def test_pool_commit_consumes_state_new_only_when_asked(mesh42, mode, depth):
    """By default the caller's `state_new` stays alive; with
    `donate_new=True` its buffers become the committed state's and the
    caller's handles are deleted.  The counter counts exactly the
    consuming commits."""
    state, specs, _ = small_state(mesh42)
    pool = Pool.open(state, specs, mesh=mesh42,
                     config=ProtectConfig(mode=mode, block_words=64,
                                          pipeline_depth=depth))
    commit = pool.commit if depth == 1 else pool.commit_async
    # built up front: the pool donates `state` itself into the first commit
    kept = _evolve(state, 1)
    givens = [_evolve(state, 2) for _ in range(4)]
    # a host view of a CPU buffer pins it against donation: read a twin
    want = _host(givens.pop())
    commit(kept, data_cursor=1)
    assert not any(x.is_deleted() for x in jax.tree.leaves(kept))
    for i, given in enumerate(givens):
        commit(given, data_cursor=2 + i, donate_new=True)
        assert all(x.is_deleted() for x in jax.tree.leaves(given))
        for a, b in zip(want, _host(pool.state)):
            np.testing.assert_array_equal(a, b)
    pool.drain()
    assert pool.metrics.counter(COUNTER).value == 3
    assert pool.metrics.counter("pool_commits_total").value == 4
    if mode == "mlpc":
        assert not pool.scrub().suspect


def test_staged_and_deferred_commits_do_not_consume(mesh42):
    """The staged-canary path keeps the old state as its select's False
    arm and the deferred engine keeps its own buffers: both ignore
    `donate_new`, leave the caller's state alive and count nothing."""
    state, specs, _ = small_state(mesh42)
    staged = Pool.open(state, specs, mesh=mesh42,
                       config=ProtectConfig(mode="mlpc", block_words=64))
    deferred = Pool.open(state, specs, mesh=mesh42,
                         config=ProtectConfig(mode="mlpc", block_words=64,
                                              window=4))
    for pool, canary in ((staged, jnp.asarray(True)), (deferred, True)):
        new = _evolve(state, 1)
        pool.commit_async(new, canary_ok=canary, donate_new=True)
        assert bool(pool.drain()[0].result())
        assert not any(x.is_deleted() for x in jax.tree.leaves(new))
        assert pool.metrics.counter(COUNTER).value == 0
