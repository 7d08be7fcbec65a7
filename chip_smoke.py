#!/usr/bin/env python3
"""Chip smoke test: Pangolin's protected commit path, once, on a TPU.

    python chip_smoke.py [--seed N]              # one chip (the default)
    python chip_smoke.py --chips 4 [--seed N]    # the four-chip MLPC path

One chip runs three phases:
  (a) kernels — every `kernels/ops.py` commit entry the engines call, run
      natively (Mosaic, not interpret mode, not the jnp oracle) on a 64 MB
      row of 16384 pages x 1024 words made from the seed, each compared
      bit for bit with its oracle in `kernels/ref.py`;
  (b) training — `Trainer` on full-width qwen2-0.5b over a (1, 1) mesh with
      protect="ml", through the calls `launch/train.py` makes: a few steps,
      finite losses, green health;
  (c) serving — `Server` on the same model with protect="ml": batch 4,
      prompt 16, 16 new tokens, equal to an unprotected server's tokens.
A zone is the mesh's data axis, so one chip holds no parity: (b) and (c)
run no kernel, which is why (a) drives the kernels directly.

`--chips 4` runs only the path that exists across chips, and what it is
compared with: qwen2-0.5b on a (4, 1) mesh with MLPC at r=1 and at r=3 —
steps, one rank loss, `pool.recover`, a full `pool.scrub` that must come
back clean, more steps — and a final train state bit-identical to the
same steps run with protect="none".

The script exits non-zero where JAX finds no TPU (it never falls back to
the CPU) and when any phase fails.  The last line of stdout is
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
The times it prints are smoke timings, not metrics.
"""
import argparse
import functools
import gc
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "qwen2-0.5b"
N_PAGES, PAGE_WORDS = 16384, 1024         # the kernel phase's 64 MB row
TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ = 3, 4, 512
SERVE_BATCH, SERVE_PROMPT, SERVE_NEW = 4, 16, 16
LOST_RANK = 2


def log(msg: str) -> None:
    print(msg, flush=True)


def timed(name: str, fn, *args, **kw):
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    log(f"[smoke timing] {name}: {time.perf_counter() - t0:.1f} s")
    return out


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def bits_equal(got, want) -> bool:
    """Bit-for-bit equality of two pytrees of arrays, compared on the host."""
    import jax
    import numpy as np

    def raw(x):
        x = np.asarray(x)
        return x.dtype, x.shape, x.reshape(-1).view(np.uint8)

    g = jax.tree.leaves(jax.device_get(got))
    w = jax.tree.leaves(jax.device_get(want))
    if len(g) != len(w):
        return False
    for a, b in zip(g, w):
        (da, sa, ba), (db, sb, bb) = raw(a), raw(b)
        if da != db or sa != sb or not np.array_equal(ba, bb):
            return False
    return True


def peak_gib(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2**30:.2f} GiB"


# -- (a) kernels ---------------------------------------------------------------

def kernel_phase(seed: int, n: int = N_PAGES, bw: int = PAGE_WORDS) -> None:
    import jax
    import jax.numpy as jnp

    from repro.configs.base import ProtectConfig
    from repro.core import gf
    from repro.kernels import ops, ref

    check(ops._pallas_path(None) is False,
          "the commit kernels would run as jnp oracles or in interpret "
          "mode, not as native Mosaic kernels")
    u32 = jnp.uint32
    k_old, k_new, k_acc = jax.random.split(jax.random.PRNGKey(seed), 3)
    old = jax.random.bits(k_old, (n, bw), u32)
    new = jax.random.bits(k_new, (n, bw), u32)
    acc = jax.random.bits(k_acc, (n, bw), u32)
    # every 97th page fails verify-at-open
    stored = ref.fletcher_blocks_ref(old)
    stored = stored.at[::97, 1].set(stored[::97, 1] ^ u32(1))
    co3 = jnp.asarray([gf.pow_g_int(k * 1) for k in range(3)], u32)
    one = jnp.ones((1,), u32)                  # the r=1 stack: g^0 only
    pc = ProtectConfig()
    cb = ops.stream_chunk_blocks(n, bw,
                                 threshold_words=pc.stream_threshold_words,
                                 chunk_words=pc.stream_chunk_words)
    check(cb is not None, f"a {n}-page row must take the streamed path")
    delta = (old ^ new).reshape(-1)
    tenants = 4

    def per_tenant(x):
        return x.reshape((tenants, n // tenants) + x.shape[1:])

    def tb_oracle(old, new, stored, co):       # one oracle call per tenant
        outs = [ref.fused_verify_commit_s_ref(o, w, s, co)
                for o, w, s in zip(old, new, stored)]
        return (jnp.stack([sd.reshape(co.shape[0], -1) for sd, _, _ in outs]),
                jnp.stack([c for _, c, _ in outs]),
                jnp.stack([b for _, _, b in outs]))

    def tail(*xs):                      # a partial last streamed chunk
        return tuple(x[:n - 5] for x in xs)

    stream = functools.partial(ops.fused_verify_commit_s_stream,
                               chunk_blocks=cb)

    def r1(entry):                      # engines pass no coefficients at r=1
        return lambda old, new, stored, _: entry(old, new, stored)

    # (name, kernel entry, its oracle, operands); the operands are jit
    # arguments, never closed-over constants
    cases = [
        ("fused_verify_commit_s r=1", r1(ops.fused_verify_commit_s),
         ref.fused_verify_commit_s_ref, (old, new, stored, one)),
        ("fused_verify_commit_s r=3", ops.fused_verify_commit_s,
         ref.fused_verify_commit_s_ref, (old, new, stored, co3)),
        ("fused_verify_commit_s_stream r=1", r1(stream),
         ref.fused_verify_commit_s_stream_ref, (old, new, stored, one)),
        ("fused_verify_commit_s_stream r=3", stream,
         ref.fused_verify_commit_s_stream_ref, (old, new, stored, co3)),
        ("fused_verify_commit_s_stream r=3, ragged tail", stream,
         ref.fused_verify_commit_s_stream_ref,
         tail(old, new, stored) + (co3,)),
        ("fused_accum_commit", ops.fused_accum_commit,
         ref.fused_accum_commit_ref, (acc, old, new)),
        ("fused_accum_commit_stream",
         functools.partial(ops.fused_accum_commit_stream, chunk_blocks=cb),
         ref.fused_accum_commit_stream_ref, (acc, old, new)),
        ("fletcher_stream",
         functools.partial(ops.fletcher_stream, chunk_blocks=cb),
         ref.fletcher_stream_ref, (new,)),
        ("syndrome_scale r=3 (sdelta_stack)", ops.syndrome_scale,
         ref.sdelta_stack_ref, (delta, co3)),
        ("fused_verify_commit_s_tb r=3, 4 tenants",
         ops.fused_verify_commit_s_tb, tb_oracle,
         (per_tenant(old), per_tenant(new), per_tenant(stored), co3)),
    ]
    for name, kernel, oracle, operands in cases:
        got = jax.block_until_ready(kernel(*operands))
        want = jax.jit(oracle)(*operands)
        check(bits_equal(got, want), f"{name}: kernel != kernels/ref.py")
        log(f"kernel {name}: bit-identical to its oracle "
            f"({n} x {bw} u32 row)")


# -- (b) / --chips 4: training -------------------------------------------------

def make_trainer(mesh, seed: int, protect: str, redundancy: int = 1):
    from repro.configs.base import ProtectConfig, TrainConfig
    from repro.configs.registry import get_config
    from repro.runtime.trainer import Trainer

    cfg = get_config(ARCH, reduced=False)
    # the calls launch/train.py makes, at its default cadence
    trainer = Trainer(
        cfg, TrainConfig(total_steps=TRAIN_STEPS),
        ProtectConfig(mode=protect, scrub_period=50, redundancy=redundancy),
        mesh, seq_len=TRAIN_SEQ, global_batch=TRAIN_BATCH, seed=seed)
    trainer.initialize()
    return trainer


def run_steps(trainer, steps: int) -> list:
    outs = trainer.run(steps)
    losses = [o["loss"] for o in outs]
    check(all(o["committed"] for o in outs), "a step failed to commit")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    return losses


def train_phase(mesh, seed: int) -> None:
    import jax

    trainer = make_trainer(mesh, seed, "ml")
    n_params = sum(x.size for x in
                   jax.tree.leaves(trainer.prot.state["params"]))
    log(f"train {ARCH}: {n_params / 1e6:.1f}M params, mesh "
        f"{dict(mesh.shape)}, protect=ml, batch {TRAIN_BATCH} x seq "
        f"{TRAIN_SEQ}")
    losses = run_steps(trainer, TRAIN_STEPS)
    health = trainer.pool.health()
    check(health.status == "green", f"health {health.status} "
          f"{health.reasons}")
    log(f"train losses {losses}, health {health.status}, peak memory "
        f"{peak_gib(jax.devices()[0])}")


# -- (c) serving -----------------------------------------------------------------

def serve_phase(mesh, seed: int) -> None:
    import jax

    from repro.configs.base import ProtectConfig
    from repro.configs.registry import get_config
    from repro.models.transformer import build_model
    from repro.runtime.server import Server

    cfg = get_config(ARCH, reduced=False)
    params = build_model(cfg, mesh).init(jax.random.PRNGKey(seed))
    prompt = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                (SERVE_BATCH, SERVE_PROMPT), 0, cfg.vocab)
    tokens = {}
    for protect in ("ml", "none"):
        srv = Server(cfg, ProtectConfig(mode=protect, block_words=256,
                                        scrub_period=16),
                     mesh, batch=SERVE_BATCH,
                     max_len=SERVE_PROMPT + SERVE_NEW + 1)
        srv.start(params)
        tokens[protect] = jax.device_get(srv.generate(prompt,
                                                      n_new=SERVE_NEW))
        if srv.pool is not None:
            health = srv.pool.health()
            check(health.status == "green", f"serving health "
                  f"{health.status} {health.reasons}")
        del srv
    out = tokens["ml"]
    check(out.shape == (SERVE_BATCH, SERVE_NEW), f"shape {out.shape}")
    check((out == tokens["none"]).all(),
          "protected decode differs from unprotected decode")
    log(f"serve {ARCH}: {out.shape} tokens with protect=ml equal to "
        f"protect=none, peak memory {peak_gib(jax.devices()[0])}")


# -- --chips 4: MLPC across the zone --------------------------------------------

def zone_phase(mesh, seed: int) -> None:
    import jax

    from repro.pool import Fault
    from repro.runtime import failure

    first = TRAIN_STEPS - 1
    base = make_trainer(mesh, seed, "none")
    run_steps(base, TRAIN_STEPS)
    # held on the host, so each protected run has the chips to itself
    want = jax.device_get(base.prot.state)
    del base
    gc.collect()
    for r in (3, 1):
        trainer = make_trainer(mesh, seed, "mlpc", redundancy=r)
        run_steps(trainer, first)
        pool = trainer.pool
        pool.prot, event = failure.inject_rank_loss(pool.protector,
                                                    pool.prot, LOST_RANK)
        rep = pool.recover(Fault.rank_loss(event.lost_rank))
        check(rep.verified and rep.reverified,
              f"r={r}: recovery of rank {LOST_RANK} did not verify")
        scrub = pool.scrub()
        check(not scrub.suspect and not scrub.repaired,
              f"r={r}: scrub after recovery not clean: {scrub}")
        losses = run_steps(trainer, TRAIN_STEPS - first)
        check(bits_equal(trainer.prot.state, want),
              f"r={r}: final train state differs from protect=none")
        health = pool.health()
        check(health.status == "green", f"r={r}: health {health.status} "
              f"{health.reasons}")
        log(f"zone mlpc r={r}: rank {LOST_RANK} lost and recovered, scrub "
            f"clean, last loss {losses[-1]}, state bit-identical to "
            f"protect=none, health {health.status}")
        del trainer, pool
        gc.collect()          # a Trainer and its Pool reference each other
    log(f"peak memory per chip "
        f"{[peak_gib(d) for d in jax.devices()]}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=[1, 4], default=1,
                    help="4: run only the four-chip MLPC zone phase")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"found {len(devices)}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_mesh

    hits = []
    jax.monitoring.register_event_listener(
        lambda event, **kw: hits.append(event)
        if event == "/jax/compilation_cache/cache_hits" else None)
    log(f"device: {devices[0].device_kind} x {len(devices)}, compile cache "
        f"{enable_compile_cache()}")
    if args.chips == 4:
        mesh = make_mesh((4, 1), devices=devices[:4])
        timed("zone phase (mlpc r=1, r=3 vs none)", zone_phase, mesh,
              args.seed)
    else:
        mesh = make_mesh((1, 1), devices=devices[:1])
        timed("kernel phase", kernel_phase, args.seed)
        timed("train phase", train_phase, mesh, args.seed)
        gc.collect()          # free the trainer's 5.5 GiB state first
        timed("serve phase", serve_phase, mesh, args.seed)
    log(f"compile-cache hits: {len(hits)}")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
