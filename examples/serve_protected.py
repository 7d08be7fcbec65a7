"""Serving demo: batched decode with Pangolin protection of the KV cache.

    PYTHONPATH=src python examples/serve_protected.py [--tokens 64] [--smoke]

Decode is the paper's *atomic-style small update*: each step touches a tiny
known range of the cache, so the server's pool uses the incremental (patch)
side of the hybrid scheme — checksums refresh per dirty page, parity via
XOR patch.  Mid-stream, the demo corrupts the live cache and shows the
pool's scrub+repair keeping the generation identical to an uncorrupted
run.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import argparse
import time

import jax
import numpy as np

from repro.configs.base import ModelConfig, ProtectConfig
from repro.launch.mesh import make_mesh
from repro.runtime import failure
from repro.runtime.server import Server


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, default=48)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (fewer tokens, smaller batch)")
    args = ap.parse_args()
    if args.smoke:
        args.tokens, args.batch = 16, 4

    mesh = make_mesh((4, 2))
    cfg = ModelConfig(
        name="srv-demo", family="dense", n_layers=4, d_model=128, n_heads=8,
        n_kv=2, d_ff=256, vocab=1024, param_dtype="float32",
        compute_dtype="float32")
    from repro.models.transformer import build_model
    model = build_model(cfg, mesh)
    params = model.init(jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (args.batch, 8),
                                0, cfg.vocab)

    # reference: protected run with no faults
    ref_srv = Server(cfg, ProtectConfig(mode="mlpc", block_words=256), mesh,
                     batch=args.batch, max_len=args.tokens + 16)
    ref_srv.start(params)
    t0 = time.time()
    ref = ref_srv.generate(prompt, n_new=args.tokens)
    dt = time.time() - t0
    print(f"reference generation: {args.batch}x{args.tokens} tokens "
          f"({args.batch * args.tokens / dt:.0f} tok/s) | cache overhead: "
          f"{ref_srv.pool.overhead_report()['protection_fraction']:.3f}")

    # faulted run: corrupt the live cache mid-generation, repair online
    srv = Server(cfg, ProtectConfig(mode="mlpc", block_words=256), mesh,
                 batch=args.batch, max_len=args.tokens + 16)
    srv.start(params)
    tok = srv.prefill(prompt)
    out = [np.asarray(jax.device_get(tok))]
    for i in range(args.tokens - 1):
        if i == args.tokens // 2:
            srv.prot, _ = failure.inject_scribble(
                srv.protector, srv.prot, rank=2, word_offsets=[31, 77])
            rep = srv.pool.scrub()
            print(f"[token {i}] cache scribbled -> scrub found "
                  f"{rep.bad_locations}, repaired={rep.repair_ok}")
        tok = srv.step(tok)
        out.append(np.asarray(jax.device_get(tok)))
    got = np.stack(out, axis=1)
    assert np.array_equal(got, ref), "faulted run must match reference"
    print("faulted generation matches reference bit-for-bit — "
          "online cache repair is transparent to serving")


if __name__ == "__main__":
    main()
