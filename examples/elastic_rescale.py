"""Elastic rescale demo: move a protected training job between meshes.

    PYTHONPATH=src python examples/elastic_rescale.py [--smoke]

A job training on a (4, 2) mesh loses nodes and continues on (2, 2); later
it scales back up to (4, 2).  The divisibility-fallback sharding rules keep
the same model valid on every mesh; protection (zone geometry depends on G)
is rebuilt after each move by `Pool.rescale` — flush any open window,
reshard the state bit-exactly, rebuild parity/checksums on the new
geometry, carry the step counter — exactly as Pangolin rebuilds parity
when row geometry changes.  Loss history continues seamlessly across both
moves.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import argparse

import jax
import numpy as np

from repro.configs.base import ModelConfig, ProtectConfig, TrainConfig
from repro.launch.mesh import make_mesh
from repro.runtime.trainer import Trainer


def make_trainer(mesh, seed=0):
    cfg = ModelConfig(
        name="elastic-demo", family="dense", n_layers=2, d_model=64,
        n_heads=4, n_kv=2, d_ff=128, vocab=512, param_dtype="float32",
        compute_dtype="float32")
    t = Trainer(cfg, TrainConfig(learning_rate=1e-3, warmup_steps=5,
                                 total_steps=200),
                ProtectConfig(mode="mlpc", block_words=64),
                mesh, seq_len=64, global_batch=8, seed=seed)
    return t


def move(trainer_old, new_mesh):
    """Move the protected job: one `Pool.rescale` call does the flush,
    the bit-exact reshard, the protection rebuild on the new zone
    geometry and the host-side step-counter carry."""
    t_new = make_trainer(new_mesh, seed=0)
    t_new.pool = trainer_old.pool.rescale(new_mesh, into=t_new.pool)
    t_new.cursor = trainer_old.cursor
    return t_new


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (fewer steps per phase)")
    args = ap.parse_args()
    n = 4 if args.smoke else 10

    mesh_full = make_mesh((4, 2))
    mesh_small = make_mesh((2, 2))

    t = make_trainer(mesh_full)
    t.initialize()
    losses = [o["loss"] for o in t.run(n)]
    print(f"phase 1 (4x2, G=4):  steps 1-{n},  loss -> {losses[-1]:.4f}, "
          f"parity overhead "
          f"{t.pool.overhead_report()['parity_fraction']:.3f}")

    # nodes evicted: shrink to 2x2 (G=2), protection rebuilt
    t = move(t, mesh_small)
    losses += [o["loss"] for o in t.run(n)]
    print(f"phase 2 (2x2, G=2):  steps {n + 1}-{2 * n}, loss -> "
          f"{losses[-1]:.4f}, parity overhead "
          f"{t.pool.overhead_report()['parity_fraction']:.3f}")

    # capacity restored: scale back up, verify recovery still works
    t = move(t, mesh_full)
    losses += [o["loss"] for o in t.run(n)]
    print(f"phase 3 (4x2, G=4):  steps {2 * n + 1}-{3 * n}, loss -> "
          f"{losses[-1]:.4f}")

    from repro.runtime import failure
    t.prot, ev = failure.inject_rank_loss(t.protector, t.prot, rank=1)
    rep = t.on_failure(ev)
    print(f"post-rescale rank loss: recovered, verified={rep['verified']}")

    if not args.smoke:         # too few steps to demand descent in CI
        assert np.mean(losses[-3:]) < np.mean(losses[:3]), \
            "loss must decrease"
    assert int(jax.device_get(t.prot.step)) == 3 * n
    print(f"elastic rescale demo passed: {3 * n} contiguous steps across "
          "3 meshes")


if __name__ == "__main__":
    main()
