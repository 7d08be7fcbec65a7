"""Compile a cell's train step and commit for a described TPU, no chip.

    JAX_PLATFORMS=cpu python bench/aot.py --workload <name>

Builds the cell's `Trainer` on a mesh of described v5e devices (v5e:2x2,
its first `chips` devices), lowers the train-step program and the pool's
commit program at the cell's batch against abstract shapes with their
shardings, compiles them with the TPU compiler, and prints each
program's `memory_analysis()` per device as one JSON line.  This is what
the compiler would refuse, and how much memory it plans, before any chip
time is spent; it runs nothing.
"""
import argparse
import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


def _gib(x) -> float:
    return round(x / 2 ** 30, 3)


def memory(compiled) -> dict:
    m = compiled.memory_analysis()
    return {"arguments_gib": _gib(m.argument_size_in_bytes),
            "outputs_gib": _gib(m.output_size_in_bytes),
            "temporaries_gib": _gib(m.temp_size_in_bytes),
            "aliased_gib": _gib(m.alias_size_in_bytes)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--global-batch", type=int, default=None,
                    help="compile at another batch than the cell's")
    args = ap.parse_args(argv)
    harness.add_paths()
    import jax
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.models import api

    cell = harness.load_cell(args.workload)
    if args.global_batch:
        cell.traffic["global_batch"] = args.global_batch
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    devices = list(topo.devices)[:cell.chips]
    train = harness.kind_module(cell.traffic["kind"])
    trainer, init = train.build(cell, devices, 0)
    mesh = trainer.mesh
    state = jax.eval_shape(init, jax.ShapeDtypeStruct((2,), "uint32"))
    specs = api.train_state_specs(trainer.model, trainer.optimizer, mesh)
    shard = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                         is_leaf=lambda x: isinstance(x, P))
    state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        state, shard)
    tr = cell.traffic
    batch = {"tokens": jax.ShapeDtypeStruct(
        (tr["global_batch"], tr["seq_len"]), "int32",
        sharding=trainer._batch_shardings["tokens"])}
    out = {"workload": cell.name, "chips": cell.chips,
           "global_batch": tr["global_batch"]}
    step = trainer._train_step.lower(state, batch).compile()
    out["train_step"] = memory(step)
    protector = trainer.pool.protector
    prot = jax.eval_shape(protector.init, state)
    zone, rep = (NamedSharding(mesh, protector._zone_spec),
                 NamedSharding(mesh, P()))

    def placed(tree, sharding):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sharding), tree)

    prot = dataclasses.replace(
        prot, state=state,
        **{f: placed(getattr(prot, f), zone)
           for f in ("synd", "cksums", "digest", "row")
           if getattr(prot, f) is not None},
        log=placed(prot.log, rep), step=placed(prot.step, rep))
    commit = trainer.pool.commit_program()
    out["commit"] = memory(commit.lower(prot, state).compile())
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
