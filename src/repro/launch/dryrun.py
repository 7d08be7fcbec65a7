import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# NOTE: the two lines above MUST run before any other import (jax locks the
# device count on first init), which is why the docstring sits below them
# and no __future__ import is used in this module.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this driver builds the jitted step function with full
production shardings and runs `.lower(**abstract_inputs).compile()` —
ShapeDtypeStruct stand-ins only, zero allocation.  It records:

  * memory_analysis()    — per-device bytes (proves the cell fits HBM),
  * cost_analysis()      — HLO FLOPs / bytes for the roofline,
  * parsed collective wire bytes (launch/hlo_analysis.py),
  * compile wall time.

Train cells lower the *protected* train step (train_step + Pangolin commit
fused in one program) so the parity reduce-scatter and checksum sweeps are
part of the compiled artifact the roofline reads.  Decode cells lower
serve_step (one token against a full KV cache); prefill cells lower the
forward pass.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun \
        [--arch ID|all] [--workload NAME|all] [--mesh single|multi|both]
        [--protect mlpc|mlp|ml|none|replica] [--out results.json]
"""
import argparse
import dataclasses
import json
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import WORKLOADS, get_config, workload_skips
from repro.configs.base import ProtectConfig, TrainConfig
from repro.configs.registry import list_archs
from repro.launch import hlo_analysis as hlo
from repro.launch import hlo_cost
from repro.launch.mesh import make_production_mesh
from repro.models import api
from repro.models.transformer import build_model
from repro.optim import build_optimizer

# per-arch gradient-accumulation factors for the train_4k cell (activation
# memory control; see DESIGN.md §7)
MICROBATCHES = {
    "llama4-maverick-400b-a17b": 8,
    "chameleon-34b": 16,
    "minitron-8b": 8,
    "glm4-9b": 8,
    "moonshot-v1-16b-a3b": 8,
    "seamless-m4t-large-v2": 8,
    "recurrentgemma-2b": 4,
    "xlstm-1.3b": 4,
    "qwen2-0.5b": 4,
    "qwen3-0.6b": 4,
}


def _specs_to_shardings(specs, mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def _analyze(lowered, compiled, n_devices: int, model_flops: float) -> dict:
    # XLA's cost_analysis counts loop bodies once; the trip-count-aware
    # model (launch/hlo_cost.py) rolls the call graph up with multipliers.
    xla_cost = compiled.cost_analysis()
    if isinstance(xla_cost, (list, tuple)):
        xla_cost = xla_cost[0]
    text = compiled.as_text()
    totals = hlo_cost.analyze_text(text)
    mem = compiled.memory_analysis()
    memd = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes"):
        memd[attr] = int(getattr(mem, attr, 0) or 0)
    memd["total_bytes_per_device"] = (
        memd["argument_size_in_bytes"] + memd["output_size_in_bytes"]
        + memd["temp_size_in_bytes"] - memd["alias_size_in_bytes"])
    roof = hlo.roofline_terms(totals.flops, totals.hbm_bytes,
                              totals.total_wire_bytes,
                              model_flops=model_flops / n_devices,
                              device_kind=hlo.V5E)
    return {
        "cost": {"flops": totals.flops, "hbm_bytes": totals.hbm_bytes,
                 "raw_hbm_bytes": totals.raw_hbm_bytes,
                 "xla_raw_flops": float(xla_cost.get("flops", 0.0)),
                 "xla_raw_bytes": float(
                     xla_cost.get("bytes accessed", 0.0))},
        "memory": memd,
        "collectives": {"wire_bytes": totals.wire_bytes,
                        "counts": totals.coll_counts,
                        "total_wire_bytes": totals.total_wire_bytes},
        "roofline": roof.as_dict(),
    }


def dryrun_cell(arch: str, wl_name: str, multi_pod: bool,
                protect: str = "mlpc", verbose: bool = True) -> dict:
    cfg = get_config(arch)
    wl = WORKLOADS[wl_name]
    skip = workload_skips(cfg, wl)
    rec = {"arch": arch, "workload": wl_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "protect": protect, "status": "skip" if skip else "run"}
    if skip:
        rec["skip_reason"] = skip
        return rec

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    model = build_model(cfg, mesh)
    n_params = api.count_params(cfg)
    n_active = api.count_params(cfg, active_only=True)

    if wl.kind == "train":
        train_cfg = TrainConfig(microbatches=MICROBATCHES.get(arch, 1))
        optimizer = build_optimizer(train_cfg, cfg)
        abstract_state = api.abstract_train_state(model, optimizer)
        state_specs = api.train_state_specs(model, optimizer, mesh)
        # a cold pool: layout + compiled programs, zero allocation
        from repro.pool import Pool
        pool = Pool(mesh, abstract_state, state_specs,
                    ProtectConfig(mode=protect))
        protector = pool.protector
        commit = protector.make_commit()
        train_step = api.make_train_step(model, optimizer, train_cfg)

        def step(prot, batch):
            new_state, metrics = train_step(prot.state, batch)
            prot2, ok = commit(prot, new_state,
                               data_cursor=prot.step,
                               rng_key=jax.random.PRNGKey(0))
            return prot2, (metrics["loss"], ok)

        prot_abs = protector.abstract_protected(abstract_state)
        prot_specs = protector.protected_specs()
        batch_abs = api.batch_abstract(cfg, wl)
        b_specs = api.batch_specs(cfg, mesh, wl.global_batch)
        in_sh = (_specs_to_shardings(prot_specs, mesh),
                 _specs_to_shardings(b_specs, mesh))
        # donate the protected state: the commit's functional select and the
        # new optimizer state then alias the old buffers in place — without
        # this the step holds old+new state copies (llama4: +12.5 GiB/dev)
        fn = jax.jit(step, in_shardings=in_sh, donate_argnums=(0,))
        lowered = fn.lower(prot_abs, batch_abs)
        tokens = wl.global_batch * wl.seq_len
        model_flops = 6.0 * n_active * tokens
        rec["protection_overhead"] = protector.overhead_report()
    elif wl.kind == "prefill":
        forward = api.make_prefill(model)
        pspecs = model.param_specs(mesh)
        abstract_params = model.abstract_params()
        batch_abs = api.batch_abstract(cfg, wl)
        b_specs = api.batch_specs(cfg, mesh, wl.global_batch)
        in_sh = (_specs_to_shardings(pspecs, mesh),
                 _specs_to_shardings(b_specs, mesh))
        fn = jax.jit(forward, in_shardings=in_sh)
        lowered = fn.lower(abstract_params, batch_abs)
        tokens = wl.global_batch * wl.seq_len
        model_flops = 2.0 * n_active * tokens
    else:  # decode
        decode = api.make_decode_step(model)
        pspecs = model.param_specs(mesh)
        abstract_params = model.abstract_params()
        dec_abs = api.decode_abstract(cfg, wl, model)
        dec_specs = api.decode_specs(cfg, wl, model, mesh)
        in_sh = (_specs_to_shardings(pspecs, mesh),
                 _specs_to_shardings(dec_specs["token"], mesh),
                 _specs_to_shardings(dec_specs["cache"], mesh),
                 NamedSharding(mesh, P()))
        fn = jax.jit(decode, in_shardings=in_sh)
        lowered = fn.lower(abstract_params, dec_abs["token"],
                           dec_abs["cache"], dec_abs["pos"])
        model_flops = 2.0 * n_active * wl.global_batch
    t_lower = time.time() - t0

    t1 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t1

    rec.update(_analyze(lowered, compiled, n_dev, model_flops))
    rec.update({
        "status": "ok",
        "n_devices": n_dev,
        "n_params": n_params,
        "n_active_params": n_active,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
    })
    if verbose:
        r = rec["roofline"]
        print(f"[{arch} x {wl_name} x {rec['mesh']}] OK "
              f"compile={t_compile:.1f}s "
              f"mem/dev={rec['memory']['total_bytes_per_device']/2**30:.2f}GiB "
              f"compute={r['compute_s']*1e3:.2f}ms "
              f"memory={r['memory_s']*1e3:.2f}ms "
              f"coll={r['collective_s']*1e3:.2f}ms bound={r['bound']}",
              flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--workload", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--protect", default="mlpc")
    ap.add_argument("--out", default="dryrun_results.json")
    ap.add_argument("--resume", action="store_true",
                    help="skip cells already in --out")
    args = ap.parse_args()

    archs = list_archs() if args.arch == "all" else [args.arch]
    wls = list(WORKLOADS) if args.workload == "all" else [args.workload]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    results = []
    done = set()
    if args.resume and os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)
        done = {(r["arch"], r["workload"], r["mesh"]) for r in results
                if r.get("status") in ("ok", "skip")}

    failures = 0
    for arch in archs:
        for wl in wls:
            for mp in meshes:
                key = (arch, wl, "2x16x16" if mp else "16x16")
                if key in done:
                    continue
                try:
                    rec = dryrun_cell(arch, wl, mp, protect=args.protect)
                except Exception as e:  # noqa: BLE001 — record and continue
                    rec = {"arch": arch, "workload": wl,
                           "mesh": key[2], "status": "error",
                           "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-2000:]}
                    failures += 1
                    print(f"[{arch} x {wl} x {key[2]}] FAILED: "
                          f"{rec['error']}", flush=True)
                results.append(rec)
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)
    n_ok = sum(1 for r in results if r.get("status") == "ok")
    n_skip = sum(1 for r in results if r.get("status") == "skip")
    print(f"\ndry-run complete: {n_ok} ok, {n_skip} skip, "
          f"{failures} failed -> {args.out}")
    return 0 if failures == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
