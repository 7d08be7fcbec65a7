"""repro — Pangolin-JAX: fault-tolerant protection of distributed training/serving state.

A JAX/TPU adaptation of "Pangolin: A Fault-Tolerant Persistent Memory
Programming Library" (Zhang & Swanson, 2019).  See DESIGN.md for the
NVMM -> multi-pod-HBM mapping.

Public surface (the pgl analogue — see repro/pool.py for the mapping):

    from repro import Pool, Fault, ProtectConfig

    pool = Pool.open(state, specs, mesh=mesh,
                     config=ProtectConfig(mode="mlpc"))
    with pool.transaction() as tx:
        tx.stage(new_state)
    pool.recover(Fault.rank_loss(2))

`Protector` / `DeferredProtector` remain importable as the low-level
engine layer; everything above them should go through `Pool`.
"""

__version__ = "0.1.0"

__all__ = ["Pool", "Fault", "Transaction", "ProtectConfig", "Mode",
           "Protector", "DeferredProtector", "ProtectedState",
           "MetricsRegistry", "Tracer", "HealthReport"]

# Lazy re-exports (PEP 562): `python -m repro.launch.*` imports this
# package before the launchers set XLA_FLAGS, and several core modules
# create device scalars at import time — eager re-exports here would
# lock the backend's device count before --host-devices applies.
_EXPORTS = {
    "ProtectConfig": ("repro.configs.base", "ProtectConfig"),
    "DeferredProtector": ("repro.core.epoch", "DeferredProtector"),
    "Mode": ("repro.core.txn", "Mode"),
    "ProtectedState": ("repro.core.txn", "ProtectedState"),
    "Protector": ("repro.core.txn", "Protector"),
    "Fault": ("repro.pool", "Fault"),
    "Pool": ("repro.pool", "Pool"),
    "Transaction": ("repro.pool", "Transaction"),
    # telemetry plane (repro.obs is jax-free, but Pool re-exports pull
    # in the full stack, so these stay lazy with the rest)
    "MetricsRegistry": ("repro.obs.metrics", "MetricsRegistry"),
    "Tracer": ("repro.obs.trace", "Tracer"),
    "HealthReport": ("repro.obs.health", "HealthReport"),
}


def __getattr__(name):
    try:
        module, attr = _EXPORTS[name]
    except KeyError:
        raise AttributeError(f"module 'repro' has no attribute {name!r}")
    import importlib
    value = getattr(importlib.import_module(module), attr)
    globals()[name] = value        # cache for subsequent lookups
    return value


def __dir__():
    return sorted(set(globals()) | set(_EXPORTS))
