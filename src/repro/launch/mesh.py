"""Mesh construction: the one place a `jax.sharding.Mesh` is built.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — the dry-run must set
XLA_FLAGS=--xla_force_host_platform_device_count=512 *before* first init.

Every axis is `AxisType.Auto`.  Since jax 0.9 `jax.make_mesh` defaults to
Explicit axes, under which an unconstrained reshape of a sharded array
raises; the protection core reshapes rows and digests freely inside jit and
leaves their sharding to the compiler.

Mesh axes:
  launchers:   device_mesh_shape(len(jax.devices())) -> ("data", "model")
  single-pod:  (16, 16)        -> ("data", "model")     = 256 chips (v5e pod)
  multi-pod:   (2, 16, 16)     -> ("pod", "data", "model") = 512 chips

The "data" axis is the Pangolin zone axis (parity groups of G=16); "pod"
carries cross-pod redo-log/metadata replication and (optionally) the
compressed-gradient exchange.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
from jax.sharding import AxisType, Mesh

# Largest zone the launchers build from the devices they find: one v5e
# host's four chips.  Devices beyond it go to the model axis.
LAUNCH_ZONE = 4


def make_mesh(shape: Sequence[int], axes: Sequence[str] = ("data", "model"),
              *, devices: Optional[Sequence] = None) -> Mesh:
    """A mesh of `shape` over `axes` (default: the first devices)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def device_mesh_shape(n_devices: int) -> tuple[int, int]:
    """The launchers' (data, model) shape for `n_devices` devices.

    One chip gives (1, 1), a four-chip host (4, 1), and the eight forced
    CPU host devices of the tests (4, 2).
    """
    data = math.gcd(n_devices, LAUNCH_ZONE)
    return data, n_devices // data


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def data_axis_size(mesh: Mesh) -> int:
    return mesh.shape["data"]
