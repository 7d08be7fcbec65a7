"""Cross-pod gradient compression with error feedback.

Between pods, the data-center interconnect is the scarcest link.  When
`TrainConfig.grad_compression` is on, batches shard only *within* a pod
(rule override), so autodiff's gradient psum covers the in-pod data axis
only; the cross-pod combine is then explicit and quantized:

    q  = int8(round((g + ef) / scale)),  scale = max|g + ef| / 127
    g' = mean_pods(dequant(q));          ef' = (g + ef) - dequant(q)

Error feedback keeps the quantization bias from accumulating (standard
EF-SGD result); wire traffic across pods drops 2x vs bf16 / 4x vs f32.
Implemented as a shard_map over the full mesh operating on each leaf's
local shard with a ppermute exchange across the pod axis.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

PyTree = Any


def _quantize(x: jax.Array):
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-30) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def _leaf_crosspod_mean(g: jax.Array, ef: jax.Array, axis: str):
    """One leaf: quantized all-reduce-mean across `axis` + error feedback."""
    n = lax.axis_size(axis)
    xf = g.astype(jnp.float32) + ef
    q, scale = _quantize(xf)
    ef_new = xf - _dequantize(q, scale)
    # exchange: rotate quantized payloads around the pod ring, accumulating
    # dequantized values (n is small — 2..8 pods)
    acc = _dequantize(q, scale)
    perm = [(i, (i + 1) % n) for i in range(n)]
    q_r, s_r = q, scale
    for _ in range(n - 1):
        q_r = lax.ppermute(q_r, axis, perm)
        s_r = lax.ppermute(s_r, axis, perm)
        acc = acc + _dequantize(q_r, s_r)
    return (acc / n).astype(g.dtype), ef_new.astype(ef.dtype)


def make_crosspod_compressed_mean(mesh, grad_specs: PyTree,
                                  pod_axis: str = "pod"):
    """Returns f(grads, ef) -> (mean grads, new ef), shard_mapped."""

    def _fn(grads, ef):
        return jax.tree.map(
            lambda g, e: _leaf_crosspod_mean(g, e, pod_axis), grads, ef)

    def split(out):
        g = jax.tree.map(lambda t: t[0], out,
                         is_leaf=lambda x: isinstance(x, tuple))
        e = jax.tree.map(lambda t: t[1], out,
                         is_leaf=lambda x: isinstance(x, tuple))
        return g, e

    smapped = shard_map(_fn, mesh=mesh, in_specs=(grad_specs, grad_specs),
                        out_specs=jax.tree.map(
                            lambda s: (s, s), grad_specs,
                            is_leaf=lambda x: isinstance(x, P)),
                        check_vma=False)

    def apply(grads, ef):
        return split(smapped(grads, ef))

    return apply


def init_error_feedback(params: PyTree) -> PyTree:
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
