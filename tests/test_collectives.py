"""XOR collectives: the distributed realization of Pangolin's atomic-XOR
algebra.  Each collective must equal a host-side XOR reference, for any
operand content, and the three variants must agree with each other."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.dist import collectives as coll


def put_rows(mesh, rows):
    """rows: (G, n) np.uint32 -> sharded (G*n,) array, row g on data-rank g."""
    g, n = rows.shape
    arr = jnp.asarray(rows.reshape(-1))
    return jax.device_put(arr, NamedSharding(mesh, P(("data",))))


def run_zone(mesh, fn, x, out_spec):
    f = shard_map(fn, mesh=mesh, in_specs=(P(("data",)),),
                  out_specs=out_spec, check_vma=False)
    return jax.jit(f)(x)


@pytest.mark.parametrize("n", [8, 64, 256])
def test_xor_reduce_scatter(mesh42, n):
    g = mesh42.shape["data"]
    rng = np.random.default_rng(n)
    rows = rng.integers(0, 2**32, size=(g, n), dtype=np.uint32)
    x = put_rows(mesh42, rows)
    out = run_zone(mesh42, lambda r: coll.xor_reduce_scatter(r, "data"),
                   x, P(("data",)))
    want = functools.reduce(np.bitwise_xor, rows)  # (n,)
    np.testing.assert_array_equal(np.asarray(out), want)


@pytest.mark.parametrize("n", [8, 60])   # 60: needs padding inside all_reduce
def test_xor_all_reduce(mesh42, n):
    g = mesh42.shape["data"]
    rng = np.random.default_rng(n + 1)
    rows = rng.integers(0, 2**32, size=(g, n), dtype=np.uint32)
    x = put_rows(mesh42, rows)
    # every rank gets the full XOR; stack outputs to verify each rank's copy
    out = run_zone(mesh42, lambda r: coll.xor_all_reduce(r, "data")[None],
                   x, P(("data",)))
    want = functools.reduce(np.bitwise_xor, rows)
    got = np.asarray(out).reshape(g, n)
    for r in range(g):
        np.testing.assert_array_equal(got[r], want)


def test_xor_tree_reduce_matches_all_reduce(mesh81):
    g = mesh81.shape["data"]
    rng = np.random.default_rng(42)
    rows = rng.integers(0, 2**32, size=(g, 32), dtype=np.uint32)
    x = put_rows(mesh81, rows)
    out_tree = run_zone(mesh81, lambda r: coll.xor_tree_reduce(r, "data")[None],
                        x, P(("data",)))
    want = functools.reduce(np.bitwise_xor, rows)
    got = np.asarray(out_tree).reshape(g, 32)
    for r in range(g):
        np.testing.assert_array_equal(got[r], want)


def test_xor_fold_matches_reduce():
    rng = np.random.default_rng(0)
    for n in (1, 2, 3, 5, 8, 13):
        x = rng.integers(0, 2**32, size=(n, 16), dtype=np.uint32)
        out = coll.xor_fold(jnp.asarray(x), axis=0)
        want = functools.reduce(np.bitwise_xor, x)
        np.testing.assert_array_equal(np.asarray(out), want)


def test_all_gather_row_inverse_of_scatter(mesh42):
    g = mesh42.shape["data"]
    rng = np.random.default_rng(3)
    rows = rng.integers(0, 2**32, size=(g, 16), dtype=np.uint32)
    x = put_rows(mesh42, rows)

    def fn(r):
        seg = coll.xor_reduce_scatter(r, "data")
        return coll.all_gather_row(seg, "data")[None]

    out = run_zone(mesh42, fn, x, P(("data",)))
    want = functools.reduce(np.bitwise_xor, rows)
    got = np.asarray(out).reshape(g, 16)
    for r in range(g):
        np.testing.assert_array_equal(got[r], want)
