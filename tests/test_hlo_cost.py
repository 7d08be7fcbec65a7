"""The trip-count-aware HLO cost model feeding the roofline: validated
against XLA's own cost_analysis on unrolled programs, and against analytic
expectations on scanned programs (where XLA under-counts loop bodies)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import hlo_analysis, hlo_cost


def compiled_text(fn, *args):
    lowered = jax.jit(fn).lower(*args)
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    return compiled.as_text(), cost


def test_matmul_flops_match_xla():
    a = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    b = jax.ShapeDtypeStruct((256, 512), jnp.float32)
    text, xla = compiled_text(lambda x, y: x @ y, a, b)
    tot = hlo_cost.analyze_text(text)
    want = 2 * 128 * 256 * 512
    assert tot.flops == pytest.approx(want, rel=0.02)
    assert float(xla.get("flops", 0)) == pytest.approx(want, rel=0.02)


def test_scan_multiplies_body_flops():
    """XLA counts the while body once; the cost model must multiply by the
    trip count."""
    n_iters, m = 7, 64
    w = jax.ShapeDtypeStruct((n_iters, m, m), jnp.float32)
    x = jax.ShapeDtypeStruct((m, m), jnp.float32)

    def scanned(ws, x0):
        def body(x, w):
            return w @ x, ()
        out, _ = jax.lax.scan(body, x0, ws)
        return out

    text, xla = compiled_text(scanned, w, x)
    tot = hlo_cost.analyze_text(text)
    body_flops = 2 * m * m * m
    assert tot.flops == pytest.approx(n_iters * body_flops, rel=0.1)
    # and XLA indeed under-counts (sanity check of the premise)
    assert float(xla.get("flops", 0)) <= body_flops * 2


def test_unrolled_vs_scanned_agree():
    """Total flops of the same computation must match whether scanned or
    unrolled — the invariant the trip-count roll-up exists to provide."""
    n_iters, m = 5, 32
    ws = jnp.ones((n_iters, m, m), jnp.float32)
    x0 = jnp.ones((m, m), jnp.float32)

    def scanned(ws, x0):
        def body(x, w):
            return w @ x, ()
        return jax.lax.scan(body, x0, ws)[0]

    def unrolled(ws, x0):
        x = x0
        for i in range(n_iters):
            x = ws[i] @ x
        return x

    t_s, _ = compiled_text(scanned, ws, x0)
    t_u, _ = compiled_text(unrolled, ws, x0)
    f_s = hlo_cost.analyze_text(t_s).flops
    f_u = hlo_cost.analyze_text(t_u).flops
    assert f_s == pytest.approx(f_u, rel=0.1)


def test_parse_hlo_finds_entry():
    text, _ = compiled_text(lambda x: x + 1.0,
                            jax.ShapeDtypeStruct((8,), jnp.float32))
    comps = hlo_cost.parse_hlo(text)
    assert any(c.is_entry for c in comps.values())


def test_roofline_terms_bound_selection():
    v5e = hlo_analysis.V5E
    r = hlo_analysis.roofline_terms(flops=1e15, hbm_bytes=1e9, wire_bytes=1e6,
                                    device_kind=v5e)
    assert r.bound == "compute"
    r = hlo_analysis.roofline_terms(flops=1e9, hbm_bytes=1e13, wire_bytes=1e6,
                                    device_kind=v5e)
    assert r.bound == "memory"
    r = hlo_analysis.roofline_terms(flops=1e9, hbm_bytes=1e9, wire_bytes=1e13,
                                    device_kind=v5e)
    assert r.bound == "collective"
    r = hlo_analysis.roofline_terms(1e12, 1e9, 1e6, model_flops=5e11,
                                    device_kind=v5e)
    assert r.useful_ratio == pytest.approx(0.5)


def test_peaks_keyed_by_device_kind():
    """v5e peaks carry their source; a device with none is an error."""
    p = hlo_analysis.peaks(hlo_analysis.V5E)
    assert (p.flops_bf16, p.hbm_bw) == (197e12, 819e9)
    assert "TPU v5e" in p.source
    with pytest.raises(KeyError, match="no published peaks"):
        hlo_analysis.roofline_terms(1e9, 1e9, 1e9, device_kind="cpu")


def test_collective_parsing_shard_map(mesh42):
    """psum inside shard_map must be seen as an all-reduce with wire bytes
    2 (G-1)/G * payload."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    n = 1024

    def f(x):
        return jax.lax.psum(x, "data")

    sm = shard_map(f, mesh=mesh42, in_specs=P(), out_specs=P(),
                   check_vma=False)
    x = jax.ShapeDtypeStruct((n,), jnp.float32)
    text, _ = compiled_text(sm, x)
    tot = hlo_cost.analyze_text(text)
    assert tot.coll_counts["all-reduce"] >= 1
    g = 4
    want = 2 * (g - 1) / g * n * 4
    assert tot.wire_bytes["all-reduce"] == pytest.approx(want, rel=0.05)


def test_collective_parsing_all_gather(mesh42):
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def f(x):
        return jax.lax.all_gather(x, "data", axis=0, tiled=True)

    sm = shard_map(f, mesh=mesh42, in_specs=P(("data",)), out_specs=P(),
                   check_vma=False)
    x = jax.ShapeDtypeStruct((64,), jnp.float32)   # 16 per rank
    text, _ = compiled_text(sm, x)
    tot = hlo_cost.analyze_text(text)
    assert tot.coll_counts["all-gather"] >= 1
    g = 4
    want = (g - 1) / g * 64 * 4      # result bytes convention
    assert tot.wire_bytes["all-gather"] == pytest.approx(want, rel=0.05)


def test_memory_bytes_reasonable():
    """Fusion-aware byte count for y = x @ w: reads x, w; writes y."""
    m = 256
    a = jax.ShapeDtypeStruct((m, m), jnp.float32)
    text, _ = compiled_text(lambda x, y: x @ y, a, a)
    tot = hlo_cost.analyze_text(text)
    want = 3 * m * m * 4
    assert tot.hbm_bytes == pytest.approx(want, rel=0.25)
