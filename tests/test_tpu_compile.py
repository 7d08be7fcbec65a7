"""The commit kernels compile for a TPU v5e chip that is described, not attached.

Interpret mode accepts kernels the chip's compiler refuses (unsigned
reductions, loads from HBM refs, tiles past the VMEM limit), so each
kernel of the commit path is compiled here at a 16 MB row of 4096 pages
x 1024 words for one chip of a described v5e:2x2 topology.  Nothing
runs: this proves what Mosaic accepts, not results or times.

The topology is described only inside the module fixture: the TPU
library may be loaded by one process at a time, so describing it while
a module is imported would break the other test workers.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import commit_fused, fletcher, gf_parity

N_BLOCKS, BLOCK_WORDS, R = 4096, 1024, 3


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    # a compile for a described chip can be written to the persistent
    # cache but not read back without one: keep the cache out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler installed here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        cc.reset_cache()


def _args(sharding, *shapes):
    return [jax.ShapeDtypeStruct(s, jnp.uint32, sharding=sharding)
            for s in shapes]


ROW, TERMS, COEFFS = (N_BLOCKS, BLOCK_WORDS), (N_BLOCKS, 2), (R,)
CASES = {
    "fused_verify_commit_s": (gf_parity.fused_verify_commit_s,
                              (ROW, ROW, TERMS, COEFFS)),
    "fused_verify_commit_s_stream": (gf_parity.fused_verify_commit_s_stream,
                                     (ROW, ROW, TERMS, COEFFS)),
    "fused_accum_commit_stream": (commit_fused.fused_accum_commit_stream,
                                  (ROW, ROW, ROW)),
    "fletcher_stream": (fletcher.fletcher_stream, (ROW,)),
    "sdelta_stack": (gf_parity.sdelta_stack,
                     ((N_BLOCKS * BLOCK_WORDS,), COEFFS)),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_commit_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = CASES[name]
    compiled = fn.lower(*_args(one_chip, *shapes)).compile()
    # a native Mosaic kernel, not an XLA fallback
    assert "tpu_custom_call" in compiled.as_text()
