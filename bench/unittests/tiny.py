"""A cell of the benchmark cut to a size the CPU runs in seconds.

Same files, same code, same comparison; the configuration's widths (its
layout's `tiny`), the batch and the sequence are shrunk, and the limits
are this size's own: sound runs on the CPU read up to loss_gap 5.2e-5,
grad_gap 0.0084 and update_gap 0.011 (seeds 1, 2, 3, 7 and 2**40 + 7,
one and four devices); the program's fp8 path reads grad_gap 1.0 and
update_gap 0.99, half a batch reads update_gap 0.2 or more.
"""
import harness

LIMITS = {"loss_gap": 1.5e-4, "grad_gap": 0.03, "update_gap": 0.03}


def cell(workload: str):
    c = harness.load_cell(workload)
    c.config.update(harness.layout_module(c.config).tiny(c.config))
    c.traffic.update(global_batch=8, seq_len=32)
    if c.traffic["fault_every"]:
        c.traffic["fault_every"] = 2
    c.traffic["protect"]["block_words"] = 256
    c.limits = dict(LIMITS)
    return c


def run(c, seed: int = 7, seconds: float = 0.3):
    """One run of the cell on the CPU's first devices: (result, checks)."""
    import jax
    harness.add_paths()
    return harness.kind_module("train").run(c, jax.devices()[:c.chips], seed,
                                       seconds, False, 0.0)
