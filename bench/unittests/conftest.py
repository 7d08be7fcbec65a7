"""Shared set-up of the benchmark's own tests.

The tests that drive a cell on the CPU need four host devices; the
suite's `tests/conftest.py` forces eight with the same flag, so both set
it only where it is unset.  No test module imports jax while it loads.
"""
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (BENCH, os.path.join(os.path.dirname(BENCH), "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
