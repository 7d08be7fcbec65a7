"""fletcher_roofline (%, kernels): least time of the commit's kernel over
its device time in the trace.

The commit runs one kernel per step, the Fletcher checksum sweep over the
rank's row; it does a few integer operations per word and no matmul, so
its bound is memory: the bytes it must move (flops.fletcher_bytes of the
rank's share of the state) over the chip's HBM bandwidth."""
from flops import fletcher_bytes


def read(ctx):
    if ctx.trace is None or not ctx.row_words:
        return None
    r = ctx.trace.rules
    t = ctx.trace.op_s(r["commit_kernels"], r["commit_programs"])
    calls = ctx.trace.op_count(r["commit_kernels"], r["commit_programs"])
    if t <= 0 or calls <= 0:
        return None
    need = calls * fletcher_bytes(ctx.row_words, ctx.block_words) \
        / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * need / t
