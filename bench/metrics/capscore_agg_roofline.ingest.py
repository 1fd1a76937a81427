"""capscore_agg's share of its HBM roofline: the bytes its declared
operands and packed output must move per call (``harness/kernels.py``),
over the chip's published HBM bandwidth, divided by the kernel's summed
device time.  The kernel does no matrix product, so the HBM bound is the
only roofline that applies."""
from bench.harness.kernels import capscore_agg_bytes
from bench.harness.readers import KERNEL_PATTERNS, op_time_ns


def read(ctx):
    t, calls = op_time_ns(ctx, KERNEL_PATTERNS["capscore_agg"])
    if not calls or t <= 0:
        return None
    need = calls * capscore_agg_bytes(ctx.counters["chunk"],
                                      ctx.counters["lanes"])
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / (t * 1e-9)
