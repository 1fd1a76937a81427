"""Device time of the cross-chip collectives (summary exchange and the
pass-2 psum) per two-pass job, read from the first chip's trace plane,
in milliseconds."""
from bench.harness.readers import COLLECTIVE, op_time_ns


def read(ctx):
    t, n = op_time_ns(ctx, COLLECTIVE)
    jobs = ctx.counters.get("jobs", 0)
    return t * 1e-6 / jobs if n and jobs else None
