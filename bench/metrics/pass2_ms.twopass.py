"""Device time of pass 2 of the distributed two-pass program (its
``pass2`` scope: the sampled keys' sort, the exact-weight lookups over the
shard and their psum) per job, on the first chip, in milliseconds."""
from bench.harness.program import stage_time


def read(ctx):
    t = stage_time(ctx, "two_pass_multi", ("pass2",))
    jobs = ctx.counters.get("jobs", 0)
    return t * 1e-6 / jobs if t and jobs else None
