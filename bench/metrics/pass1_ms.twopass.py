"""Device time of pass 1 of the distributed two-pass program (its
``pass1_score`` and ``pass1_fold`` scopes: element hashes and scores, the
per-chunk bottom-(k+1) fold) per job, on the first chip, in
milliseconds."""
from bench.harness.program import stage_time


def read(ctx):
    t = stage_time(ctx, "two_pass_multi", ("pass1_score", "pass1_fold"))
    jobs = ctx.counters.get("jobs", 0)
    return t * 1e-6 / jobs if t and jobs else None
