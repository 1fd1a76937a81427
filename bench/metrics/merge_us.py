"""Device time of the chunk step's ``merge`` stage (the per-lane
sorted-runs table merge, ``fixed_k_merge``) per
chunk of the ingest program, in microseconds, on the base of ``chunk_us``
(per tenant-chunk in the bank).  Split by cell: ``merge_us.ingest``,
``merge_us.bank``."""
from bench.harness.program import per_chunk_us


def read(ctx):
    return per_chunk_us(ctx, "merge")
