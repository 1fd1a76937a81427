"""Device time of the chunk step's ``score`` stage (every lane scored and
reduced to per-key columns, ``capscore_agg``) per
chunk of the ingest program, in microseconds, on the base of ``chunk_us``
(per tenant-chunk in the bank).  Split by cell: ``score_us.ingest``,
``score_us.bank``."""
from bench.harness.program import per_chunk_us


def read(ctx):
    return per_chunk_us(ctx, "score")
