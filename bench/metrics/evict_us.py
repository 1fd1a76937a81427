"""Device time of the chunk step's ``evict`` stage (the eviction back to k,
``_scheduled_evict``) per
chunk of the ingest program, in microseconds, on the base of ``chunk_us``
(per tenant-chunk in the bank).  Split by cell: ``evict_us.ingest``,
``evict_us.bank``."""
from bench.harness.program import per_chunk_us


def read(ctx):
    return per_chunk_us(ctx, "evict")
