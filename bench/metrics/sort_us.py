"""Device time of the chunk step's ``chunk_sort`` stage (the one key sort of
the chunk and its pre-gathered view, ``chunk_order``) per
chunk of the ingest program, in microseconds, on the base of ``chunk_us``
(per tenant-chunk in the bank).  Split by cell: ``sort_us.ingest``,
``sort_us.bank``."""
from bench.harness.program import per_chunk_us


def read(ctx):
    return per_chunk_us(ctx, "chunk_sort")
