"""Device time of the chunk step's ``fold`` stage (the bottom-(k+1)
summary fold, ``pass1_fold_keysorted``) per
chunk of the ingest program, in microseconds, on the base of ``chunk_us``
(per tenant-chunk in the bank).  Split by cell: ``fold_us.ingest``,
``fold_us.bank``."""
from bench.harness.program import per_chunk_us


def read(ctx):
    return per_chunk_us(ctx, "fold")
