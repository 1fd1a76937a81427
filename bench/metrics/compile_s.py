"""Set-up seconds the program spent tracing, lowering, compiling or
loading its programs from the cache (its compile records under a program
span, before the window).  Split by cell: ``compile_s.ingest``,
``compile_s.bank``, ``compile_s.twopass``."""
from bench.harness.program import compile_s


def read(ctx):
    return compile_s(ctx)
