"""Device time of the ingest program the driver dispatches (its name in
the counters: ``update_multi`` for the service, ``update_bank`` for the
tenant bank) per 2048-element chunk (per tenant-chunk in the bank)
ingested in the traced window, in microseconds."""
import re

from bench.harness.readers import module_time_ns


def read(ctx):
    program = ctx.counters.get("program")
    chunks = ctx.counters.get("chunks", 0)
    if not program or not chunks:
        return None
    t = module_time_ns(ctx, re.compile(re.escape(program)))
    return t * 1e-3 / chunks if t > 0 else None
