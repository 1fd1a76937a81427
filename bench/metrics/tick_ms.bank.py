"""Host time of the scheduler's ingest tick (the program's
``scheduler.tick`` spans: packing the ``[T, chunk]`` arrays and enqueuing
``update_bank``) per ``StatsScheduler.step`` in the window, in
milliseconds."""
from bench.harness.program import span_ms_per


def read(ctx):
    return span_ms_per(ctx, "scheduler.tick", per="scheduler.step")
