"""Host time of the scheduler's admission (the program's
``scheduler.admit`` spans: round-robin pick and staging into the bank's
queues) per ``StatsScheduler.step`` in the window, in milliseconds."""
from bench.harness.program import span_ms_per


def read(ctx):
    return span_ms_per(ctx, "scheduler.admit", per="scheduler.step")
