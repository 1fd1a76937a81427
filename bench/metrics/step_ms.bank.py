"""Mean host time of one ``StatsScheduler.step`` call in the window (the
benchmark's own span around the call), in milliseconds."""
from bench.harness.readers import host_span_mean_ms


def read(ctx):
    return host_span_mean_ms(ctx, "step")
