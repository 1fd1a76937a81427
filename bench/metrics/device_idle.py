"""Device idle share of the traced window: 1 minus the union of each
chip's operation intervals over the window, averaged over the cell's
chips.  Split by the end-to-end metric it moves (``device_idle.ingest``,
``device_idle.twopass``, ...)."""
from bench.harness.readers import idle_percent


def read(ctx):
    return idle_percent(ctx)
