"""Share of the first chip's busy time spent in the chunk step's Pallas
kernels (capscore_agg, capscore_multi, chunksort), matched by their names
in the device trace; nothing when no kernel of those names ran."""
from bench.harness.readers import kernel_share


def read(ctx):
    return kernel_share(ctx)
