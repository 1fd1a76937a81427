"""Where the system's f64 steps run (DESIGN.md §7.1).

The query plane and pass II promise answers bit-identical to numpy, which
holds only where the device runs IEEE binary64 arithmetic.  A TPU emulates
f64 and does not round as binary64 does, so on a TPU these steps run on the
host's CPU device; everywhere else on the default device.
"""
from __future__ import annotations

import contextlib

import jax


@contextlib.contextmanager
def x64_scope():
    """x64 on for the block, placed on a device with IEEE f64."""
    with jax.enable_x64(True):
        if jax.default_backend() != "tpu":
            yield
            return
        with jax.default_device(jax.devices("cpu")[0]):
            yield
