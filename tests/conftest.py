"""Test fixtures. NOTE: no XLA_FLAGS here — smoke tests and benches must see
exactly 1 CPU device (the 512-device mesh lives only in launch/dryrun.py and
subprocess-based distributed tests)."""
import numpy as np
import pytest


@pytest.fixture(scope="session")
def zipf_stream():
    """A deterministic Zipf(1.5) stream of 20k elements (paper §7 setup)."""
    rng = np.random.default_rng(1)
    keys = (rng.zipf(1.5, size=20000) % 5000).astype(np.int64)
    return keys


@pytest.fixture(scope="session")
def zipf_truth(zipf_stream):
    ukeys, cnts = np.unique(zipf_stream, return_counts=True)
    return ukeys, cnts


@pytest.fixture
def force_merge_form(monkeypatch):
    """``force(form)`` steers the table merge's platform resolver
    (``vectorized.merge_form``) to ``'gather'`` or ``'sort'``.  Compiled
    programs are dropped at each switch and at teardown, so no program
    traced under one form is reused under the other."""
    import jax

    from repro.core import vectorized as V

    def force(form):
        jax.clear_caches()
        monkeypatch.setattr(V, "merge_form", lambda: form)

    yield force
    jax.clear_caches()
