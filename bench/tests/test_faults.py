"""A run whose timed path is broken underneath comes out not correct.

Each case drives the rest of a run on the CPU at a small size (the look for
the chip is the entry point's, not ``run_cell``'s) with one fault planted in
the program where it produces its answer: a step that returns its state
unchanged, half of each batch left out, the exchange between chips left out,
and an answer altered; and, inside the chunk step, the survivors' count
adjustment left out and an eviction that races on the wrong score.  The
sound run beside them comes out correct.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.core import distributed as DD
from repro.core import incremental as I
from repro.core import vectorized as VZ

from small import run_small


def _unchanged(orig):
    def f(state, *args, **kw):
        return state
    return f


def _half_batch(orig):
    def f(state, keys, weights, *args, **kw):
        n = keys.shape[0] // 2
        return orig(state, keys[:n], weights[:n], *args, **kw)
    return f


def _half_tenants(orig):
    def f(state, keys, weights, active, *args, **kw):
        active = jnp.asarray(active)
        half = jnp.arange(active.shape[0]) < active.shape[0] // 2
        return orig(state, keys, weights, active & half, *args, **kw)
    return f


def _altered_seed(orig):
    def f(*args, **kw):
        st = orig(*args, **kw)
        return dataclasses.replace(st, bk_seeds=st.bk_seeds * 0.5)
    return f


def _no_count_adjustment(orig):
    def f(state_keys, counts, kb, seed, tau, l, delta, tau_star, valid, z,
          entry_thresh, ex, inv_l):
        return orig(state_keys, counts, kb, seed, tau, l, delta, tau_star,
                    valid, z, entry_thresh, jnp.zeros_like(ex), inv_l)
    return f


def _evict_on_key_base(orig):
    def f(state_keys, counts, kb, tau, l, salt, round_no):
        valid, z, entry_thresh, ex, inv_l = orig(state_keys, counts, kb, tau,
                                                 l, salt, round_no)
        return valid, jnp.where(valid, kb, z), entry_thresh, ex, inv_l
    return f


FAULTS = {
    "adcap-ingest": [("update_multi", _unchanged), ("update_multi", _half_batch),
                     ("update_multi", _altered_seed)],
    "bank-ingest": [("update_bank", _unchanged), ("update_bank", _half_tenants),
                    ("update_bank", _altered_seed)],
}


STEP_FAULTS = [("_evict_apply", _no_count_adjustment),
               ("_evict_z", _evict_on_key_base)]


@pytest.fixture
def fresh_programs():
    """Faults planted below the jitted entry points take effect only in
    programs traced after the patch: start and end with empty caches."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("cell", ["adcap-ingest", "bank-ingest",
                                  "adcap-dist4"])
def test_sound_run_is_correct(cell):
    assert run_small(cell)["correct"]


@pytest.mark.parametrize("cell,target,fault", [
    (c, t, f) for c, fs in FAULTS.items() for t, f in fs],
    ids=lambda x: getattr(x, "__name__", str(x)))
def test_service_fault_is_caught(monkeypatch, cell, target, fault):
    monkeypatch.setattr(I, target, fault(getattr(I, target)))
    assert not run_small(cell)["correct"]


@pytest.mark.parametrize("cell,target,fault", [
    (c, t, f) for c in FAULTS for t, f in STEP_FAULTS],
    ids=lambda x: getattr(x, "__name__", str(x)))
def test_chunk_step_fault_is_caught(monkeypatch, fresh_programs, cell,
                                    target, fault):
    monkeypatch.setattr(VZ, target, fault(getattr(VZ, target)))
    assert not run_small(cell)["correct"]


def _no_exchange(keys, seeds, cap, axis_name):
    return keys, seeds


def _pass1_unchanged(orig):
    def f(keys_shard, weights_shard, *, ls, salt, k, chunk, axis_name,
          merge="tree"):
        empty = jnp.full_like(keys_shard[:chunk], 2**31 - 1)
        return orig(empty, weights_shard[:chunk],
                    ls=ls, salt=salt, k=k, chunk=chunk, axis_name=axis_name,
                    merge=merge)
    return f


def _pass1_half(orig):
    def f(keys_shard, weights_shard, **kw):
        n = keys_shard.shape[0] // 2
        return orig(keys_shard[:n], weights_shard[:n], **kw)
    return f


def _weights_altered(orig):
    def f(*args, **kw):
        return orig(*args, **kw) + 1.0
    return f


@pytest.mark.parametrize("target,patch", [
    ("pass1_shard_multi", _pass1_unchanged),
    ("pass1_shard_multi", _pass1_half),
    ("tree_merge_bottomk_multi", lambda orig: _no_exchange),
    ("pass2_shard_multi", _weights_altered),
], ids=["state_unchanged", "half_batch", "no_exchange", "answer_altered"])
def test_two_pass_fault_is_caught(monkeypatch, target, patch):
    monkeypatch.setattr(DD, target, patch(getattr(DD, target)))
    assert not run_small("adcap-dist4")["correct"]
