"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

The TPU compiler is installed on CPU-only hosts too: it compiles for a chip
that is described (``v5e:2x2``) but not attached, and refuses exactly what
the chip's compiler would refuse — unsupported Mosaic lowerings, VMEM over
budget, misaligned blocks — which interpret-mode tests cannot see.  Nothing
runs, so these tests say nothing about results or speed.

The topology is described only inside the ``topo`` fixture (never at
import): only one process at a time may load the TPU library, and every
test worker imports this file.  All such compiles live in this one file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import obs
from repro.core import incremental as I
from repro.kernels.capscore import capscore as CS
from repro.kernels.capscore.tiling import tile_config
from repro.kernels.chunksort import chunksort as CK

LS = (1.0, 16.0, 256.0, 4096.0)  # StatsConfig's shipped l-grid


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    """Compile ``fn`` for the described chip; the Mosaic kernels must be in
    the program (a Pallas route traced in interpret mode would not be)."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("n", [2048, 65536])
def test_capscore_compiles_for_v5e(one_chip, n):
    s = lambda shape, dt: _sds(one_chip, shape, dt)
    _compile(lambda k, e, w, l, t, salt: CS.capscore(
                 k, e, w, l, t, salt, interpret=False,
                 cfg=tile_config("capscore", "tpu")),
             s((n,), jnp.int32), s((n,), jnp.int32), s((n,), jnp.float32),
             s((), jnp.float32), s((), jnp.float32), s((), jnp.uint32))


@pytest.mark.parametrize("n", [2048, 65536])
def test_capscore_multi_compiles_for_v5e(one_chip, n):
    s = lambda shape, dt: _sds(one_chip, shape, dt)
    L = len(LS)
    _compile(lambda k, e, w, ls, ts, salt: CS.capscore_multi(
                 k, e, w, ls, ts, salt, n_l=L, interpret=False,
                 cfg=tile_config("capscore_multi", "tpu")),
             s((n,), jnp.int32), s((n,), jnp.int32), s((n,), jnp.float32),
             s((L,), jnp.float32), s((L,), jnp.float32), s((), jnp.uint32))


@pytest.mark.parametrize("C,n_l", [(2048, 1), (2048, 4), (4096, 1),
                                   (4096, 4)])
def test_capscore_agg_compiles_for_v5e(one_chip, C, n_l):
    """The fused score+aggregate kernel keeps its whole packed output
    resident in VMEM: it must fit at the shipped chunk (2048) and at 4096."""
    s = lambda shape, dt: _sds(one_chip, shape, dt)
    _compile(lambda k, e, w, seg, ls, ts, salt: CS.capscore_agg(
                 k, e, w, seg, ls, ts, salt, n_l=n_l, interpret=False,
                 cfg=tile_config("capscore_agg", "tpu")),
             s((C,), jnp.int32), s((C,), jnp.int32), s((C,), jnp.float32),
             s((C,), jnp.int32), s((n_l,), jnp.float32),
             s((n_l,), jnp.float32), s((), jnp.uint32))


@pytest.mark.parametrize("P", [256, 1024, 2048, 8192])
def test_chunksort_compiles_for_v5e(one_chip, P):
    s = lambda shape, dt: _sds(one_chip, shape, dt)
    _compile(lambda k, i: CK.sort_pairs(k, i, interpret=False,
                                        cfg=tile_config("chunksort", "tpu")),
             s((P,), jnp.int32), s((P,), jnp.int32))


def test_multi_lane_chunk_step_compiles_for_v5e(one_chip, monkeypatch):
    """The whole multi-lane chunk step at the shipped defaults (k=4096, four
    lanes, chunk 2048) with both Pallas routes forced.  The kernel wrappers
    pick interpret mode and tile flavors from the platform, which is the
    CPU here: the test steers that decision to the TPU's."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    state, spec = I.init_multi_state(LS, k=4096, chunk=2048,
                                     backend="pallas", sort_backend="pallas")
    shapes = jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype), state)
    n = 2 * spec.chunk
    text = _compile(lambda st, k, w: I._update_multi_impl(st, k, w, spec),
                    shapes, _sds(one_chip, (n,), jnp.int32),
                    _sds(one_chip, (n,), jnp.float32))
    # one block sort + three merge passes (2048 = 256 * 2^3) + the aggregate
    assert text.count("tpu_custom_call") >= 5
    # every chunk-step stage scope reaches the compiled program's metadata,
    # where the benchmark's stage readers find it (repro.obs.stage_map)
    for name in obs.CHUNK_STAGES:
        assert f"/{name}/" in text, name
    assert set(obs.parse_stage_map(text).stages.values()) >= set(
        obs.CHUNK_STAGES)
    # the Mosaic kernels keep the instruction names the benchmark's kernel
    # readers match in a device trace
    from bench.harness.readers import KERNEL_PATTERNS

    kernels = re.findall(r"^\s+(?:ROOT )?%?([^\s=]+) = .*custom_call_target="
                         r'"tpu_custom_call"', text, re.M)
    for pat in ("capscore_agg", "chunksort"):
        assert any(KERNEL_PATTERNS[pat].search(k) for k in kernels), pat


def test_distributed_two_pass_compiles_for_v5e_2x2(topo, monkeypatch):
    """The multi-l distributed two-pass program over all four chips of the
    described host: the compiled Pallas scoring kernel inside
    ``jax.shard_map`` must state how its outputs vary across the mesh, and
    the merges must lower to cross-chip collectives."""
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.core import distributed as DD
    from repro.launch.mesh import make_mesh

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = make_mesh((4,), ("data",), devices=topo.devices)
    shard = NamedSharding(mesh, PartitionSpec("data"))
    n = 4 * 4096
    fn = DD.make_distributed_two_pass_multi(mesh, ls=LS, salt=7, k=256,
                                            chunk=2048)
    text = fn.lower(_sds(shard, (n,), jnp.int32),
                    _sds(shard, (n,), jnp.float32)).compile().as_text()
    assert "tpu_custom_call" in text
    assert "collective-permute" in text and "all-reduce" in text
    assert set(obs.parse_stage_map(text).stages.values()) == set(
        obs.TWO_PASS_STAGES)
