"""reprolint: fixture-based good/bad pairs per rule, pragma/baseline
mechanics, config parsing, repo cleanliness, and the retrace contract."""
import json
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

REPO_ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO_ROOT))

from tools.reprolint import Config, lint_text  # noqa: E402
from tools.reprolint.config import _read_toml_section  # noqa: E402
from tools.reprolint.engine import LintEngine, lint_paths  # noqa: E402

HOT = "src/repro/core/incremental.py"  # hot-path module in the default config
COLD = "src/repro/stats/service.py"    # library but not hot-path
REGISTRY = "src/repro/core/segments.py"


def codes(src, relpath=HOT):
    return [v.code for v in lint_text(textwrap.dedent(src), relpath)]


# ---------------------------------------------------------------------------
# RPL001 — host-device sync
# ---------------------------------------------------------------------------

def test_rpl001_jit_scope_float_on_traced_bad():
    src = """
        import jax

        @jax.jit
        def f(x):
            return float(x) + 1.0
    """
    assert "RPL001" in codes(src)


def test_rpl001_jit_scope_item_bad():
    src = """
        import jax

        @jax.jit
        def f(x):
            return x.sum().item()
    """
    assert "RPL001" in codes(src)


def test_rpl001_jit_scope_np_on_traced_bad():
    src = """
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            return np.sum(x)
    """
    assert "RPL001" in codes(src)


def test_rpl001_jit_scope_shape_and_static_good():
    src = """
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("spec",))
        def f(x, spec):
            n = int(x.shape[0])
            k = float(spec.k)
            return x * n + k
    """
    assert "RPL001" not in codes(src)


def test_rpl001_hot_module_state_pull_bad():
    src = """
        def finalize(state: SamplerState):
            return float(state.l)
    """
    assert "RPL001" in codes(src)


def test_rpl001_hot_module_device_get_good():
    src = """
        import jax

        def finalize(state: SamplerState):
            l = jax.device_get(state.l)
            return float(l)
    """
    assert "RPL001" not in codes(src)


def test_rpl001_jit_call_result_tracked():
    # values returned by a module-level jitted name are device-tainted
    src = """
        import functools
        import jax

        def _impl(state, keys):
            return state

        _update = functools.partial(jax.jit, donate_argnums=(0,))(_impl)

        def run(state: SamplerState, keys):
            st = _update(state, keys)
            return int(st.overflow)
    """
    assert "RPL001" in codes(src)


def test_rpl001_unannotated_param_not_flagged():
    # hostness is conservative: unknown roots never flag
    src = """
        def summarize(result):
            return float(result.estimate)
    """
    assert "RPL001" not in codes(src)


# ---------------------------------------------------------------------------
# RPL002 — selection primitives outside the dual registry
# ---------------------------------------------------------------------------

RPL002_SRC = """
    import jax.numpy as jnp

    def pick(x):
        return jnp.argsort(x)
"""


def test_rpl002_hot_module_bad():
    assert "RPL002" in codes(RPL002_SRC)


def test_rpl002_top_k_bad():
    src = """
        import jax

        def pick(x):
            return jax.lax.top_k(x, 4)
    """
    assert "RPL002" in codes(src)


def test_rpl002_registry_exempt_good():
    assert "RPL002" not in codes(RPL002_SRC, relpath=REGISTRY)


def test_rpl002_cold_module_good():
    assert "RPL002" not in codes(RPL002_SRC, relpath=COLD)


# ---------------------------------------------------------------------------
# RPL003 — state-advancing jit without donation
# ---------------------------------------------------------------------------

def test_rpl003_partial_jit_no_donate_bad():
    src = """
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("spec",))
        def _update(state, keys, spec):
            return state
    """
    assert "RPL003" in codes(src, relpath=COLD)


def test_rpl003_donated_good():
    src = """
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("spec",), donate_argnums=(0,))
        def _update(state, keys, spec):
            return state
    """
    assert "RPL003" not in codes(src, relpath=COLD)


def test_rpl003_lambda_jit_bad_then_donated():
    bad = """
        import jax
        step = jax.jit(lambda cache, tok: (cache, tok))
    """
    good = """
        import jax
        step = jax.jit(lambda cache, tok: (cache, tok), donate_argnums=(0,))
    """
    assert "RPL003" in codes(bad, relpath=COLD)
    assert "RPL003" not in codes(good, relpath=COLD)


def test_rpl003_stateless_params_good():
    src = """
        import jax

        @jax.jit
        def score(keys, weights, salt):
            return keys
    """
    assert "RPL003" not in codes(src, relpath=COLD)


# ---------------------------------------------------------------------------
# RPL004 — f64 literals outside enable_x64
# ---------------------------------------------------------------------------

def test_rpl004_bare_f64_bad():
    src = """
        import jax.numpy as jnp

        def acc():
            return jnp.zeros((4,), jnp.float64)
    """
    assert "RPL004" in codes(src)


def test_rpl004_inside_enable_x64_good():
    src = """
        import jax.numpy as jnp

        def acc():
            with enable_x64():
                return jnp.zeros((4,), jnp.float64)
    """
    assert "RPL004" not in codes(src)


def test_rpl004_inside_jax_enable_x64_true_good():
    """The ``jax.enable_x64(True)`` context form opens an x64 scope too."""
    src = """
        import jax
        import jax.numpy as jnp

        def acc():
            with jax.enable_x64(True):
                return jnp.zeros((4,), jnp.float64)
    """
    assert "RPL004" not in codes(src)


def test_rpl004_out_of_scope_good():
    src = """
        import jax.numpy as jnp

        def acc():
            return jnp.zeros((4,), jnp.float64)
    """
    assert "RPL004" not in codes(src, relpath="tests/test_foo.py")


# ---------------------------------------------------------------------------
# RPL005 — ambient randomness in library scope
# ---------------------------------------------------------------------------

def test_rpl005_np_random_bad():
    src = """
        import numpy as np

        def scores(n):
            return np.random.default_rng(0).uniform(size=n)
    """
    assert "RPL005" in codes(src)


def test_rpl005_jax_prngkey_bad():
    src = """
        import jax

        def scores(n):
            key = jax.random.PRNGKey(0)
            return jax.random.uniform(key, (n,))
    """
    assert codes(src).count("RPL005") == 2


def test_rpl005_from_import_bad():
    src = """
        from numpy.random import default_rng

        def scores(n):
            return default_rng(0).uniform(size=n)
    """
    assert "RPL005" in codes(src)


def test_rpl005_out_of_scope_good():
    src = """
        import numpy as np

        def workload(n):
            return np.random.default_rng(0).integers(0, n, n)
    """
    assert "RPL005" not in codes(src, relpath="benchmarks/gen.py")
    assert "RPL005" not in codes(src, relpath="src/repro/data/synth.py")


# ---------------------------------------------------------------------------
# RPL006 — raw sentinel comparisons
# ---------------------------------------------------------------------------

def test_rpl006_raw_compare_bad():
    src = """
        def live_mask(keys):
            return keys != EMPTY
    """
    assert "RPL006" in codes(src)


def test_rpl006_int_empty_and_literal_bad():
    src = """
        def masks(keys):
            a = keys == int(EMPTY)
            b = keys == 2147483647
            return a, b
    """
    assert codes(src).count("RPL006") == 2


def test_rpl006_helper_good():
    src = """
        from .segments import is_live

        def live_mask(keys):
            return is_live(keys)
    """
    assert "RPL006" not in codes(src)


def test_rpl006_registry_exempt_good():
    src = """
        def is_live(keys):
            return keys != EMPTY
    """
    assert "RPL006" not in codes(src, relpath=REGISTRY)


# ---------------------------------------------------------------------------
# RPL007 — unhashable static defaults
# ---------------------------------------------------------------------------

def test_rpl007_list_default_bad():
    src = """
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("ls",))
        def f(x, ls=[1.0, 2.0]):
            return x
    """
    assert "RPL007" in codes(src, relpath=COLD)


def test_rpl007_tuple_default_good():
    src = """
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("ls",))
        def f(x, ls=(1.0, 2.0)):
            return x
    """
    assert "RPL007" not in codes(src, relpath=COLD)


def test_rpl007_nonstatic_list_default_good():
    # an unhashable default on a *traced* arg is not a cache-key problem
    src = """
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("k",))
        def f(x, k=4, pads=[0, 0]):
            return x
    """
    assert "RPL007" not in codes(src, relpath=COLD)


# ---------------------------------------------------------------------------
# Pragmas, baseline, config
# ---------------------------------------------------------------------------

def test_pragma_same_line_suppresses():
    src = """
        import jax.numpy as jnp

        def pick(x):
            return jnp.argsort(x)  # reprolint: disable=RPL002 -- boundary conversion
    """
    assert "RPL002" not in codes(src)


def test_pragma_comment_block_above_suppresses():
    src = """
        import jax.numpy as jnp

        def pick(x):
            # reprolint: disable=RPL002 -- once-per-restore boundary, not
            # on the per-chunk path
            return jnp.argsort(x)
    """
    assert "RPL002" not in codes(src)


def test_pragma_without_justification_does_not_suppress():
    # the bare pragma is assembled at runtime so the textual pragma scanner
    # doesn't flag this fixture when linting the test file itself
    src = """
        import jax.numpy as jnp

        def pick(x):
            return jnp.argsort(x)  # PRAGMA
    """.replace("PRAGMA", "reprolint" + ": disable=RPL002")
    got = codes(src)
    assert "RPL002" in got      # not suppressed
    assert "RPL000" in got      # and the bare pragma itself is reported


def test_file_level_pragma_suppresses():
    src = """
        # reprolint: disable-file=RPL002 -- reference oracle module, sorts allowed
        import jax.numpy as jnp

        def pick(x):
            return jnp.argsort(x)
    """
    assert "RPL002" not in codes(src)


def test_baseline_matches_by_context(tmp_path):
    (tmp_path / "baseline.json").write_text(json.dumps({
        "version": 1,
        "entries": [{"code": "RPL002", "path": HOT, "context": "pick",
                     "reason": "fixture"}],
    }))
    config = Config.from_mapping(tmp_path, {"baseline": "baseline.json"})
    engine = LintEngine(config)
    src = textwrap.dedent(RPL002_SRC)
    result = engine.lint_source(src, HOT)
    assert not any(v.code == "RPL002" for v in result.violations)
    assert result.baselined == 1
    # a different context does not match
    other = src.replace("def pick", "def choose")
    result2 = LintEngine(config).lint_source(other, HOT)
    assert any(v.code == "RPL002" for v in result2.violations)


def test_toml_section_parser():
    text = textwrap.dedent("""
        [tool.other]
        x = 1

        [tool.reprolint]
        baseline = "b.json"  # trailing comment
        hot_path = [
            "src/a.py",  # comment in list
            "src/b/*.py",
        ]
        flag = true
        n = 3

        [tool.after]
        y = 2
    """)
    got = _read_toml_section(text, "tool.reprolint")
    assert got == {
        "baseline": "b.json",
        "hot_path": ["src/a.py", "src/b/*.py"],
        "flag": True,
        "n": 3,
    }


def test_repo_is_clean():
    """The committed tree has zero unsuppressed violations (CI acceptance)."""
    result = lint_paths(root=REPO_ROOT)
    assert result.ok, "\n".join(v.render() for v in result.violations)


def test_pallas_empty_key_matches_sentinel():
    # capscore.py mirrors segments.EMPTY as a kernel-local np scalar (jnp
    # constants don't lower inside the Mosaic kernel); keep them in lockstep.
    from repro.core.segments import EMPTY
    from repro.kernels.capscore.capscore import _EMPTY_KEY

    assert int(_EMPTY_KEY) == int(EMPTY)
    assert _EMPTY_KEY.dtype == np.int32


# ---------------------------------------------------------------------------
# Retrace contract
# ---------------------------------------------------------------------------

def test_incremental_update_compiles_exactly_once():
    """Repeated same-shape chunk batches reuse ONE executable (the donated
    update's steady-state contract; budgeted in reprolint_traces.json)."""
    from repro.core import incremental as inc

    # unique (chunk, k) so compiles from other tests in this process don't
    # collide with the delta measurement
    chunk, k = 320, 48
    before = inc._update_multi_donated._cache_size()
    m = inc.MultiSampler([2.0, 8.0], k=k, chunk=chunk)
    for b in range(3):
        m.observe(np.arange(2 * chunk, dtype=np.int64) + 7 * b)
    after = inc._update_multi_donated._cache_size()
    assert after - before == 1


def test_retrace_budget_file_consistent():
    data = json.loads((REPO_ROOT / "tools/reprolint/reprolint_traces.json").read_text())
    budgets = data["budgets"]
    assert budgets and all(isinstance(v, int) and v >= 0 for v in budgets.values())
    from tools.reprolint import retrace

    # the committed budget must encode the exactly-once steady-state contract
    for key in retrace._EXACTLY_ONCE:
        assert budgets[key] == 1, key
