"""aqplint fixture suite: every pass must catch its bad snippet and
accept its good twin, suppressions/baseline must behave, and the CLI
must produce the documented exit codes.

These tests run the analyzer on throwaway fixture trees under
``tmp_path`` — never on the real repo (the repo-wide run is the CI lint
job, pinned clean by ``tools/aqplint/baseline.json``).
"""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from aqplint import baseline as baseline_mod
from aqplint.__main__ import build_findings
from aqplint.core import Project, parse_suppressions
from aqplint.passes import ALL_PASSES

TOOLS_DIR = Path(__file__).resolve().parent.parent / "tools"


def lint(tmp_path, files, only=None):
    """Write fixture ``files`` (relpath -> source), lint, return findings."""
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    project = Project([tmp_path], repo_root=tmp_path)
    if only is None:
        return build_findings(project)
    out = []
    for name, run in ALL_PASSES:
        if name in only:
            out.extend(run(project))
    return out


def codes(findings):
    return sorted(f.code for f in findings)


# -- purity (AQP101) -----------------------------------------------------------

def test_purity_flags_host_sync_in_jit_root(tmp_path):
    found = lint(tmp_path, {"mod.py": """
        import jax
        import numpy as np

        @jax.jit
        def bad(x):
            return np.asarray(x).item() + float(x)
    """}, only={"purity"})
    assert codes(found).count("AQP101") == 3  # np.asarray, .item, float


def test_purity_flags_print_in_while_loop_body(tmp_path):
    found = lint(tmp_path, {"mod.py": """
        import jax

        def outer(x):
            def body(c):
                print(c)
                return c - 1
            return jax.lax.while_loop(lambda c: c > 0, body, x)
    """}, only={"purity"})
    assert codes(found) == ["AQP101"]
    assert found[0].symbol == "outer.body"


def test_purity_accepts_pure_and_static_casts(tmp_path):
    found = lint(tmp_path, {"mod.py": """
        import functools
        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit, static_argnames=("n",))
        def good(x, n):
            return jnp.asarray(x) * float(n) + float(1)
    """}, only={"purity"})
    assert found == []


def test_purity_ignores_untraced_host_code(tmp_path):
    found = lint(tmp_path, {"mod.py": """
        import numpy as np

        def host_only(x):
            return float(np.asarray(x).sum())
    """}, only={"purity"})
    assert found == []


def test_purity_follows_callback_convention_params(tmp_path):
    # a closure handed over as a *_fn argument is traced by convention
    found = lint(tmp_path, {"mod.py": """
        def build(refresh_fn):
            return refresh_fn

        def make():
            def refresh(lo, hi):
                return int(lo), hi
            return build(refresh_fn=refresh)
    """}, only={"purity"})
    assert codes(found) == ["AQP101"]


# -- parity (AQP2xx) -----------------------------------------------------------

_PARITY_BASE = """
    class Bounder:
        pass
"""


def test_parity_flags_missing_device_twin(tmp_path):
    found = lint(tmp_path, {"mod.py": _PARITY_BASE + """
        class Bad(Bounder):
            def _lbound_batch(self, s, a, b, N, delta):
                return s
    """}, only={"parity"})
    assert codes(found) == ["AQP201"]


def test_parity_flags_signature_drift(tmp_path):
    found = lint(tmp_path, {"mod.py": _PARITY_BASE + """
        class Drifted(Bounder):
            def _lbound_batch(self, s, a, b, N, delta):
                return s

            def _lbound_batch_device(self, s, a, b, N, delta, extra):
                return s
    """}, only={"parity"})
    assert codes(found) == ["AQP202"]


def test_parity_flags_orphan_device_twin(tmp_path):
    found = lint(tmp_path, {"mod.py": _PARITY_BASE + """
        class Orphan(Bounder):
            def _lbound_batch_device(self, s, a, b, N, delta):
                return s
    """}, only={"parity"})
    assert codes(found) == ["AQP203"]


def test_parity_accepts_matched_pair_with_valid_extra(tmp_path):
    found = lint(tmp_path, {"mod.py": """
        class StoppingCondition:
            pass

        class Good(StoppingCondition):
            def active(self, lo, hi, est, counts):
                return lo

            def active_device(self, lo, hi, est, counts, valid):
                return lo
    """}, only={"parity"})
    assert found == []


def test_parity_module_coverage_in_count_sum(tmp_path):
    found = lint(tmp_path, {"count_sum.py": """
        __all__ = ["count_ci", "count_ci_device", "sum_ci"]

        def count_ci(m_v, r, R, delta):
            return m_v

        def count_ci_device(m_v, r, R, delta):
            return m_v

        def sum_ci(count, avg):
            return count
    """}, only={"parity"})
    assert codes(found) == ["AQP201"]
    assert "sum_ci" in found[0].message


# -- dtype (AQP3xx) ------------------------------------------------------------

def test_dtype_flags_f32_in_device_function(tmp_path):
    found = lint(tmp_path, {"mod.py": """
        import jax.numpy as jnp

        def width_batch_device(lo, hi):
            return (hi - lo).astype(jnp.float32)
    """}, only={"dtype"})
    assert codes(found) == ["AQP301"]


def test_dtype_accepts_f64_in_device_function(tmp_path):
    found = lint(tmp_path, {"mod.py": """
        import jax.numpy as jnp

        def width_batch_device(lo, hi):
            return (hi - lo).astype(jnp.float64)
    """}, only={"dtype"})
    assert found == []


_CORE_FIXTURE = """
    def count_ci_device(m_v, r, R, delta):
        return m_v
"""


def test_dtype_flags_unguarded_device_twin_caller(tmp_path):
    found = lint(tmp_path, {
        "src/core/count_sum.py": _CORE_FIXTURE,
        "src/serving.py": """
            def serve(x):
                return count_ci_device(x, 1.0, 2.0, 0.05)
        """}, only={"dtype"})
    assert codes(found) == ["AQP302"]


def test_dtype_accepts_guarded_device_twin_caller(tmp_path):
    found = lint(tmp_path, {
        "src/core/count_sum.py": _CORE_FIXTURE,
        "src/serving.py": """
            def serve(x):
                require_x64()
                return count_ci_device(x, 1.0, 2.0, 0.05)
        """}, only={"dtype"})
    assert found == []


# -- collectives (AQP4xx) ------------------------------------------------------

def test_collectives_flags_psum_outside_shard_map(tmp_path):
    found = lint(tmp_path, {"mod.py": """
        import jax

        def lonely(x):
            return jax.lax.psum(x, "shards")
    """}, only={"collectives"})
    assert codes(found) == ["AQP401"]


@pytest.mark.parametrize("imp, call", [
    ("", "jax.shard_map"),
    ("from jax import shard_map", "shard_map"),
])
def test_collectives_accepts_psum_under_shard_map(tmp_path, imp, call):
    found = lint(tmp_path, {"mod.py": f"""
        import jax
        {imp}

        def build(mesh, specs):
            def fold(x):
                return jax.lax.psum(x, "shards")
            return {call}(fold, mesh=mesh, in_specs=specs,
                          out_specs=specs)
    """}, only={"collectives"})
    assert found == []


def test_collectives_flags_unknown_and_missing_axis(tmp_path):
    found = lint(tmp_path, {"mod.py": """
        import jax
        from jax import shard_map

        def build(mesh, specs):
            def fold(x):
                a = jax.lax.psum(x, "rows")
                return a + jax.lax.pmax(x)
            return shard_map(fold, mesh=mesh, in_specs=specs,
                             out_specs=specs)
    """}, only={"collectives"})
    assert codes(found) == ["AQP402", "AQP402"]


def test_collectives_flags_pending_fold_off_cadence(tmp_path):
    files = {"mod.py": """
        import jax
        from jax import shard_map

        def build(mesh, specs):
            def {name}(c):
                return jax.lax.psum(c.pend_sums, "shards")
            def fold(c):
                return {name}(c)
            return shard_map(fold, mesh=mesh, in_specs=specs,
                             out_specs=specs)
    """}
    bad = lint(tmp_path / "bad",
               {k: v.format(name="body") for k, v in files.items()},
               only={"collectives"})
    good = lint(tmp_path / "good",
                {k: v.format(name="_merge_refresh")
                 for k, v in files.items()},
                only={"collectives"})
    assert codes(bad) == ["AQP403"]
    assert good == []


# -- shapes (AQP5xx) -----------------------------------------------------------

def test_shapes_flags_nonzero_without_size(tmp_path):
    found = lint(tmp_path, {"mod.py": """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def pick(mask):
            return jnp.nonzero(mask)
    """}, only={"shapes"})
    assert codes(found) == ["AQP501"]


def test_shapes_accepts_nonzero_with_size(tmp_path):
    found = lint(tmp_path, {"mod.py": """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def pick(mask):
            return jnp.nonzero(mask, size=8, fill_value=0)
    """}, only={"shapes"})
    assert found == []


def test_shapes_flags_traced_slice_bound(tmp_path):
    found = lint(tmp_path, {"mod.py": """
        import jax

        @jax.jit
        def head(x, n):
            return x[:n]
    """}, only={"shapes"})
    assert codes(found) == ["AQP502"]


def test_shapes_accepts_static_slice_bound(tmp_path):
    found = lint(tmp_path, {"mod.py": """
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("n",))
        def head(x, n):
            return x[:n]
    """}, only={"shapes"})
    assert found == []


def test_shapes_flags_non_hashable_static_arg(tmp_path):
    files = {"mod.py": """
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("dims",))
        def f(x, dims):
            return x

        def caller(x):
            return f(x, dims={value})
    """}
    bad = lint(tmp_path / "bad",
               {k: v.format(value="[1, 2]") for k, v in files.items()},
               only={"shapes"})
    good = lint(tmp_path / "good",
                {k: v.format(value="(1, 2)") for k, v in files.items()},
                only={"shapes"})
    assert codes(bad) == ["AQP503"]
    assert good == []


# -- faults (AQP104) -----------------------------------------------------------

def test_faults_flags_production_import_of_testing(tmp_path):
    found = lint(tmp_path, {
        "repro/__init__.py": "",
        "repro/serve/__init__.py": "",
        "repro/serve/bad.py": """
            from repro.testing.faults import FaultInjector

            def step(pas):
                return FaultInjector([])
        """}, only={"faults"})
    assert codes(found) == ["AQP104"]
    assert found[0].path.endswith("repro/serve/bad.py")


def test_faults_flags_plain_import_form(tmp_path):
    found = lint(tmp_path, {"repro/worse.py": """
        def lazy():
            import repro.testing
            return repro.testing
    """}, only={"faults"})
    assert codes(found) == ["AQP104"]
    assert found[0].symbol == "lazy"


def test_faults_exempts_harness_and_tests(tmp_path):
    found = lint(tmp_path, {
        "repro/testing/__init__.py": """
            from repro.testing.faults import FaultInjector
        """,
        "repro/testing/faults.py": """
            class FaultInjector:
                pass
        """,
        "tests/test_chaos.py": """
            from repro.testing import FaultInjector
        """,
        "benchmarks/bench_chaos.py": """
            import repro.testing.faults as faults
        """}, only={"faults"})
    assert found == []


# -- suppressions --------------------------------------------------------------

_BAD_JIT = """
    import jax

    @jax.jit
    def bad(x):
        return float(x){comment}
"""


def test_suppression_with_reason_silences_finding(tmp_path):
    found = lint(tmp_path, {"mod.py": _BAD_JIT.format(
        comment="  # aqplint: disable=AQP101(x is static here)")})
    assert found == []


def test_suppression_without_reason_is_not_honoured(tmp_path):
    found = lint(tmp_path, {"mod.py": _BAD_JIT.format(
        comment="  # aqplint: disable=AQP101")})
    assert codes(found) == ["AQP001", "AQP101"]


def test_unused_suppression_is_flagged(tmp_path):
    found = lint(tmp_path, {"mod.py": """
        def fine():  # aqplint: disable=AQP101(not actually needed)
            return 1
    """})
    assert codes(found) == ["AQP002"]


def test_suppression_inside_string_literal_is_ignored(tmp_path):
    found = lint(tmp_path, {"mod.py": '''
        SNIPPET = """
        x = 1  # aqplint: disable=AQP101(inside a string, not a comment)
        """
    '''})
    assert found == []


def test_suppression_on_comment_line_applies_to_next_line(tmp_path):
    found = lint(tmp_path, {"mod.py": """
        import jax

        @jax.jit
        def bad(x):
            # aqplint: disable=AQP101(x is static here)
            return float(x)
    """})
    assert found == []


def test_parse_suppressions_extracts_code_and_reason():
    sups = parse_suppressions(
        "x = 1  # aqplint: disable=AQP301(fold-side f32 by design)\n")
    assert len(sups) == 1
    assert sups[0].code == "AQP301"
    assert sups[0].reason == "fold-side f32 by design"
    assert sups[0].line == 1


# -- baseline ------------------------------------------------------------------

def test_baseline_diff_splits_new_and_stale(tmp_path):
    found = lint(tmp_path, {"mod.py": _BAD_JIT.format(comment="")})
    assert codes(found) == ["AQP101"]
    base = {baseline_mod.key_of(found[0]): 1,
            "AQP999::gone.py::nope": 1}
    new, stale = baseline_mod.diff(found, base)
    assert new == []
    assert stale == ["AQP999::gone.py::nope"]
    # a second identical finding would exceed the count of 1
    new2, _ = baseline_mod.diff(found * 2, base)
    assert len(new2) == 1


def test_baseline_roundtrip(tmp_path):
    found = lint(tmp_path, {"mod.py": _BAD_JIT.format(comment="")})
    path = tmp_path / "baseline.json"
    baseline_mod.save(path, found)
    loaded = baseline_mod.load(path)
    assert loaded == {baseline_mod.key_of(found[0]): 1}


# -- CLI smoke -----------------------------------------------------------------

def run_cli(cwd, *args):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(TOOLS_DIR)
    return subprocess.run(
        [sys.executable, "-m", "aqplint", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


@pytest.mark.slow
def test_cli_exit_codes_and_baseline_flow(tmp_path):
    src = tmp_path / "src"
    src.mkdir()
    (src / "mod.py").write_text(textwrap.dedent(_BAD_JIT.format(
        comment="")))

    dirty = run_cli(tmp_path, "src")
    assert dirty.returncode == 1
    assert "AQP101" in dirty.stdout

    wrote = run_cli(tmp_path, "src", "--write-baseline",
                    "--baseline", "base.json")
    assert wrote.returncode == 0
    assert json.loads((tmp_path / "base.json").read_text())["findings"]

    baselined = run_cli(tmp_path, "src", "--baseline", "base.json")
    assert baselined.returncode == 0
    assert "1 baselined" in baselined.stdout

    ignored = run_cli(tmp_path, "src", "--baseline", "base.json",
                      "--no-baseline")
    assert ignored.returncode == 1

    missing = run_cli(tmp_path, "no_such_dir")
    assert missing.returncode == 2


@pytest.mark.slow
def test_cli_clean_tree_exits_zero_with_json(tmp_path):
    src = tmp_path / "src"
    src.mkdir()
    (src / "mod.py").write_text("def fine():\n    return 1\n")
    clean = run_cli(tmp_path, "src", "--json")
    assert clean.returncode == 0
    payload = json.loads(clean.stdout)
    assert payload["new"] == []


# -- repo-wide invariant -------------------------------------------------------

@pytest.mark.slow
def test_repo_is_clean_against_committed_baseline():
    """The CI lint job's contract, runnable locally: the real tree has
    no findings beyond tools/aqplint/baseline.json."""
    repo = TOOLS_DIR.parent
    res = run_cli(repo, "src", "tests")
    assert res.returncode == 0, res.stdout + res.stderr
