"""Regression tests for the version-compat layer (ISSUE 1 bugfixes):

* ``repro.launch.mesh`` imports and builds meshes on the installed jax
  (0.9.0);
* test collection survives without ``hypothesis`` installed (the bundled
  fallback in tests/_hypothesis_fallback.py takes over).

Subprocess-based, mirroring tests/test_multidevice.py's pattern, so the
main pytest process's module state is never perturbed."""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def _run(args, env_extra=None, cwd=REPO):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.update(env_extra or {})
    return subprocess.run(args, capture_output=True, text=True,
                          timeout=300, env=env, cwd=cwd)


def test_mesh_imports_and_builds_on_installed_jax():
    r = _run([sys.executable, "-c", textwrap.dedent("""
        import repro.launch.mesh as m
        from repro import compat
        mesh = m.single_device_mesh()
        assert tuple(mesh.axis_names) == ("data", "model"), mesh
        mesh2 = compat.make_mesh((1, 1), ("a", "b"))
        assert tuple(mesh2.axis_names) == ("a", "b")
        print("MESH OK", compat.JAX_VERSION, compat.HAS_AXIS_TYPE)
    """)])
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    assert "MESH OK" in r.stdout


def test_compat_is_single_home_for_version_gated_imports():
    """No module outside repro/compat.py may import the symbols that have
    moved between jax releases (AxisType, shard_map) straight from jax —
    the next jax bump must stay a one-file change."""
    offenders = []
    for dirpath, _, files in os.walk(os.path.join(SRC, "repro")):
        for fname in files:
            if not fname.endswith(".py") or fname == "compat.py":
                continue
            path = os.path.join(dirpath, fname)
            with open(path) as f:
                text = f.read()
            for needle in ("from jax.sharding import AxisType",
                           "jax.sharding.AxisType",
                           "jax.experimental.shard_map",
                           "jax.shard_map",
                           "jax.lax.axis_size"):
                if needle in text:
                    offenders.append((os.path.relpath(path, SRC), needle))
    assert not offenders, offenders


def _no_hypothesis_env(tmp_path):
    """A dir whose hypothesis.py raises ImportError — simulates the package
    being absent even when the interpreter has it installed."""
    blocker = tmp_path / "blocker"
    blocker.mkdir()
    (blocker / "hypothesis.py").write_text(
        'raise ImportError("hypothesis blocked for compat regression test")\n')
    return {"PYTHONPATH": str(blocker) + os.pathsep + SRC}


def test_collect_only_succeeds_without_hypothesis(tmp_path):
    r = _run([sys.executable, "-m", "pytest", "--collect-only", "-q",
              "tests"], env_extra=_no_hypothesis_env(tmp_path))
    assert r.returncode == 0, f"stdout:\n{r.stdout[-3000:]}\nstderr:\n{r.stderr[-3000:]}"
    summary = [ln for ln in r.stdout.strip().splitlines() if ln.strip()][-1]
    assert "tests collected" in summary and "error" not in summary, summary


def test_property_tests_run_on_fallback(tmp_path):
    """Without hypothesis, @given tests still execute (bundled fallback) —
    and still fail on a falsified property, rather than silently passing."""
    prop = tmp_path / "test_fallback_prop.py"
    prop.write_text(textwrap.dedent("""
        from hypothesis import given
        from hypothesis import strategies as st

        @given(st.lists(st.integers(0, 50), min_size=1, max_size=20))
        def test_sorted_is_permutation(xs):
            assert sorted(xs)[0] == min(xs)

        @given(st.integers(1, 100))
        def test_falsifiable_property_fails(n):
            assert n < 50  # must be caught by the fallback runner

        from hypothesis import assume

        @given(st.integers(1, 100))
        def test_unsatisfiable_assume_fails(n):
            assume(False)   # 0 examples executed -> must NOT pass vacuously
    """))
    # minimal conftest that installs the fallback, like tests/conftest.py
    conftest = tmp_path / "conftest.py"
    conftest.write_text(textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {os.path.join(REPO, 'tests')!r})
        try:
            from hypothesis import given  # noqa: F401
        except ImportError:
            import _hypothesis_fallback
            _hypothesis_fallback.install()
    """))
    r = _run([sys.executable, "-m", "pytest", "-q", str(prop)],
             env_extra=_no_hypothesis_env(tmp_path), cwd=str(tmp_path))
    assert "2 failed, 1 passed" in r.stdout, \
        f"stdout:\n{r.stdout[-3000:]}\nstderr:\n{r.stderr[-2000:]}"
    assert "Falsifying example" in r.stdout
    assert "Unable to satisfy assumptions" in r.stdout


def test_full_tier1_collection_clean():
    """pytest --collect-only in the *current* environment: zero collection
    errors (the seed's headline failure mode)."""
    r = _run([sys.executable, "-m", "pytest", "--collect-only", "-q",
              "tests"])
    assert r.returncode == 0, f"stdout:\n{r.stdout[-3000:]}\nstderr:\n{r.stderr[-3000:]}"
