"""The suite's own harness (tests/conftest.py): a test's limit and the
bound on a worker's memory mappings."""

import gc
import os
import subprocess
import sys
import textwrap

import pytest

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))


def test_sleeping_test_fails_by_name_and_the_next_runs(tmp_path):
    """A child pytest loads this suite's conftest as a plugin, with the
    limit cut to half a second: the sleeper fails with its own name in the
    message, and the test after it still runs and passes."""
    (tmp_path / "test_sleeper.py").write_text(textwrap.dedent("""
        import time

        import conftest

        conftest.TEST_LIMIT_S = 0.5


        def test_sleeps():
            time.sleep(60)


        def test_next():
            pass
    """))
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "conftest",
         "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly",
         "--rootdir", str(tmp_path), str(tmp_path)],
        env={**os.environ, "PYTHONPATH": TESTS_DIR, "JAX_PLATFORMS": "cpu"},
        cwd=str(tmp_path), capture_output=True, text=True, timeout=120)
    out = r.stdout + r.stderr
    assert r.returncode == 1, out
    assert "1 failed, 1 passed" in out, out
    assert "test_sleeper.py::test_sleeps ran past its limit of 0.5 s" in out, \
        out


def test_dropping_compiled_programs_gives_their_mappings_back():
    """What `_bounded_maps` counts on: the mappings of compiled programs
    go when the caches are cleared, with the jitted functions still held."""
    import jax
    import jax.numpy as jnp
    from conftest import drop_compiled_programs, live_maps

    if not live_maps():
        pytest.skip("no /proc/self/maps here")
    fns = [jax.jit(lambda x, i=i: jnp.cumsum(jnp.sort(x)) + i)
           for i in range(12)]
    # an earlier test's garbage may still hold programs: a collection in the
    # middle of this one gave their mappings back and read as -1,655 grown
    gc.collect()
    before = live_maps()
    for i, f in enumerate(fns):
        f(jnp.arange(64 + i))
    grown = live_maps() - before
    assert grown >= 12 * 3, grown  # a program is several mappings
    drop_compiled_programs()
    assert live_maps() - before < grown / 2
    assert int(fns[0](jnp.arange(64))[-1]) == 63 * 64 // 2  # compiles again
