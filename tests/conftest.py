"""Test configuration.

Every test runs on XLA:CPU with eight virtual devices
(``xla_force_host_platform_device_count``): the mesh tests
(``test_mesh_exec.py``, ``test_mesh_exchange.py``, ``test_aot_tpu_compile.py``'s
2x2 case) need more than one device to build a ``Mesh`` and run real
collectives in one process, and eight is the most any of them asks for. The
devices cost nothing until a program spans them (tests/test_tpch.py alone:
156.9 CPU-seconds with eight, 156.7 with one). What the suite does pay
for is XLA:CPU's compile latency: a test's seconds are mostly the compiles
of the programs its ``ExecConfig`` mints (multi-operand sorts: one to two
seconds each), once per process, because XLA:CPU executables are never
cached on disk (``presto_tpu/__init__.py``). So a module shares one catalog
and as few ``ExecConfig``s as its assertions allow, and a new test over 30 s
gives the reason in its docstring.
"""

import os

# Tests force the CPU: the flag before jax is imported, the platform right
# after, before any backend is initialized.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# XLA:CPU is this suite's test bench and nobody's deployment: what a test
# holds is the program's answer, not the speed of LLVM's code. At the
# backend's lowest optimization level (and without LLVM's expensive passes)
# tests/test_grace_agg.py costs 354 CPU-seconds for 589, with every answer
# unchanged. tests/test_aot_tpu_compile.py, which asks the TPU's compiler
# for its verdict, turns this off for its module.
jax.config.update("jax_disable_most_optimizations", True)

import gc  # noqa: E402
import signal  # noqa: E402

import numpy as np  # noqa: E402
import pandas as pd  # noqa: E402
import pytest  # noqa: E402

# A test's own limit: a hang fails one test by name and the suite goes on.
# Four times the slowest test that is kept (75 s among six workers).
TEST_LIMIT_S = 300.0


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running sweeps excluded from the tier-1 'not slow' run")


@pytest.fixture(autouse=True)
def _test_limit(request):
    """Fail a test by name once it has run for TEST_LIMIT_S. Autouse and
    first in this file, so the fixtures a test sets up after it (module
    catalogs, clusters) are inside the limit too. SIGALRM is handled between
    bytecodes: a wait on a lock or a socket is interrupted, one XLA compile
    is not (it ends, then the failure is raised)."""
    limit = TEST_LIMIT_S

    def on_alarm(signum, frame):
        pytest.fail(f"{request.node.nodeid} ran past its limit of "
                    f"{limit:g} s", pytrace=False)

    old = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, limit)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)


def live_maps() -> int:
    """Memory mappings this process holds (0 where /proc is not there)."""
    try:
        with open("/proc/self/maps") as f:
            return sum(1 for _ in f)
    except OSError:
        return 0


def _max_map_count() -> int:
    try:
        with open("/proc/sys/vm/max_map_count") as f:
            return int(f.read())
    except (OSError, ValueError):
        return 65530  # the kernel's default


_MAP_BUDGET = _max_map_count() // 2


def drop_compiled_programs() -> None:
    """Let go of every XLA:CPU executable this process holds. The jitted
    functions stay valid: the next call of one compiles it again."""
    jax.clear_caches()
    gc.collect()


@pytest.fixture(autouse=True)
def _bounded_maps():
    """The home of the suite's roaming abort (ROADMAP D10). XLA:CPU maps
    every compiled program's code into the process, about 25 mappings a
    program and some hundreds for a fused or an HLL step, and jax's caches
    keep them while the function lives: a worker a quarter of an hour old
    held 35,000-64,000, and one module's runner can hold 56,000. At
    ``vm.max_map_count`` (65,530) the next mmap fails inside LLVM's JIT
    (``LLVM compilation error: Cannot allocate memory``) and the process
    dies in ``backend_compile_and_load``, segfaulted or aborted, in
    whichever test compiled next. So a worker past half the limit drops
    what it has compiled before the next test."""
    yield
    if live_maps() > _MAP_BUDGET:
        drop_compiled_programs()


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(1234)


def assert_frames_match(got: pd.DataFrame, exp: pd.DataFrame, sort_by=None,
                        rtol=1e-9, check_order=False):
    """QueryAssertions analog: compare result sets, numeric tolerance,
    optional row-order insensitivity."""
    import decimal

    assert list(got.columns) == list(exp.columns), (
        f"columns differ: {list(got.columns)} vs {list(exp.columns)}"
    )
    g, e = got.copy(), exp.copy()

    def normalize(df):
        for c in df.columns:
            vals = df[c].to_numpy()
            if not len(vals):
                continue
            first = next((v for v in vals if v is not None), None)
            # object columns of Decimals/floats/ints (NULL-able columns
            # materialize as object arrays) → float with NaN for None so
            # numeric comparison applies
            if isinstance(first, decimal.Decimal) or (
                vals.dtype == object and isinstance(first, (float, int))
            ):
                df[c] = [float(v) if v is not None else np.nan for v in vals]
        return df

    g, e = normalize(g), normalize(e)
    if not check_order:
        by = sort_by or list(g.columns)
        g = g.sort_values(by=by, ignore_index=True)
        e = e.sort_values(by=by, ignore_index=True)
    assert len(g) == len(e), f"row count: {len(g)} vs {len(e)}"
    for c in g.columns:
        gv, ev = g[c].to_numpy(), e[c].to_numpy()
        if np.issubdtype(np.asarray(ev).dtype, np.number):
            np.testing.assert_allclose(
                np.asarray(gv, dtype=float), np.asarray(ev, dtype=float),
                rtol=rtol, err_msg=f"column {c}",
            )
        else:
            assert list(gv) == list(ev), f"column {c}: {gv[:10]} vs {ev[:10]}"


@pytest.fixture
def frames_match():
    return assert_frames_match
