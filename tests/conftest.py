"""Test configuration: force an 8-device virtual CPU platform so every test
exercises the same mesh/sharding code paths the driver validates multi-chip
(xla_force_host_platform_device_count), without TPU compile latency."""

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

import numpy as np  # noqa: E402
import pandas as pd  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running sweeps excluded from the tier-1 'not slow' run")


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
