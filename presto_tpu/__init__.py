"""presto_tpu — a TPU-native distributed SQL query engine.

A from-scratch re-design of the capability surface of Presto (reference:
oerling/presto, the "Aria" fork of prestodb 0.227) for TPU hardware:

- SQL frontend (lexer/parser/analyzer)             ~ presto-parser, sql/analyzer
- Logical planner + optimizer + fragmenter         ~ sql/planner
- Columnar execution on fixed-shape device batches ~ operator/* over Page/Block
- XLA-jitted fused pipelines (scan-filter-project-agg) ~ presto-bytecode codegen
- Distributed exchanges via jax.sharding + all_to_all  ~ execution/buffer + ExchangeClient
- TPC-H connector + parquet storage                ~ presto-tpch, presto-orc/hive

Architecture stance (NOT a port): Presto compensates for the JVM with runtime
bytecode generation and flat long[] hash tables; we compensate for XLA's
static-shape world with fixed-capacity column batches, validity + live-row
masks instead of selection vectors, sort-based grouping instead of
pointer-chasing hash tables, and host-precomputed dictionary lookup tables
instead of on-device string processing.
"""

import os as _os

import jax

# A SQL engine needs 64-bit integers (BIGINT, DECIMAL-as-scaled-int64) and
# float64 (DOUBLE). TPU emulates both; hot money arithmetic uses int64.
jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache: the engine's per-plan-node programs
# include multi-operand int64 sorts whose TPU compiles run tens of seconds
# to minutes each; caching them on disk cuts warm-up across processes
# (reference analog: Presto's generated-class caches are per-JVM; XLA's
# serialized executables survive restarts). One place decides where it
# lives, and the outside can set it: JAX_COMPILATION_CACHE_DIR.


def compile_cache_dir(environ) -> "str | None":
    """Where this package points JAX's persistent compilation cache, as a
    pure function of the environment: None — leave alone — where
    ``JAX_COMPILATION_CACHE_DIR`` is set (jax has read it itself), else
    ``<checkout>/.jax_cache``. The path is part of the cache's key, so
    nothing that varies (machine, pid, time, tmp name) goes into it."""
    if environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    return _os.path.join(
        _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
        ".jax_cache")


_cache_dir = compile_cache_dir(_os.environ)
if _cache_dir is not None:
    jax.config.update("jax_compilation_cache_dir", _cache_dir)
# persist every program: nothing else survives a process, and on the TPU
# even the small ones cost about a second each to compile
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

# XLA:CPU executable (de)serialization SEGFAULTS on this host/jaxlib
# (reproduced three times: twice in put_executable_and_time — once
# even under a process-wide lock, ruling out a pure thread race —
# and once in the deserialize path; always on the big multi-operand
# sort programs the engine compiles). The persistent cache therefore
# BYPASSES the cpu backend: callers see a plain miss and compile
# in-process (the per-process jit caches still dedupe), while TPU —
# where compiles of tens of seconds make the cache worth having — keeps it,
# serialized through one lock. One installation (jax 0.9.0): the three
# jax._src names below exist there, and a jax that moves them fails this
# import loudly instead of running unpatched.
import threading as _threading  # noqa: E402

from jax._src import compilation_cache as _cc  # noqa: E402

_cc_lock = _threading.Lock()
_orig_cc_get = _cc.get_executable_and_time
_orig_cc_put = _cc.put_executable_and_time


def _cc_platform(a, k):
    for x in list(a) + list(k.values()):
        p = getattr(x, "platform", None)
        if isinstance(p, str):
            return p
    return None


def _guarded_cc_get(*a, **k):
    if _cc_platform(a, k) == "cpu":
        return None, None  # plain miss: compile in-process
    with _cc_lock:
        return _orig_cc_get(*a, **k)


def _guarded_cc_put(*a, **k):
    if _cc_platform(a, k) == "cpu":
        return None
    with _cc_lock:
        return _orig_cc_put(*a, **k)


_cc.get_executable_and_time = _guarded_cc_get
_cc.put_executable_and_time = _guarded_cc_put

# CONCURRENT XLA:CPU compiles from multiple threads also
# segfault on this host (reproduced in backend_compile_and_load
# once the cache paths were bypassed; the same programs compile
# fine serially — e.g. every warm-cache suite run). Serialize
# compilation through the same lock: concurrent compiles only
# ever happen in the in-process multi-worker cluster, where the
# per-process jit caches already dedupe most of them.
from jax._src import compiler as _compiler  # noqa: E402

_orig_bcl = _compiler.backend_compile_and_load


def _locked_bcl(*a, **k):
    with _cc_lock:
        return _orig_bcl(*a, **k)


_compiler.backend_compile_and_load = _locked_bcl

__version__ = "0.1.0"

from presto_tpu.types import (  # noqa: E402
    BOOLEAN,
    BIGINT,
    INTEGER,
    DOUBLE,
    REAL,
    DATE,
    VARCHAR,
    DecimalType,
    Type,
)
from presto_tpu.batch import Batch, Column  # noqa: E402

__all__ = [
    "BOOLEAN",
    "BIGINT",
    "INTEGER",
    "DOUBLE",
    "REAL",
    "DATE",
    "VARCHAR",
    "DecimalType",
    "Type",
    "Batch",
    "Column",
]
