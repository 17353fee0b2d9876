"""Benchmark-side spans and the client wrapper that records them.

The system under test is driven through the program's own
`presto_tpu.client.StatementClient`; this wrapper only puts a clock round
its two HTTP steps (the POST and each nextUri GET) and counts them. With
`annotate=True` every span is also written into the profiler's trace
(`jax.profiler.TraceAnnotation`, name `bench:<span>`), so that device idle
gaps can be charged to what the client was doing.
"""

from __future__ import annotations

import contextlib
import time
from typing import List, Tuple

from presto_tpu.client import StatementClient

for _m in ("_submit", "_advance"):
    if not hasattr(StatementClient, _m):  # the seam this wrapper stands on
        raise ImportError(f"presto_tpu.client.StatementClient has no {_m}")


class SpanLog:
    """(name, statement index, start, end) on time.perf_counter()."""

    def __init__(self, annotate: bool = False):
        self.spans: List[Tuple[str, int, float, float]] = []
        self._annotate = annotate
        if annotate:
            import jax.profiler

            self._ann = jax.profiler.TraceAnnotation

    @contextlib.contextmanager
    def span(self, name: str, stmt: int):
        ann = self._ann(f"bench:{name}") if self._annotate else None
        if ann is not None:
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.spans.append((name, stmt, t0, time.perf_counter()))
            if ann is not None:
                ann.__exit__(None, None, None)


class TimedClient(StatementClient):
    def __init__(self, server, sql, session, log: SpanLog, stmt: int):
        self._log, self._stmt = log, stmt
        self.polls = 0
        super().__init__(server, sql, session)

    def _submit(self):
        with self._log.span("post", self._stmt):
            super()._submit()

    def _advance(self) -> bool:
        if self._next_uri is None:
            return False
        self.polls += 1
        with self._log.span("poll", self._stmt):
            return super()._advance()
