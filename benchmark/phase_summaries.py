"""What the ten engine-phase readers share: the program's own per-statement
summaries (`presto_tpu.obs.trace.summaries()`: phases by thread role, task
and query walls, span count), picked out for the statements of this run.

The statements are those `statement_max_s` selects: in a traced run the ones
sent after the profiler was stopped, all of them where there is none such.
A program without `summaries()` (a commit before the engine had phases), or
a run whose statements left none, gives nothing to read: `None`.
"""

from __future__ import annotations

from typing import Callable, List, Optional


def of_run(run) -> List[dict]:
    from presto_tpu.obs import trace

    read = getattr(trace, "summaries", None)
    if read is None:
        return []
    t1 = run["traced"]["t1"]
    done = [s for s in run["completed"] if t1 is None or s["t0"] >= t1] \
        or run["completed"]
    by_id = {d["queryId"]: d for d in read()}
    return [by_id[s["query_id"]] for s in done if s["query_id"] in by_id]


def phases(summary: dict, role: Optional[str] = None,
           prefix: str = ""):
    """(role, name, aggregate) of a summary's phases; `role` None for every
    thread role, `prefix` the start of the phase's name."""
    for r, by_name in summary["phases"].items():
        if role is not None and r != role:
            continue
        for name, agg in by_name.items():
            if name.startswith(prefix):
                yield r, name, agg


def mean(run, per_statement: Callable[[dict], Optional[float]]):
    """Mean over the run's statements of `per_statement(summary)`, leaving
    out the statements for which it has nothing (`None`)."""
    values = [v for v in map(per_statement, of_run(run)) if v is not None]
    return sum(values) / len(values) if values else None


def total(field: str, role: Optional[str] = None, prefix: str = "",
          names=None) -> Callable[[dict], float]:
    """A `per_statement` for `mean`: the sum of `field` over the phases that
    `role`, `prefix` and `names` (exact names, any of them) select."""
    def per_statement(summary: dict) -> float:
        return sum(agg[field] for _, name, agg in phases(summary, role, prefix)
                   if names is None or name in names)
    return per_statement
