"""kernels: halving rounds a sort-engine probe runs inside one bucket of its
build's directory (`items` of `join_search` over its `n`: one occurrence a
build, `items` the table's `search_steps`; `ops/join.py`, read beside the
build's live count in `exec/runtime.py`, `_observe_build_table`), mean per
statement. A binary search of the whole build takes log2 of its capacity."""

from benchmark import phase_summaries as ps


def _per_statement(summary):
    found = [agg for _, name, agg in ps.phases(summary) if name == "join_search"]
    builds = sum(agg["n"] for agg in found)
    # a build whose buckets are all empty reports no `items` at all
    return sum(agg.get("items", 0) for agg in found) / builds if builds else None


def read(run):
    return ps.mean(run, _per_statement)
