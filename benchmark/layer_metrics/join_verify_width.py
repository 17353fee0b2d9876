"""kernels: build lanes a sort-engine unique probe may verify past its bucket
search (`items` of `join_verify` over its `n`: one occurrence a build, `items`
the table's `verify_width`, the widest run of live lanes sharing bucket and
32-bit fingerprint; `ops/join.py`, read beside the build's live count in
`exec/runtime.py`, `_observe_build_table`), mean per statement. 1 for a build
of distinct keys; nothing from a program without the phase (PR 36's parent)."""

from benchmark import phase_summaries as ps


def _per_statement(summary):
    found = [agg for _, name, agg in ps.phases(summary) if name == "join_verify"]
    builds = sum(agg["n"] for agg in found)
    # a build with no live lane reports no `items` at all
    return sum(agg.get("items", 0) for agg in found) / builds if builds else None


def read(run):
    return ps.mean(run, _per_statement)
