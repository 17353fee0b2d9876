"""scheduler + operators: overflow waves inside a statement's leaf replays
(`n` of `agg_replay_wave`: one occurrence, with no time of its own, each time
a leaf's table proved too small and the leaf was merged again at a bigger
one; `exec/runtime.py`, `_bump_replay_wave` under `finalize_leaf`), all
threads, mean per statement. A leaf's table is sized from its row count, so
the reading is **0.0, a number**, where a statement replayed without a wave:
the regression this guards must not vanish from the line. `None` only where
a statement has no `agg_replay`."""

from benchmark import agg_phases, phase_summaries as ps


def read(run):
    return ps.mean(run, agg_phases.count("n", "agg_replay_wave"))
