"""scheduler + operators: how much of the task threads' time no phase
names - 100 x (sum of the statement's `task` spans' walls - sum of
`self_s` of the `task` role's phases, waits included) over that sum of
walls, mean per statement."""

from benchmark import phase_summaries as ps


def read(run):
    named = ps.total("self_s", role="task")

    def per_statement(summary):
        wall = summary["task_wall_s"]
        return 100.0 * (wall - named(summary)) / wall if wall else None

    return ps.mean(run, per_statement)
