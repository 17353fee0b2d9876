"""scheduler + operators: the slowest statement of the window (in a traced
run, of those sent after the profiler was stopped)."""


def read(run):
    t1 = run["traced"]["t1"]
    walls = [s["t1"] - s["t0"] for s in run["completed"]
             if t1 is None or s["t0"] >= t1] \
        or [s["t1"] - s["t0"] for s in run["completed"]]
    return max(walls) if walls else None
