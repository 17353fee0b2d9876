"""scheduler + operators: 95th percentile of the wall of every statement of
the window, in the cells whose window holds over 200 statements. In a traced
run the statements sent while the profiler was on are left out (they run a
fifth slower and would be the tail). It stood among the end-to-end metrics in
the issue; its spread from run to run on a shared host (PERF.md, section 2)
admits no bound, so it is read here."""

import numpy as np


def read(run):
    t1 = run["traced"]["t1"]
    walls = [s["t1"] - s["t0"] for s in run["completed"]
             if t1 is None or s["t0"] >= t1] \
        or [s["t1"] - s["t0"] for s in run["completed"]]
    return float(np.percentile(walls, 95)) if walls else None
