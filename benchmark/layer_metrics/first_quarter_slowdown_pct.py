"""scheduler + operators: how much slower the first quarter of the window's
statements ran than the last quarter, as a share of the last (in a traced
run, of the statements sent after the profiler was stopped). Statements get
faster through every window for a cause not yet named (PERF.md, section 2);
a change that only moves that warm-up moves this number with `statement_s`.
Nothing to read under eight statements."""


def read(run):
    t1 = run["traced"]["t1"]
    walls = [s["t1"] - s["t0"] for s in run["completed"]
             if t1 is None or s["t0"] >= t1]
    q = len(walls) // 4
    if q < 2:
        return None
    first, last = sum(walls[:q]) / q, sum(walls[-q:]) / q
    return 100.0 * (first / last - 1.0)
