"""compile plane: programs compiled inside the measured window; 0 is sound."""


def read(run):
    return run["window_counters"]["compiles"]
