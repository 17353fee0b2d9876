"""compile plane: seconds spent tracing and compiling (or loading from the
persistent cache) the programs that set-up minted."""


def read(run):
    return run["setup_counters"]["trace_wall_s"]
