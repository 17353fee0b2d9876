"""compile plane: programs compiled over set-up (programs.snapshot())."""


def read(run):
    return run["setup_counters"]["compiles"]
