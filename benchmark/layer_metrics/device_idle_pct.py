"""device: share of the traced window in which no operation ran."""


def read(run):
    tr = run["device_trace"]
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"]) if tr else None
