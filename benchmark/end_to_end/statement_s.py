"""The whole measured window over the statements completed in it."""


def read(run):
    n = len(run["completed"])
    return run["window_s"] / n if n else None
