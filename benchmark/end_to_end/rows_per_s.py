"""Input rows of the scanned base tables, summed over the statements
completed in the window, over the window's seconds."""


def read(run):
    rows = sum(run["rows_in"][s["query"]] for s in run["completed"])
    return rows / run["window_s"] if rows else None
