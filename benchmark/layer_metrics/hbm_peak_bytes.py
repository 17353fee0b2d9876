"""device: peak bytes in use on the chip, read after the window."""


def read(run):
    peak = run["memory_stats"].get("peak_bytes_in_use")
    return int(peak) if peak else None
