"""kernels: the least time the chip could take for the statements of the
traced window - bytes of the referenced columns of the scanned tables, read
once at the device's peak HBM bandwidth (peaks.json) - over the time the
device was busy in that window. Bound: memory. Nothing to read without a
device trace; never 0."""


def read(run):
    tr, peaks = run["device_trace"], run["peaks"]
    if not tr or not peaks or not tr["busy_s"]:
        return None
    done = [s for s in run["completed"]
            if s["t1"] <= run["traced"]["t1"] and s["t0"] >= run["traced"]["t0"]]
    least = sum(run["bytes_in"][s["query"]] for s in done) \
        / peaks["hbm_bytes_per_s"]
    return 100.0 * least / tr["busy_s"] if least else None
