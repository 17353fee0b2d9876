"""scheduler + operators: executions of compiled programs on the device in
the traced window, over the statements completed in it."""


def read(run):
    tr, n = run["device_trace"], run["traced"]["statements"]
    return tr["launches"] / n if tr and n else None
