"""Share of the traced window in which no op ran on the device (the mean
over the cell's devices): 1 − union of device-op intervals ÷ window."""


def read(t: dict):
    if t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
