"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's busy intervals) / window, mean over chips."""


def read(run):
    s = run.summary
    if s is None or s.window_s <= 0:
        return None
    return 100.0 * (1.0 - s.busy_s / s.window_s)
