"""1 - device busy time over the traced window's length (the first
sweep cells of the window)."""


def read(m):
    if m.trace is None or m.trace["window_s"] <= 0:
        return None
    return 1.0 - m.trace["busy_s"] / m.trace["window_s"]
