"""Device busy time (union of device-op intervals in the profiler
trace) per lockstep step of the traced sweep cells."""


def read(m):
    steps = sum(c.steps for c in m.cells[:m.traced_cells])
    if m.trace is None or not steps:
        return None
    return 1e3 * m.trace["busy_s"] / steps
