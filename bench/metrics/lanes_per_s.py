"""Seed-lanes finished per second: every lane of the window's finished
sweep cells over the time from the window's start to the end of the
last one (host clock)."""


def read(m):
    if not m.cells:
        return None
    return m.lanes * len(m.cells) / (m.cells[-1].end - m.window_start)
