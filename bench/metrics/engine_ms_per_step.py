"""The engine call (``simjax.run_fifo_batch``: batch pack, settle,
16-step windows, host syncs, readback) per lockstep step: the
``bench.engine`` host span over the lockstep steps (largest lane event
count) of the window's sweep cells."""


def read(m):
    steps = sum(c.steps for c in m.cells)
    if not steps:
        return None
    return 1e3 * sum(c.engine_s for c in m.cells) / steps
