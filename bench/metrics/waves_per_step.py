"""Backfill wave iterations per lockstep step the device ran: the
engine's ``wave_iters`` counter over its ``batch_steps``, over the
window's sweep cells."""


def read(m):
    run = sum(c.batch_steps for c in m.cells)
    if not run:
        return None
    return sum(c.wave_iters for c in m.cells) / run
