"""Settle cascade iterations (the first settle's too) per lockstep step
the device ran: the engine's ``cascade_iters`` counter over its
``batch_steps``, over the window's sweep cells."""


def read(m):
    run = sum(c.batch_steps for c in m.cells)
    if not run:
        return None
    return sum(c.cascade_iters for c in m.cells) / run
