"""Share of the lockstep steps the device ran that some lane needed:
the most events of a lane over the engine's ``batch_steps`` (whole
windows of steps between host syncs), over the window's sweep cells."""


def read(m):
    run = sum(c.batch_steps for c in m.cells)
    if not run:
        return None
    return sum(c.steps for c in m.cells) / run
