"""Share of lane-steps that did work: all lanes' event counts over
lanes x lockstep steps.  The rest are finished lanes riding along."""


def read(m):
    steps = sum(c.steps for c in m.cells)
    if not steps:
        return None
    return sum(sum(c.lane_events) for c in m.cells) / (m.lanes * steps)
