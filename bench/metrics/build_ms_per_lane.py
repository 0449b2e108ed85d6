"""Scenario build (generator, relabelling, strict lint) per lane: the
``bench.build`` host span over the window's lanes."""


def read(m):
    if not m.cells:
        return None
    return 1e3 * sum(c.build_s for c in m.cells) / (m.lanes * len(m.cells))
