"""Host pack (``simjax.pack_instance``) per lane: the ``bench.pack``
host span over the window's lanes."""


def read(m):
    if not m.cells:
        return None
    return 1e3 * sum(c.pack_s for c in m.cells) / (m.lanes * len(m.cells))
