"""Device idle time under the engine's host syncs, per sync: the idle
gaps whose innermost host span is ``simjax.sync`` (the done-flag read)
or ``simjax.dispatch`` (the next window's enqueue), over the
``batch_syncs`` of the traced sweep cells."""


def read(m):
    syncs = sum(c.batch_syncs for c in m.cells[:m.traced_cells])
    if m.trace is None or "program_spans" not in m.trace or not syncs:
        return None
    idle = dict(m.trace["idle_gaps"])
    return 1e3 * (idle.get("simjax.sync", 0.0)
                  + idle.get("simjax.dispatch", 0.0)) / syncs
