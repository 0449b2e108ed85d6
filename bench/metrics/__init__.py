"""One reader per per-layer or end-to-end metric: ``read(m) -> float | None``."""


def phase_ms_per_step(m, phase: str) -> float | None:
    """Device self time of the engine phase ``phase`` (ops under its
    named scope, ``trace_reduce``'s ``device_phases``) per lockstep step
    of the traced sweep cells; None where no op in the trace carries a
    scope, 0 where others do and this phase none."""
    steps = sum(c.steps for c in m.cells[:m.traced_cells])
    phases = dict((m.trace or {}).get("device_phases", []))
    if not phases or not steps:
        return None
    return 1e3 * phases.get(phase, 0.0) / steps
