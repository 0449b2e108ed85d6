"""Device self time of the event horizon (next event time, fluid
advance), the ops under the engine's ``simjax.horizon`` scope, per
lockstep step of the traced sweep cells."""

from bench.metrics import phase_ms_per_step


def read(m):
    return phase_ms_per_step(m, "simjax.horizon")
