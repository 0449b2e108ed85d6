"""Device self time of the backfill waves (priority keys, the wave
loop), the ops under the engine's ``simjax.backfill`` scope, per
lockstep step of the traced sweep cells."""

from bench.metrics import phase_ms_per_step


def read(m):
    return phase_ms_per_step(m, "simjax.backfill")
