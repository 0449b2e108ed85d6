"""Device self time of the MADD walk (live flows, demand prefix sums,
the scan over jobs), the ops under the engine's ``simjax.madd`` scope,
per lockstep step of the traced sweep cells."""

from bench.metrics import phase_ms_per_step


def read(m):
    return phase_ms_per_step(m, "simjax.madd")
