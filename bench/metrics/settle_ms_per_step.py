"""Device self time of the settle cascade (all of ``_settle``: tasks and
metaflows readied as their dependencies finish), the ops under the
engine's ``simjax.settle`` scope, per lockstep step of the traced sweep
cells."""

from bench.metrics import phase_ms_per_step


def read(m):
    return phase_ms_per_step(m, "simjax.settle")
