"""The plain reference states the same fifo semantics as the numpy core,
and its float32 run (the control) lands outside every cell's limit."""

from __future__ import annotations

import numpy as np
import pytest

from bench import harness, reference
from repro.core import Fabric, make_scheduler, simulate


def _lanes(name: str, seeds):
    spec = harness.load_spec(name)
    n = spec["config"]["n_ports"]
    return spec, harness.build_lanes([(s, list(range(n))) for s in seeds],
                                     spec["traffic"], spec["config"])


def _diff(a, b) -> float:
    assert set(a.jct) == set(b.jct) and set(a.cct) == set(b.cct)
    return max(max(abs(a.jct[k] - b.jct[k]) for k in b.jct),
               max(abs(a.cct[k] - b.cct[k]) for k in b.cct))


@pytest.mark.parametrize("name,seeds", [("fb2010.sweep20", (0, 7)),
                                        ("fb2010.replay32", (0,))])
def test_reference_agrees_with_numpy_core(name, seeds):
    spec, lanes = _lanes(name, seeds)
    for fabric, jobs in lanes:
        plain = harness.plain_lane(jobs, spec["config"])
        ours = reference.simulate(plain)
        core = simulate(jobs, make_scheduler("fifo"),
                        fabric=Fabric(n_ports=fabric.n_ports))
        assert _diff(ours, core) <= 1e-9


@pytest.mark.parametrize("name,seeds", [("fb2010.sweep20", (0, 1)),
                                        ("fb2010.replay32", (0,))])
def test_float32_reference_misses_the_limit(name, seeds):
    spec, lanes = _lanes(name, seeds)
    worst = 0.0
    for _, jobs in lanes:
        plain = harness.plain_lane(jobs, spec["config"])
        worst = max(worst, _diff(reference.simulate(plain, np.float32),
                                 reference.simulate(plain)))
    assert worst > 3 * spec["traffic"]["limit_abs_diff"]


def test_reference_hand_cases():
    # Two flows share port 0's egress; fifo serves job a first: a's flow
    # alone (MADD), b backfills nothing until a drains at t=2, then runs
    # at full rate: b's JCT = 2 + 1 = 3 (arrival 0).
    lane = {"n_ports": 3, "port_capacity": 1.0, "jobs": [
        {"name": "a", "arrival": 0.0,
         "nodes": [{"name": "m", "deps": [], "flows": [(0, 1, 2.0)]}]},
        {"name": "b", "arrival": 0.0,
         "nodes": [{"name": "m", "deps": [], "flows": [(0, 2, 1.0)]},
                   {"name": "c", "deps": ["m"], "load": 0.5}]}]}
    # Tasks precede metaflows within a job: rebuild b's node order.
    lane["jobs"][1]["nodes"].reverse()
    r = reference.simulate(lane)
    assert r.jct == {"a": 2.0, "b": 3.5}
    assert r.cct == {"a": 2.0, "b": 3.0}
    with pytest.raises(RuntimeError, match="deadlock"):
        reference.simulate({"n_ports": 2, "port_capacity": 0.0, "jobs": [
            {"name": "a", "arrival": 0.0,
             "nodes": [{"name": "m", "deps": [], "flows": [(0, 1, 1.0)]}]}]})
