"""The benchmark's data: every cell, configuration and metric is found
by name, each configuration builds the lanes it stood for, and a cell
is added by new files alone."""

from __future__ import annotations

import filecmp
import hashlib
import importlib
import json
import shutil

import numpy as np
import pytest

pytest.importorskip("jax")

from bench import harness, reference  # noqa: E402
from repro.core import simjax  # noqa: E402

BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]


def _identity_plan(spec):
    n = spec["config"]["n_ports"]
    return [(s, list(range(n))) for s in range(spec["traffic"]["lanes"])]


def _padded(packed) -> dict:
    legs = [np.bincount(p.flow_links[p.flow_links < p.n_links].ravel(),
                        minlength=p.n_links).max() for p in packed]
    return {"F": max(p.flow_node.size for p in packed),
            "N": max(p.node_job.size for p in packed),
            "J": max(p.arrival.size for p in packed),
            "E": max(p.edge_parent.size for p in packed),
            "ML": int(max(legs))}


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_load_by_name(name):
    spec = harness.load_spec(name)
    assert spec["traffic"]["chips"] == spec["chips"]
    assert spec["chips"] in (1, 4)
    assert spec["config"]["name"] == spec["traffic"]["config"]
    assert importlib.import_module(
        f"bench.configs.{spec['config']['name']}").build_lanes
    assert {"lanes_per_s", "setup_s"} <= set(spec["end_to_end"])
    assert spec["per_layer"]


@pytest.mark.parametrize("name", METRICS)
def test_metric_readers_load_by_name(name):
    reader = importlib.import_module(f"bench.metrics.{name}")
    m = harness.Measurements(lanes=2, setup_s=1.0, window_start=0.0,
                             cells=[], trace=None)
    value = reader.read(m)
    assert value is None or name == "setup_s"


def test_configs_are_named_by_benchmark():
    for c in BENCH["configs"]:
        cfg = json.loads((harness.ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        cells = [harness.load_spec(w["name"])["traffic"]
                 for w in BENCH["workloads"] if w["config"] == c["name"]]
        keys = set(cfg).union(*cells)
        assert set(c["reduced"]) == set(cfg["reduced"]) <= keys
        assert cfg["source"] == c["source"]


@pytest.mark.parametrize("name", CELLS)
def test_lanes_keep_their_padded_shape(name):
    """The padded batch shape of the cell's lanes (the engine's program
    shape) is the one its file pins."""
    spec = harness.load_spec(name)
    shape = spec["traffic"]["pins"]["shape"]
    lanes = harness.build_lanes(_identity_plan(spec), spec["traffic"],
                                spec["config"])
    packed = [simjax.pack_instance(f, j) for f, j in lanes]
    assert _padded(packed) == shape
    # Every seed's relabelling keeps the shape: the seed moves no work.
    for seed in (3, 2 ** 31 + 7):
        plan = harness.lane_plan(seed, spec["traffic"], spec["config"])
        assert sorted(s for s, _ in plan) == list(
            range(spec["traffic"]["lanes"]))
        lanes = harness.build_lanes(plan, spec["traffic"], spec["config"])
        assert _padded([simjax.pack_instance(f, j)
                        for f, j in lanes]) == shape


@pytest.mark.parametrize("name", CELLS)
def test_generators_match_their_fingerprint(name):
    """The copied generators still build, at the cell's size, the jobs
    they built when they were copied."""
    spec = harness.load_spec(name)
    lanes = harness.build_lanes(_identity_plan(spec), spec["traffic"],
                                spec["config"])
    plain = [harness.plain_lane(jobs, spec["config"]) for _, jobs in lanes]
    digest = hashlib.sha256(
        json.dumps(plain, sort_keys=True).encode()).hexdigest()
    assert digest == spec["traffic"]["pins"]["sha256"]


def test_a_cell_is_added_by_new_files_alone(tmp_path, monkeypatch):
    """A copy of the benchmark with one more cell, ``fb2010.replay32``
    under another name with its own pins: the per-cell tests pass on it,
    and the copy differs from the benchmark only by the cell's file and
    its ``BENCHMARK.json`` entry."""
    root = tmp_path / "checkout"
    shutil.copytree(harness.ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    name, traffic_name = "fb2010.replay32b", "replay32b"
    cell = next(w for w in BENCH["workloads"]
                if w["name"] == "fb2010.replay32")
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append(dict(cell, name=name, traffic=traffic_name))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    traffic = json.loads(
        (root / "bench" / "workloads" / "fb2010.replay32.json").read_text())
    traffic["traffic"] = traffic_name
    (root / "bench" / "workloads" / f"{name}.json").write_text(
        json.dumps(traffic))

    def unchanged(a, b):
        cmp = filecmp.dircmp(a, b, ignore=["__pycache__"])
        assert not cmp.diff_files and not cmp.left_only
        assert set(cmp.right_only) <= {f"{name}.json"}
        for sub in cmp.common_dirs:
            unchanged(a / sub, b / sub)

    unchanged(harness.ROOT / "bench", root / "bench")
    assert {k: v for k, v in bench.items() if k != "workloads"} == {
        k: v for k, v in BENCH.items() if k != "workloads"}
    assert bench["workloads"][:-1] == BENCH["workloads"]

    monkeypatch.setattr(harness, "ROOT", root)
    test_cell_files_load_by_name(name)
    test_lanes_keep_their_padded_shape(name)
    test_generators_match_their_fingerprint(name)
    assert harness.load_spec(name)["per_layer"] == harness.load_spec(
        "fb2010.replay32")["per_layer"]


def test_only_a_big_switch_is_taken(monkeypatch):
    real = harness.load_config
    monkeypatch.setattr(harness, "load_config", lambda name: dict(
        real(name), topology="fat_tree"))
    with pytest.raises(ValueError, match="big_switch"):
        harness.load_spec("fb2010.sweep20")


def test_relabelling_moves_no_result():
    spec = harness.load_spec("fb2010.sweep20")
    cfg = spec["config"]
    (_, jobs), = harness.build_lanes([(1, list(range(cfg["n_ports"])))],
                                     spec["traffic"], cfg)
    perm = harness.lane_plan(99, spec["traffic"], cfg)[0][1]
    a = reference.simulate(harness.plain_lane(jobs, cfg))
    b = reference.simulate(harness.plain_lane(harness.relabel(jobs, perm),
                                              cfg))
    assert a == b


def test_another_lane_set_is_disjoint():
    spec = harness.load_spec("fb2010.sweep20")
    traffic, cfg = spec["traffic"], spec["config"]
    b = traffic["lanes"]
    first = {s for s, _ in harness.lane_plan(5, traffic, cfg)}
    other = {s for s, _ in harness.lane_plan(
        5, dict(traffic, first_lane=b), cfg)}
    assert first == set(range(b)) and other == set(range(b, 2 * b))


def test_fb2010_lanes_keep_the_trace_rate_and_racks():
    """Arrivals are Poisson at 526 coflows an hour, in port-time units
    (one MB per unit at 125 MB/s), and every coflow sits on distinct
    racks of the 150."""
    spec = harness.load_spec("fb2010.sweep20")
    cfg = spec["config"]
    lanes = harness.build_lanes([(s, list(range(cfg["n_ports"])))
                                 for s in range(200)],
                                dict(spec["traffic"], lanes=200), cfg)
    gaps = [b.arrival - a.arrival for _, jobs in lanes
            for a, b in zip(jobs, jobs[1:])]
    want = 3600 / 526 * 125
    assert abs(np.mean(gaps) / want - 1) < 0.05
    for _, jobs in lanes:
        assert len(jobs) == spec["traffic"]["coflows"]
        for job in jobs:
            src = {f.src for m in job.metaflows.values() for f in m.flows}
            dst = {f.dst for m in job.metaflows.values() for f in m.flows}
            assert not src & dst and max(src | dst) < cfg["n_ports"]
