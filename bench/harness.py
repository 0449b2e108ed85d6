"""One benchmark run of one cell: set-up, measured window, check.

A cell (``BENCHMARK.json`` ``workloads``) names a configuration and a
traffic mix.  Everything particular to them lives in files found by
name: ``bench/workloads/<cell>.json`` (lanes, the limit of the check,
and under ``pins`` the padded batch shape and the generator's
fingerprint that the tests hold the cell to),
``bench/configs/<config>.json`` (sizes) with
``bench/configs/<config>.py`` (``build_lanes``), and one reader per
metric, ``bench/metrics/<metric>.py``.  So a new configuration and its
cell take exactly these files, and no edit of a file that is there:

* one ``configs`` entry and one ``workloads`` entry in ``BENCHMARK.json``;
* ``bench/configs/<config>.json`` and ``bench/configs/<config>.py``;
* ``bench/workloads/<cell>.json``.

A new cell of a configuration that is there takes the ``workloads``
entry and its file alone; a new metric, its ``per_layer`` or
``end_to_end`` entry and ``bench/metrics/<metric>.py``.

A sweep cell is what a user waits for: the cell's B seed-lanes built
(generator, then strict lint), packed, run by the lockstep engine, and
their per-job JCT/CCT back on the host.  The window runs sweep cells
back to back; every one is compared, lane by lane and job by job, with
``bench/reference.py`` once the window has closed.

``--seed`` fixes how the lanes run: the cell's lanes are always the
same B seeds of its configuration (the same work in every run),
the seed draws their order in the batch and a relabelling of the
fabric's ports for each lane.  On a big switch with equal ports that
relabelling leaves every JCT and CCT as it was, so the work does not
change with the seed while the engine's inputs do.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib
import inspect
import json
import math
import random
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
from jax import monitoring

from bench import reference, trace_reduce
from repro.analysis.lint import lint_jobs, strict
from repro.core import simjax
from repro.core.fabric import Fabric, make_topology
from repro.core.metaflow import JobDAG

ROOT = Path(__file__).resolve().parents[1]

_LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"
#: A traced run profiles the window's sweep cells until this much of the
#: window has passed: a TPU trace holds one event per executed op, and
#: collecting and reading a minute of them takes minutes.
TRACE_SECONDS = 10.0
#: ``simjax.LaneResult``'s counters; a sweep cell keeps the most over
#: its lanes (the same for every lane where the engine counts a batch).
COUNTERS = ("wave_iters", "cascade_iters", "batch_steps", "batch_syncs")
#: The one fabric ``bench/reference.py`` routes.
TOPOLOGY = "big_switch"


# ------------------------------------------------------------------ spec
def load_spec(name: str) -> dict:
    """The cell's ``BENCHMARK.json`` entry joined with its traffic file,
    its configuration and the metrics it reports."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    traffic = json.loads(
        (ROOT / "bench" / "workloads" / f"{name}.json").read_text())
    if (traffic["config"], traffic["traffic"]) != (cell["config"],
                                                   cell["traffic"]):
        raise ValueError(f"{name}: traffic file names another cell")

    def mine(metrics):
        return [m["name"] for m in metrics
                if name in m.get("workloads", [name])]

    config = load_config(cell["config"])
    if config["topology"] != TOPOLOGY:
        raise ValueError(f"{name}: the reference routes only a "
                         f"{TOPOLOGY}, not a {config['topology']}")
    return {"name": name, "chips": cell["chips"], "traffic": traffic,
            "config": config,
            "end_to_end": mine(bench["end_to_end"]),
            "per_layer": mine(bench["per_layer"]),
            "units": {m["name"]: m["unit"]
                      for m in bench["end_to_end"] + bench["per_layer"]}}


def load_config(name: str) -> dict:
    return json.loads(
        (ROOT / "bench" / "configs" / f"{name}.json").read_text())


def lane_plan(seed: int, traffic: dict, config: dict
              ) -> list[tuple[int, list[int]]]:
    """``(lane seed, port relabelling)`` of each lane, in batch order.

    The lane seeds are ``first_lane .. first_lane + lanes - 1`` of the
    traffic (``first_lane`` 0 unless the traffic names another)."""
    rng = random.Random(seed)
    first = traffic.get("first_lane", 0)
    order = list(range(first, first + traffic["lanes"]))
    rng.shuffle(order)
    n = config["n_ports"]
    return [(s, rng.sample(range(n), n)) for s in order]


# ----------------------------------------------------------------- lanes
def relabel(jobs: list[JobDAG], perm: list[int]) -> list[JobDAG]:
    """The same jobs with port ``p`` renamed ``perm[p]``."""
    out = []
    for job in jobs:
        new = JobDAG(name=job.name, arrival=job.arrival)
        for t in job.tasks.values():
            new.add_task(t.name, t.load,
                         machine=perm[t.machine] if t.machine >= 0 else -1,
                         deps=t.deps)
        for m in job.metaflows.values():
            new.add_metaflow(m.name, [(perm[f.src], perm[f.dst], f.size)
                                      for f in m.flows], deps=m.deps)
        out.append(new)
    return out


def build_lanes(plan, traffic: dict, config: dict) -> list:
    """Step 1 of a sweep cell: ``(fabric, jobs)`` of every lane, linted
    as ``build_scenario`` lints."""
    mod = importlib.import_module(f"bench.configs.{config['name']}")
    built = mod.build_lanes([s for s, _ in plan], traffic, config)
    out = []
    for (n_ports, jobs), (_, perm) in zip(built, plan):
        fabric = Fabric(topology=make_topology(config["topology"], n_ports))
        jobs = relabel(jobs, perm)
        strict(lint_jobs(jobs, fabric.topology))
        out.append((fabric, jobs))
    return out


def plain_lane(jobs: list[JobDAG], config: dict) -> dict:
    """A lane as the reference reads it: plain numbers and names."""
    def nodes(job):
        out = [{"name": t.name, "deps": list(t.deps), "load": float(t.load)}
               for t in job.tasks.values()]
        out += [{"name": m.name, "deps": list(m.deps),
                 "flows": [(f.src, f.dst, float(f.size)) for f in m.flows]}
                for m in job.metaflows.values()]
        return out

    return {"n_ports": config["n_ports"],
            "port_capacity": config["port_capacity"],
            "jobs": [{"name": j.name, "arrival": float(j.arrival),
                      "nodes": nodes(j)} for j in jobs]}


# ------------------------------------------------------------ sweep cell
@dataclasses.dataclass
class CellRecord:
    """Host-clock spans of one sweep cell, what it returned, and the
    engine's counters (``COUNTERS``; 0 where the cell raised)."""

    build_s: float
    pack_s: float
    engine_s: float
    end: float
    lane_events: list[int]
    results: list | None          # per lane (jct, cct), None if it raised
    wave_iters: int = 0
    cascade_iters: int = 0
    batch_steps: int = 0
    batch_syncs: int = 0

    @property
    def steps(self) -> int:
        return max(self.lane_events, default=0)


def counters(results) -> dict[str, int]:
    """The batch's ``COUNTERS`` from one engine call's ``LaneResult``s:
    the most over its lanes."""
    return {f: max(getattr(r, f) for r in results) for f in COUNTERS}


def sweep_cell(plan, traffic: dict, config: dict) -> CellRecord:
    """One sweep cell through the normal path (the timed work)."""
    with jax.profiler.TraceAnnotation("bench.cell"):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.build"):
            lanes = build_lanes(plan, traffic, config)
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.pack"):
            packed = [simjax.pack_instance(f, j) for f, j in lanes]
        t2 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.engine"):
            try:
                res = simjax.run_fifo_batch(packed)
            except RuntimeError as e:      # deadlock or livelock guard
                print(f"sweep cell failed: {e}", file=sys.stderr)
                res = None
        t3 = time.perf_counter()
    return CellRecord(
        build_s=t1 - t0, pack_s=t2 - t1, engine_s=t3 - t2, end=t3,
        lane_events=[r.events for r in res] if res else [],
        results=[(r.jct, r.cct) for r in res] if res else None,
        **(counters(res) if res else {}))


def engine_programs(lanes) -> list[str]:
    """HLO text of the engine's two programs at the lanes' batch shape,
    as ``simjax.run_fifo_batch`` runs them (its default window); loaded
    from the compile cache once the lanes have run.  Their op metadata
    names each op's scope (``trace_reduce.scope_map``)."""
    window = inspect.signature(simjax.run_fifo_batch).parameters[
        "steps_per_sync"].default
    pk = simjax._pack_batch([simjax.pack_instance(f, j) for f, j in lanes])
    st = simjax._init_state(pk)
    return [simjax._multi_step_jit.lower(pk, st, window).compile().as_text(),
            simjax._settle_jit.lower(pk, st).compile().as_text()]


@dataclasses.dataclass
class Measurements:
    """What the metric readers read."""

    lanes: int                    # per sweep cell
    setup_s: float
    window_start: float
    cells: list[CellRecord]
    trace: dict | None            # trace_reduce.reduce(), traced runs only
    traced_cells: int = 0         # the window's first cells, in the trace


class CompileCounter:
    """Counts lowerings to XLA (each compile, or load from the cache)."""

    def __init__(self) -> None:
        self.n = 0
        monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event: str, duration: float, **kw) -> None:
        if event == _LOWERING:
            self.n += 1


# ------------------------------------------------------------------ check
def check(spec: dict, plan, cells: list[CellRecord]
          ) -> tuple[float, list[int]]:
    """Compare every lane of every sweep cell with the reference.

    Returns the widest |ΔJCT| or |ΔCCT| over all jobs of all lanes, in
    the configuration's time unit, and the failed lanes of each cell: a
    lane fails when its cell raised, its job set differs, or a job
    misses the limit."""
    traffic, config = spec["traffic"], spec["config"]
    built = build_lanes(plan, traffic, config)
    refs = [reference.simulate(plain_lane(jobs, config)) for _, jobs in built]
    limit = traffic["limit_abs_diff"]
    worst, failed = 0.0, []
    for cell in cells:
        if cell.results is None or len(cell.results) != len(refs):
            failed.append(len(plan))
            continue
        bad = 0
        for (jct, cct), ref in zip(cell.results, refs):
            if set(jct) != set(ref.jct) or set(cct) != set(ref.cct):
                bad += 1
                continue
            d = max(max(abs(jct[n] - ref.jct[n]) for n in ref.jct),
                    max(abs(cct[n] - ref.cct[n]) for n in ref.cct))
            if not d <= limit:            # also catches NaN
                bad += 1
            worst = max(worst, d) if math.isfinite(d) else math.inf
        failed.append(bad)
    return worst, failed


# -------------------------------------------------------------------- run
def read_metrics(names: list[str], m: Measurements, units: dict) -> dict:
    out = {}
    for name in names:
        value = importlib.import_module(f"bench.metrics.{name}").read(m)
        if value is not None:
            out[name] = {"value": value, "unit": units[name]}
    return out


def run_cell(spec: dict, seed: int, seconds: float, trace: bool,
             t_start: float) -> dict:
    """Set up, measure for ``seconds``, check; returns the result line.

    ``t_start`` is the process's start on ``time.perf_counter``'s clock:
    set-up runs from there to the window's start."""
    traffic, config = spec["traffic"], spec["config"]
    plan = lane_plan(seed, traffic, config)
    compiles = CompileCounter()
    t0 = time.perf_counter()
    warm = sweep_cell(plan, traffic, config)      # compiles or loads
    print(f"warm-up sweep cell: {time.perf_counter() - t0:.3f} s "
          f"(set-up began {t0 - t_start:.3f} s before it)", file=sys.stderr)

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    gc.collect()
    traces0, compiles0 = simjax.trace_count(), compiles.n
    print(f"trace_count before the window: {traces0}", file=sys.stderr)
    if trace_dir:
        trace_reduce.start(trace_dir)
    window_start = time.perf_counter()
    cells: list[CellRecord] = []
    traced = 0

    def stop_trace() -> int:
        t = time.perf_counter()
        jax.profiler.stop_trace()
        print(f"profiler stopped after {len(cells)} sweep cells, in "
              f"{time.perf_counter() - t:.3f} s", file=sys.stderr)
        return len(cells)

    while time.perf_counter() - window_start < seconds:
        cells.append(sweep_cell(plan, traffic, config))
        if trace_dir and not traced and (
                cells[-1].end - window_start >= TRACE_SECONDS):
            traced = stop_trace()
    if trace_dir and not traced:
        traced = stop_trace()
    traces1, compiles1 = simjax.trace_count(), compiles.n
    print(f"trace_count after the window: {traces1}", file=sys.stderr)
    starts = [window_start] + [c.end for c in cells[:-1]]
    print("sweep cells (s): " + json.dumps(
        [round(c.end - t, 4) for c, t in zip(cells, starts)]),
        file=sys.stderr)

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": (dev.memory_stats() or {}).get(
                  "peak_bytes_in_use")}
    reduced = None
    if trace_dir:
        # Lowered only now, once the window's counts are closed.
        t0 = time.perf_counter()
        programs = engine_programs(build_lanes(plan, traffic, config))
        t1 = time.perf_counter()
        loaded = trace_reduce.load(trace_dir, programs)
        t2 = time.perf_counter()
        reduced = trace_reduce.reduce(loaded)
        shutil.rmtree(trace_dir, ignore_errors=True)
        print(f"trace read: programs {t1 - t0:.3f} s, load {t2 - t1:.3f} s, "
              f"reduce {time.perf_counter() - t2:.3f} s, "
              f"{sum(map(len, loaded['devices'].values()))} device ops",
              file=sys.stderr)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]

    m = Measurements(lanes=len(plan), setup_s=window_start - t_start,
                     window_start=window_start, cells=cells, trace=reduced,
                     traced_cells=traced)
    metrics = read_metrics(spec["per_layer"] if trace
                           else spec["end_to_end"], m, spec["units"])

    worst, failed = check(spec, plan, [warm] + cells)
    checks = {
        "max_abs_diff": {"value": worst,
                           "limit": traffic["limit_abs_diff"]},
        "failed_lanes": {"value": sum(failed), "limit": 0},
        "window_traces": {"value": traces1 - traces0, "limit": 0},
        "window_lowerings": {"value": compiles1 - compiles0, "limit": 0},
    }
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    line = {"correct": correct, "attempted": len(plan) * len(cells),
            "failed": sum(failed[1:]), "metrics": metrics, "device": device}
    if reduced:
        line["breakdown"] = {
            k: reduced[k][:trace_reduce.TOP]
            for k in ("device_ops", "idle_gaps", "device_phases")
            if k in reduced}
    line["checks"] = checks
    return line
