"""Bring-up check: the lockstep engine's main path on one TPU chip.

Everything runs in this one process, which holds the chip:

* phase "registered": every registered scenario at full size, fifo,
  seeds 0-19, plus one msa cell of ``mixed``, through
  ``repro.experiments.run_cells_batched``.  The fifo lanes run on the
  chip; the msa cell runs on the numpy core in a spawned worker while
  this process holds the chip.  Seeds 0 and 19 of every scenario are
  compared job by job with ``run_cell``, the numpy oracle.
* phase "cluster": the 48-port mixed cluster at 100 jobs
  (``benchmarks/perf_sim_core.scale_mixed``), seeds 0-7 packed as one
  batch and run twice, cold then warm.  Lanes 0 and 7 are compared with
  the numpy core.

The lines before the last report, per phase, the first-call and warm
walls, lockstep events, padded shapes, peak device memory, the largest
|ΔJCT/CCT| and compile-cache hits: information, not a benchmark.  The
last line is one JSON object naming the device.  The script exits
non-zero and prints no such line without a TPU, outside a full
checkout, or when a phase fails or misses ``TOL``.

    python chip_smoke.py
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
from jax import monitoring  # noqa: E402

#: Oracle tolerance on every job's JCT and CCT, seconds (the same bound
#: ``tests/test_simjax.py`` holds the engine to on the CPU).
TOL = 1e-6
SEEDS = range(20)
ORACLE_SEEDS = (0, 19)
#: A TPU v5e takes about 77 ms per lockstep step of the 200-job, 8-lane
#: batch (6,831 steps), so two passes at 200 jobs leave no room in a
#: 20-minute run; 100 jobs (3,463 steps) keep both passes.
CLUSTER_JOBS = 100
CLUSTER_LANES = 8


class CacheEvents:
    """Counts JAX persistent-compilation-cache hits and misses."""

    def __init__(self) -> None:
        self.hits = 0
        self.misses = 0
        monitoring.register_event_listener(self)

    def __call__(self, event: str, **kwargs) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def _diff(jct: dict, cct: dict, ref_jct: dict, ref_cct: dict,
          what: str) -> float:
    """Largest |ΔJCT/CCT| of one lane against its oracle; raises on a
    different job set or a difference above ``TOL``."""
    if set(jct) != set(ref_jct) or set(cct) != set(ref_cct):
        raise AssertionError(f"{what}: job set differs from the oracle")
    diff = max(max(abs(jct[n] - ref_jct[n]) for n in ref_jct),
               max(abs(cct[n] - ref_cct[n]) for n in ref_cct))
    if not diff <= TOL:
        raise AssertionError(f"{what}: |ΔJCT/CCT| {diff} > {TOL}")
    return diff


def _padded(lanes) -> dict:
    """Batch maxima of one lane set: the padded F/N/J/L of its program."""
    return {"F": max(p.flow_node.size for p in lanes),
            "N": max(p.node_job.size for p in lanes),
            "J": max(p.arrival.size for p in lanes),
            "L": max(p.flow_links.shape[1] for p in lanes)}


def phase_registered(seeds=SEEDS, oracle_seeds=ORACLE_SEEDS,
                     quick: bool = False) -> dict:
    from repro.appdag.mixer import SCENARIOS, build_scenario
    from repro.core.simjax import pack_instance
    from repro.experiments import (Cell, resolve_topology, run_cell,
                                   run_cells_batched)

    names = sorted(SCENARIOS)
    fifo = [Cell(s, "fifo", resolve_topology(s, None), seed)
            for s in names for seed in seeds]
    msa = Cell("mixed", "msa", resolve_topology("mixed", None), 0)
    t0 = time.perf_counter()
    recs = run_cells_batched(fifo + [msa], quick=quick, workers=2)
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    warm = run_cells_batched(fifo, quick=quick, workers=2)
    warm_s = time.perf_counter() - t0

    rec_msa = recs[-1]
    if "engine" in rec_msa or rec_msa["policy"] != "msa" \
            or not rec_msa["result"]["jct"]:
        raise AssertionError(f"msa cell did not run on the numpy core: "
                             f"{rec_msa.get('engine')}")
    if any(r.get("engine") != "simjax" for r in recs[:-1] + warm):
        raise AssertionError("a fifo cell did not run on the engine")

    diff = 0.0
    for ix, cell in enumerate(fifo):
        if cell.seed not in oracle_seeds:
            continue
        ref = run_cell(cell, quick=quick)["result"]
        for run in (recs, warm):
            got = run[ix]["result"]
            diff = max(diff, _diff(got["jct"], got["cct"], ref["jct"],
                                   ref["cct"], f"{cell.scenario}/seed"
                                   f"{cell.seed}"))
    shapes = {}
    for s in names:
        lanes = [pack_instance(*build_scenario(s, seed=seed, quick=quick,
                                               lint=False))
                 for seed in seeds]
        events = max(r["result"]["events"] for r in recs[:-1]
                     if r["scenario"] == s)
        shapes[s] = {**_padded(lanes), "events": events}
    return {"first_s": first_s, "warm_s": warm_s, "cells": len(recs),
            "max_abs_diff": diff, "batches": shapes}


def phase_cluster(n_jobs: int = CLUSTER_JOBS,
                  n_lanes: int = CLUSTER_LANES) -> dict:
    from benchmarks.perf_sim_core import scale_mixed
    from repro.core import Fabric, make_scheduler, simulate
    from repro.core.simjax import pack_instance, run_fifo_batch

    lanes = []
    for seed in range(n_lanes):
        n_ports, jobs = scale_mixed(n_jobs, seed=seed)
        lanes.append(pack_instance(Fabric(n_ports=n_ports), jobs))
    t0 = time.perf_counter()
    first = run_fifo_batch(lanes)
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    warm = run_fifo_batch(lanes)
    warm_s = time.perf_counter() - t0

    diff = 0.0
    for b in (0, n_lanes - 1):
        n_ports, jobs = scale_mixed(n_jobs, seed=b)
        ref = simulate(jobs, make_scheduler("fifo"), n_ports=n_ports)
        for run in (first, warm):
            diff = max(diff, _diff(run[b].jct, run[b].cct, ref.jct,
                                   ref.cct, f"cluster/seed{b}"))
    return {"first_s": first_s, "warm_s": warm_s, "lanes": n_lanes,
            "jobs": n_jobs, "max_abs_diff": diff,
            "events": max(r.events for r in first), **_padded(lanes)}


def main() -> int:
    from repro.core.simjax import place_compile_cache

    cache_dir = place_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "nothing was run", file=sys.stderr)
        return 1
    cache = CacheEvents()
    for name, phase in (("registered", phase_registered),
                        ("cluster", phase_cluster)):
        hits, misses = cache.hits, cache.misses
        info = phase()
        info["cache_hits"] = cache.hits - hits
        info["cache_misses"] = cache.misses - misses
        info["peak_bytes_in_use"] = (dev.memory_stats() or {}).get(
            "peak_bytes_in_use")
        print(json.dumps({"phase": name, "cache_dir": cache_dir, **info}),
              flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
