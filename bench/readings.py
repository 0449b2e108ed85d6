"""The two readings a cell's limit is set from (not part of a run).

    python3 bench/readings.py --workload <cell> --first-seed <n> --seeds 12 \
        --control-seeds 3 --seconds 3

* program: the widest |ΔJCT/CCT| of sound runs, one short run per seed
  through ``harness.run_cell`` (the timed path at the cell's size);
* control: the same runs with the reference itself, in float32, put in
  the engine's place (``control_engine``): the step a later change could
  be tempted by, since the engine holds every time in float64.

Each run's compared numbers print as one JSON line; a run holds the chip
for all seeds, so the cell compiles once.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


@contextlib.contextmanager
def control_engine(harness, dtype=np.float32):
    """Within the block, sweep cells are answered by the reference in
    ``dtype`` instead of the engine."""
    from bench import reference
    from repro.core import simjax

    built: list = []
    real_build = harness.build_lanes

    def build(plan, traffic, config):
        lanes = real_build(plan, traffic, config)
        built[:] = [harness.plain_lane(jobs, config) for _, jobs in lanes]
        return lanes

    def run(packed, **kw):
        out = []
        for lane in built:
            r = reference.simulate(lane, dtype=dtype)
            out.append(simjax.LaneResult(jct=r.jct, cct=r.cct, makespan=0.0,
                                         events=r.events))
        return out

    real_run = simjax.run_fifo_batch
    harness.build_lanes, simjax.run_fifo_batch = build, run
    try:
        yield
    finally:
        harness.build_lanes, simjax.run_fifo_batch = real_build, real_run


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--first-lane", type=int, default=0,
                    help="read another lane set: seeds from this one on")
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
    from bench import harness
    from repro.core import simjax

    print(f"compile cache: {simjax.place_compile_cache()}", file=sys.stderr)

    spec = harness.load_spec(args.workload)
    spec["traffic"] = dict(spec["traffic"], first_lane=args.first_lane)
    seeds = range(args.first_seed, args.first_seed + args.seeds)
    for kind, n in (("program", args.seeds), ("control", args.control_seeds)):
        for seed in list(seeds)[:n]:
            ctx = (control_engine(harness) if kind == "control"
                   else contextlib.nullcontext())
            with ctx:
                line = harness.run_cell(spec, seed, args.seconds, False,
                                        time.perf_counter())
            print(json.dumps({"kind": kind, "seed": seed,
                              "first_lane": args.first_lane,
                              "correct": line["correct"],
                              "attempted": line["attempted"],
                              "device": line["device"],
                              "checks": line["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
