"""The engine's phases, spans and counters in a profiler trace of one
cell, with the cost of tracing (a reading by hand, not part of a run).

    python3 -m bench.trace_phases --workload <cell> --seed <n> [--cells 3]

Runs the cell's sweep cells with the profiler off, then on, and prints
one JSON line: the reduction of the trace (``bench/trace_reduce.py``),
the cell's per-layer metrics read from it as a ``--trace 1`` run reads
them, the first traced cell's engine counters, and the cells' wall times
each way.
"""

from __future__ import annotations

import argparse
import json
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--cells", type=int, default=3,
                    help="sweep cells with the profiler off, then on")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    from bench import harness, trace_reduce
    from repro.core import simjax

    print(f"compile cache: {simjax.place_compile_cache()}", file=sys.stderr)
    spec = harness.load_spec(args.workload)
    traffic, config = spec["traffic"], spec["config"]
    plan = harness.lane_plan(args.seed, traffic, config)

    def cells() -> tuple[list[float], list]:
        recs, walls = [], []
        for _ in range(args.cells):
            t0 = time.perf_counter()
            recs.append(harness.sweep_cell(plan, traffic, config))
            walls.append(recs[-1].end - t0)
        return walls, recs

    harness.sweep_cell(plan, traffic, config)          # compiles or loads
    off, _ = cells()
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    trace_reduce.start(trace_dir)
    on, recs = cells()
    jax.profiler.stop_trace()
    programs = harness.engine_programs(harness.build_lanes(plan, traffic,
                                                           config))
    reduced = trace_reduce.reduce(trace_reduce.load(trace_dir, programs))
    shutil.rmtree(trace_dir, ignore_errors=True)
    m = harness.Measurements(lanes=len(plan), setup_s=0.0,
                             window_start=0.0, cells=recs, trace=reduced,
                             traced_cells=len(recs))
    dev = jax.devices()[0]
    line = {"workload": args.workload, "seed": args.seed,
            "device": {"platform": dev.platform, "kind": dev.device_kind},
            "cell_s": {"profiler_off": off, "profiler_on": on,
                       "median_ratio": (statistics.median(on)
                                        / statistics.median(off))},
            "counters": {k: getattr(recs[0], k) for k in harness.COUNTERS},
            "reduced": reduced,
            "metrics": harness.read_metrics(spec["per_layer"], m,
                                            spec["units"])}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
