"""The engine's phases, spans and counters in a profiler trace of one
cell (a reading by hand, not part of a run).

    python3 -m bench.trace_phases --workload <cell> --seed <n> [--cells 3]

``repro.core.simjax`` names its device ops with the scopes
``simjax.settle``, ``simjax.madd``, ``simjax.backfill`` and
``simjax.horizon``, wraps its host work in ``simjax.*`` spans nested in
the benchmark's ``bench.engine``, and returns loop counters with every
lane.  ``bench/trace_reduce.py`` reads none of them: it loads the
``bench.*`` spans only, and splits idle time over spans that do not
nest.  This module extends it:

* ``load``: what ``trace_reduce.load`` loads, plus the ``simjax.*``
  spans and each device op's scope path, from the op metadata of the
  engine's compiled programs (a TPU op's event carries none);
* ``reduce``: what ``trace_reduce.reduce`` returns, with idle time split
  by the innermost span, plus ``device_phases`` (each op's self time,
  its duration less the part ops nested in it cover, summed by phase,
  ``unscoped`` for the rest; the phases add up to the busy time) and
  ``program_spans`` (the ``simjax.*`` spans' time in the window);
* ``readings``: per-step and per-sync quantities from those and the
  engine's counters;
* ``main``: runs the cell's sweep cells with the profiler off, then on,
  and prints one JSON line: the reduction, the readings, and the
  cells' wall times each way (the cost of tracing).
"""

from __future__ import annotations

import argparse
import bisect
import contextlib
import json
import math
import re
import shutil
import statistics
import sys
import tempfile
import time
from collections.abc import Sequence
from pathlib import Path

from bench import trace_reduce

ROOT = Path(__file__).resolve().parents[1]
PROGRAM_PREFIX = "simjax."
UNSCOPED = "unscoped"
CELL = trace_reduce.SPAN_PREFIX + "cell"
#: The device planes' line with one event per run of an XLA program.
MODULE_LINE = "XLA Modules"
_INSTR = re.compile(r'^\s*(?:ROOT )?(%[^ ]+) = .*?op_name="([^"]*)"', re.M)


def phase_of(path: str) -> str:
    """The engine phase an op-name path lies in (its first ``simjax.*``
    component), or ``unscoped``."""
    for part in path.split("/"):
        if part.startswith(PROGRAM_PREFIX):
            return part
    return UNSCOPED


def scope_map(programs: Sequence[str]) -> dict[tuple[str, str], str]:
    """``(module, op) -> op-name path`` from compiled programs' HLO text
    (``compiled.as_text()``), whose op metadata holds the scopes."""
    out = {}
    for text in programs:
        module = text.split(None, 2)[1].rstrip(",")     # "HloModule <name>,"
        out.update({(module, op): path for op, path in _INSTR.findall(text)})
    return out


def load(trace_dir: str | Path, programs: Sequence[str] = ()) -> dict:
    """``{"devices": {plane: [[op, start_ns, dur_ns, scope_path], ...]},
    "spans": [[name, start_ns, dur_ns], ...]}`` from the newest trace:
    the ``bench.*`` and ``simjax.*`` host spans.  A TPU op's event holds
    no op metadata, so its scope path is looked up in ``programs``
    (compiled HLO text) for the program run it lies in, on the plane's
    ``XLA Modules`` line; empty where none matches."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    data = ProfileData.from_file(str(files[-1]))
    paths = scope_map(programs)
    prefixes = (trace_reduce.SPAN_PREFIX, PROGRAM_PREFIX)
    devices, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            runs = sorted((e.start_ns, e.end_ns, e.name.split("(", 1)[0])
                          for line in plane.lines if line.name == MODULE_LINE
                          for e in line.events)
            starts = [r[0] for r in runs]
            ops = []
            for line in plane.lines:
                if line.name != trace_reduce.OP_LINE:
                    continue
                for e in line.events:
                    op = trace_reduce.op_name(e.name)
                    i = bisect.bisect_right(starts, e.start_ns) - 1
                    inside = i >= 0 and e.start_ns < runs[i][1]
                    module = runs[i][2] if inside else ""
                    ops.append([op, e.start_ns, e.duration_ns,
                                paths.get((module, op), "")])
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            spans += [[e.name, e.start_ns, e.duration_ns]
                      for line in plane.lines for e in line.events
                      if e.name.startswith(prefixes)]
    return {"devices": devices, "spans": spans}


def innermost(spans: list[tuple[float, float, str]]
              ) -> list[tuple[float, float, str]]:
    """Nested ``(start, end, name)`` host spans as pieces that follow one
    another, each named after the innermost span over it."""
    out: list[tuple[float, float, str]] = []
    stack: list[tuple[float, str]] = []     # (end, name), outermost first
    t = -math.inf

    def upto(x: float) -> None:
        nonlocal t
        if stack and x > t:
            out.append((t, x, stack[-1][1]))
        t = max(t, x)

    for a, b, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        while stack and stack[-1][0] <= a:
            upto(stack[-1][0])
            stack.pop()
        upto(a)
        stack.append((min(b, stack[-1][0]) if stack else b, name))
    while stack:
        upto(stack[-1][0])
        stack.pop()
    return out


def self_times(ivs: list[tuple[float, float, str]]) -> dict[str, float]:
    """Each interval's length less the part the intervals nested in it
    cover, summed by label; the sums add up to the intervals' union."""
    out: dict[str, float] = {}
    stack: list[list] = []                  # [end, label, own time]

    def close() -> None:
        _, label, own = stack.pop()
        out[label] = out.get(label, 0.0) + own

    for a, b, label in sorted(ivs, key=lambda s: (s[0], -s[1])):
        while stack and stack[-1][0] <= a:
            close()
        if stack:
            stack[-1][2] -= min(b, stack[-1][0]) - a
        stack.append([b, label, b - a])
    while stack:
        close()
    return out


def reduce(trace: dict) -> dict:
    """``trace_reduce.reduce`` of the trace with each idle gap given to
    the innermost host span over it, plus ``device_phases`` (where an op
    carries a scope) and ``program_spans`` (where the trace has
    ``simjax.*`` spans)."""
    cells = [s for s in trace["spans"] if s[0] == CELL]
    pieces = innermost([(s, s + d, n) for n, s, d in trace["spans"]
                        if n != CELL])
    out = trace_reduce.reduce({
        "devices": {k: [op[:3] for op in ops]
                    for k, ops in trace["devices"].items()},
        "spans": cells + [[n, a, b - a] for a, b, n in pieces]})
    w0 = min(s for _, s, _ in cells)
    w1 = max(s + d for _, s, d in cells)
    phase_ns: dict[str, float] = {}
    scoped = False
    for ops in trace["devices"].values():
        ivs = []
        for _, s, d, *path in ops:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                phase = phase_of(path[0]) if path else UNSCOPED
                scoped = scoped or phase != UNSCOPED
                ivs.append((a, b, phase))
        for k, v in self_times(ivs).items():
            phase_ns[k] = phase_ns.get(k, 0.0) + v
    n_dev = len(trace["devices"])
    if scoped:
        out["device_phases"] = [
            [k, v / n_dev / 1e9]
            for k, v in sorted(phase_ns.items(), key=lambda kv: -kv[1])]
    program: dict[str, float] = {}
    for n, s, d in trace["spans"]:
        if n.startswith(PROGRAM_PREFIX):
            program[n] = program.get(n, 0.0) + max(
                0.0, min(s + d, w1) - max(s, w0))
    if program:
        out["program_spans"] = {k: v / 1e9 for k, v in sorted(program.items())}
    return out


def counters(results) -> dict | None:
    """The batch's engine counters from one call's ``LaneResult``s (loop
    iterations: the most of any lane); None where the engine has none."""
    fields = ("wave_iters", "cascade_iters", "batch_steps", "batch_syncs")
    if not results or not all(hasattr(results[0], f) for f in fields):
        return None
    out = {f: max(getattr(r, f) for r in results) for f in fields}
    out["steps_needed"] = max(r.events for r in results)
    return out


def readings(reduced: dict, batches: list[dict | None], lanes: int) -> dict:
    """Per-step and per-sync quantities of the traced sweep cells, each
    left out where its source is missing.  ``batches`` holds each
    traced cell's ``counters``."""
    out: dict[str, float] = {}
    have = [c for c in batches if c]
    steps = sum(c["steps_needed"] for c in have)
    run = sum(c["batch_steps"] for c in have)
    phases = dict(reduced.get("device_phases", []))
    if phases and steps:
        for p in ("settle", "madd", "backfill", "horizon"):
            out[f"{p}_ms_per_step"] = (
                1e3 * phases.get(PROGRAM_PREFIX + p, 0.0) / steps)
        out["named_phase_share"] = 1 - phases.get(UNSCOPED, 0.0) / sum(
            phases.values())
    if run:
        out["waves_per_step"] = sum(c["wave_iters"] for c in have) / run
        out["cascade_iters_per_step"] = (
            sum(c["cascade_iters"] for c in have) / run)
        out["window_step_util"] = steps / run
    spans = reduced.get("program_spans", {})
    if "simjax.pack_batch" in spans and batches:
        out["batch_pack_ms_per_lane"] = (
            1e3 * spans["simjax.pack_batch"] / (lanes * len(batches)))
    idle = dict(reduced["idle_gaps"])
    syncs = sum(c["batch_syncs"] for c in have)
    if spans and syncs:
        out["sync_idle_ms_per_sync"] = 1e3 * (
            idle.get("simjax.sync", 0.0)
            + idle.get("simjax.dispatch", 0.0)) / syncs
    engine = sum(v for k, v in idle.items()
                 if k == "bench.engine" or k.startswith(PROGRAM_PREFIX))
    if spans and engine:
        out["engine_idle_named_share"] = sum(
            v for k, v in idle.items()
            if k.startswith(PROGRAM_PREFIX)) / engine
    return out


@contextlib.contextmanager
def captured_results(simjax):
    """Within the block, every ``run_fifo_batch`` call's results are
    appended to the list it yields."""
    got: list = []
    real = simjax.run_fifo_batch

    def run(lanes, **kw):
        res = real(lanes, **kw)
        got.append(res)
        return res

    simjax.run_fifo_batch = run
    try:
        yield got
    finally:
        simjax.run_fifo_batch = real


def compiled_programs(simjax, lanes, steps_per_sync: int = 16) -> list[str]:
    """HLO text of the engine's two programs at the lanes' batch shape
    (from the compile cache once the lanes have run), with
    ``run_fifo_batch``'s default window."""
    pk = simjax._pack_batch([simjax.pack_instance(f, j) for f, j in lanes])
    st = simjax._init_state(pk)
    return [simjax._multi_step_jit.lower(pk, st, steps_per_sync)
            .compile().as_text(),
            simjax._settle_jit.lower(pk, st).compile().as_text()]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--cells", type=int, default=3,
                    help="sweep cells with the profiler off, then on")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    from bench import harness
    from repro.core import simjax

    print(f"compile cache: {simjax.place_compile_cache()}", file=sys.stderr)
    spec = harness.load_spec(args.workload)
    traffic, config = spec["traffic"], spec["config"]
    plan = harness.lane_plan(args.seed, traffic, config)

    def cells() -> tuple[list, list]:
        recs, walls = [], []
        with captured_results(simjax) as got:
            for _ in range(args.cells):
                t0 = time.perf_counter()
                recs.append(harness.sweep_cell(plan, traffic, config))
                walls.append(recs[-1].end - t0)
        return walls, [counters(r) for r in got]

    harness.sweep_cell(plan, traffic, config)          # compiles or loads
    programs = compiled_programs(simjax, harness.build_lanes(plan, traffic,
                                                             config))
    off, _ = cells()
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    trace_reduce.start(trace_dir)
    on, batches = cells()
    jax.profiler.stop_trace()
    reduced = reduce(load(trace_dir, programs))
    shutil.rmtree(trace_dir, ignore_errors=True)
    dev = jax.devices()[0]
    line = {"workload": args.workload, "seed": args.seed,
            "device": {"platform": dev.platform, "kind": dev.device_kind},
            "cell_s": {"profiler_off": off, "profiler_on": on,
                       "median_ratio": (statistics.median(on)
                                        / statistics.median(off))},
            "counters": batches[0], "reduced": reduced,
            "readings": readings(reduced, batches, len(plan))}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
