"""From a profiler trace to device busy time, engine phases, top ops and
idle gaps.

``load`` reads the newest ``*.xplane.pb`` under a trace directory into
plain lists: every device op with its scope path, and the host spans of
the benchmark (``bench.*``) and of the engine (``simjax.*``).
``reduce`` turns those into numbers.  The window is the host span of
the benchmark's sweep cells (``bench.cell``, first start to last end),
so start-up and tear-down of the profiler are not in it.

* busy: the union of the device-op intervals inside the window, per
  device, averaged over the devices that ran anything;
* device ops: self time per op name, an op's duration less the part the
  ops nested in it cover (a ``while`` op's event holds its body's ops on
  the same line); the self times add up to the busy union;
* device phases: the same self times summed by engine phase, the first
  ``simjax.*`` part of an op's scope path (``simjax.settle``,
  ``simjax.madd``, ``simjax.backfill``, ``simjax.horizon``), and
  ``unscoped`` for the rest (loop control, the flag read, transfers);
  given where some op carries a scope;
* idle gaps: the window minus the busy union, split by the innermost
  host span the host was in (``simjax.sync`` inside ``bench.engine``),
  and ``between cells`` for time outside every span;
* program spans: each ``simjax.*`` span's time in the window, given
  where the trace has any.
"""

from __future__ import annotations

import bisect
import math
import re
from collections.abc import Sequence
from pathlib import Path

#: The device planes' line that holds one event per executed XLA op.
OP_LINE = "XLA Ops"
#: The device planes' line with one event per run of an XLA program.
MODULE_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
PROGRAM_PREFIX = "simjax."
CELL = SPAN_PREFIX + "cell"
UNSCOPED = "unscoped"
TOP = 10
_INSTR = re.compile(r'^\s*(?:ROOT )?(%[^ ]+) = .*?op_name="([^"]*)"', re.M)


def start(trace_dir: str | Path) -> None:
    """Start the profiler for a window: device ops and the host's
    annotations, without the Python call tracer, which slows the host
    and fills the trace with one event per Python call."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)


def op_name(event_name: str) -> str:
    """An XLA op's own name (``%fusion.12``), without the HLO text the
    TPU trace appends to it."""
    return event_name.split(" = ", 1)[0]


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
    devices, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            runs = sorted((e.start_ns, e.end_ns, e.name.split("(", 1)[0])
                          for line in plane.lines if line.name == MODULE_LINE
                          for e in line.events)
            starts = [r[0] for r in runs]
            ops = []
            for line in plane.lines:
                if line.name != OP_LINE:
                    continue
                for e in line.events:
                    op, s = op_name(e.name), e.start_ns
                    i = bisect.bisect_right(starts, s) - 1
                    module = runs[i][2] if i >= 0 and s < runs[i][1] else ""
                    ops.append([op, s, e.duration_ns,
                                paths.get((module, op), "")])
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            spans += [[e.name, e.start_ns, e.duration_ns]
                      for line in plane.lines for e in line.events
                      if e.name.startswith((SPAN_PREFIX, PROGRAM_PREFIX))]
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


def self_times(ivs: list[tuple[float, float, object]]) -> dict:
    """Each interval's length less the part the intervals nested in it
    cover, summed by label; the sums add up to the intervals' union."""
    out: dict = {}
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


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _overlap(a0: float, a1: float, b0: float, b1: float) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def reduce(trace: dict) -> dict:
    """Busy and window seconds, the top device ops by self time (at most
    ``TOP``), the device phases, the idle gaps by innermost host span
    and the program spans; each list largest first."""
    cells = [(s, s + d) for n, s, d in trace["spans"] if n == CELL]
    if not cells or not trace["devices"]:
        raise ValueError("the trace holds no sweep cell or no device op")
    w0 = min(a for a, _ in cells)
    w1 = max(b for _, b in cells)
    # The pieces follow one another on the host thread, so one pointer
    # walks them alongside the (sorted) gaps.
    pieces = innermost([(s, s + d, n) for n, s, d in trace["spans"]
                        if n != CELL])

    busy_ns, op_ns, phase_ns, gap_ns = [], {}, {}, {}
    phases: dict[str, str] = {"": UNSCOPED}      # scope path -> phase
    for ops in trace["devices"].values():
        ivs = []
        for name, s, d, *path in ops:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                p = path[0] if path else ""
                if p not in phases:
                    phases[p] = phase_of(p)
                ivs.append((a, b, (name, phases[p])))
        for (name, phase), v in self_times(ivs).items():
            op_ns[name] = op_ns.get(name, 0.0) + v
            phase_ns[phase] = phase_ns.get(phase, 0.0) + v
        merged = _union([(a, b) for a, b, _ in ivs])
        busy_ns.append(sum(b - a for a, b in merged))
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        i = 0
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 <= g0:
                continue
            while i < len(pieces) and pieces[i][1] <= g0:
                i += 1
            covered = 0.0
            j = i
            while j < len(pieces) and pieces[j][0] < g1:
                o = _overlap(g0, g1, pieces[j][0], pieces[j][1])
                name = pieces[j][2]
                gap_ns[name] = gap_ns.get(name, 0.0) + o
                covered += o
                j += 1
            if g1 - g0 > covered:
                gap_ns["between cells"] = (gap_ns.get("between cells", 0.0)
                                           + (g1 - g0 - covered))
    n_dev = len(trace["devices"])

    def ranked(d: dict, top: int | None = None) -> list:
        return [[k, v / n_dev / 1e9]
                for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]
                if v > 0]

    out = {"busy_s": sum(busy_ns) / n_dev / 1e9, "window_s": (w1 - w0) / 1e9,
           "device_ops": ranked(op_ns, TOP), "idle_gaps": ranked(gap_ns)}
    if set(phase_ns) - {UNSCOPED}:
        out["device_phases"] = ranked(phase_ns)
    program: dict[str, float] = {}
    for n, s, d in trace["spans"]:
        if n.startswith(PROGRAM_PREFIX):
            program[n] = program.get(n, 0.0) + _overlap(s, s + d, w0, w1)
    if program:
        out["program_spans"] = {k: v / 1e9 for k, v in sorted(program.items())}
    return out
