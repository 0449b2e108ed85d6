"""From a profiler trace to device busy time, top ops and idle gaps.

``load`` reads the newest ``*.xplane.pb`` under a trace directory into
plain lists; ``reduce`` turns those into numbers.  The window is the
host span of the benchmark's sweep cells (``bench.cell``, first start to
last end), so start-up and tear-down of the profiler are not in it.

* busy: the union of the device-op intervals inside the window, per
  device, averaged over the devices that ran anything;
* device ops: total device time per op name;
* idle gaps: the window minus the busy union, split by the innermost
  benchmark span (``bench.build``, ``bench.pack``, ``bench.engine``) the
  host was in, and ``between cells`` for time outside them.
"""

from __future__ import annotations

from pathlib import Path

#: The device planes' line that holds one event per executed XLA op.
OP_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
TOP = 10


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


def load(trace_dir: str | Path) -> dict:
    """``{"devices": {plane: [[op, start_ns, dur_ns], ...]},
    "spans": [[name, start_ns, dur_ns], ...]}`` from the newest trace."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    data = ProfileData.from_file(str(files[-1]))
    devices, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            ops = [[op_name(e.name), e.start_ns, e.duration_ns]
                   for line in plane.lines if line.name == OP_LINE
                   for e in line.events]
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            spans += [[e.name, e.start_ns, e.duration_ns]
                      for line in plane.lines for e in line.events
                      if e.name.startswith(SPAN_PREFIX)]
    return {"devices": devices, "spans": spans}


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
    """Busy and window seconds, the top device ops and the idle gaps by
    host span (each list at most ``TOP`` long, largest first)."""
    cells = [(s, s + d) for n, s, d in trace["spans"]
             if n == SPAN_PREFIX + "cell"]
    if not cells or not trace["devices"]:
        raise ValueError("the trace holds no sweep cell or no device op")
    w0 = min(a for a, _ in cells)
    w1 = max(b for _, b in cells)
    # The leaf spans follow one another on the host thread, so one
    # pointer walks them alongside the (sorted) gaps.
    leaves = sorted((s, s + d, n) for n, s, d in trace["spans"]
                    if n != SPAN_PREFIX + "cell")

    busy_ns, op_ns, gap_ns = [], {}, {}
    for ops in trace["devices"].values():
        ivs = []
        for name, s, d in ops:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                ivs.append((a, b))
                op_ns[name] = op_ns.get(name, 0.0) + (b - a)
        merged = _union(ivs)
        busy_ns.append(sum(b - a for a, b in merged))
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        i = 0
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 <= g0:
                continue
            while i < len(leaves) and leaves[i][1] <= g0:
                i += 1
            covered = 0.0
            j = i
            while j < len(leaves) and leaves[j][0] < g1:
                o = _overlap(g0, g1, leaves[j][0], leaves[j][1])
                name = leaves[j][2]
                gap_ns[name] = gap_ns.get(name, 0.0) + o
                covered += o
                j += 1
            if g1 - g0 > covered:
                gap_ns["between cells"] = (gap_ns.get("between cells", 0.0)
                                           + (g1 - g0 - covered))
    n_dev = len(trace["devices"])

    def top(d: dict) -> list:
        return [[k, v / n_dev / 1e9]
                for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:TOP]
                if v > 0]

    return {"busy_s": sum(busy_ns) / n_dev / 1e9, "window_s": (w1 - w0) / 1e9,
            "device_ops": top(op_ns), "idle_gaps": top(gap_ns)}
