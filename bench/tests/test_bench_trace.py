"""The reduction from a profiler trace to busy time, engine phases, top
device ops and idle gaps by innermost host span: on hand-made traces
with known answers, on slices of traces recorded on a TPU v5e, and on
traces taken on the CPU; and the per-layer readers of the engine's
phases, spans and counters."""

from __future__ import annotations

import json
import math
from pathlib import Path

import pytest

pytest.importorskip("jax")

from bench import harness, trace_reduce  # noqa: E402

HERE = Path(__file__).resolve().parent
MS = 1e6                       # trace times are in nanoseconds
LOOP = "jit(_multi_step)/while/body/closed_call"
PHASES = {"simjax.settle", "simjax.madd", "simjax.backfill", "simjax.horizon"}
#: The readers of the engine's phases, spans and counters.
ENGINE_METRICS = ["settle_ms_per_step", "madd_ms_per_step",
                  "backfill_ms_per_step", "horizon_ms_per_step",
                  "waves_per_step", "cascade_iters_per_step",
                  "window_step_util", "batch_pack_ms_per_lane",
                  "sync_idle_ms_per_sync"]


def test_hand_made_trace():
    spans = [["bench.cell", 0, 100 * MS], ["bench.build", 0, 20 * MS],
             ["bench.pack", 20 * MS, 10 * MS],
             ["bench.engine", 30 * MS, 65 * MS]]
    ops = [["A", 10 * MS, 15 * MS], ["B", 22 * MS, 18 * MS],
           ["A", 60 * MS, 20 * MS], ["C", 90 * MS, 110 * MS]]
    out = trace_reduce.reduce({"devices": {"/device:TPU:0": ops},
                               "spans": spans})
    # Busy union inside [0, 100]: [10, 40] + [60, 80] + [90, 100].
    assert out["busy_s"] == pytest.approx(0.060)
    assert out["window_s"] == pytest.approx(0.100)
    # Self time: B started inside A, so A keeps [10, 22] of its first run.
    assert [k for k, _ in out["device_ops"]] == ["A", "B", "C"]
    assert [v for _, v in out["device_ops"]] == pytest.approx(
        [0.032, 0.018, 0.010])
    # Idle: [0, 10] in build, [40, 60] and [80, 90] in engine.
    assert dict(out["idle_gaps"]) == pytest.approx(
        {"bench.engine": 0.030, "bench.build": 0.010})


def test_gap_outside_every_span_and_two_devices():
    spans = [["bench.cell", 0, 40 * MS], ["bench.engine", 0, 10 * MS],
             ["bench.cell", 60 * MS, 40 * MS],
             ["bench.engine", 60 * MS, 40 * MS]]
    out = trace_reduce.reduce({
        "devices": {"/device:TPU:0": [["A", 0, 100 * MS]],
                    "/device:TPU:1": [["A", 0, 50 * MS]]},
        "spans": spans})
    assert out["window_s"] == pytest.approx(0.1)
    assert out["busy_s"] == pytest.approx(0.075)
    # Device 1 idles in [50, 60] (between cells) and [60, 100] (engine).
    assert dict(out["idle_gaps"]) == pytest.approx(
        {"bench.engine": 0.020, "between cells": 0.005})


def test_no_device_op_is_an_error():
    with pytest.raises(ValueError):
        trace_reduce.reduce({"devices": {},
                             "spans": [["bench.cell", 0, 1]]})


def test_load_reads_the_benchmark_spans(tmp_path):
    """On the CPU a trace holds no device plane; the host spans load."""
    jax = pytest.importorskip("jax")
    trace_reduce.start(tmp_path)
    with jax.profiler.TraceAnnotation("bench.cell"):
        with jax.profiler.TraceAnnotation("bench.build"):
            jax.numpy.ones(8).block_until_ready()
    jax.profiler.stop_trace()
    tr = trace_reduce.load(tmp_path)
    names = [n for n, _, _ in tr["spans"]]
    assert sorted(names) == ["bench.build", "bench.cell"]
    (_, c0, cd), = [s for s in tr["spans"] if s[0] == "bench.cell"]
    (_, b0, bd), = [s for s in tr["spans"] if s[0] == "bench.build"]
    assert c0 <= b0 and b0 + bd <= c0 + cd
    assert tr["devices"] == {}


@pytest.mark.parametrize("which", ["mixed48", "replay32"])
def test_recorded_tpu_slice(which):
    """Slices of traced runs on a TPU v5e, with the spans clipped to
    them, and the reductions recorded before the engine's phases were
    read by ``trace_reduce`` (with ``device_ops`` then counting a loop's
    body inside the loop too):

    * ``mixed48``: the first 3,000 device ops of a ``mixed48.heavy8``
      run, the benchmark's spans only, no scopes;
    * ``replay32``: the first 2,000 device ops of a ``fb2010.replay32``
      sweep cell of the instrumented engine (batch pack, first settle,
      the first steps of the first window), each op with the scope path
      its compiled program gives it.
    """
    tr = json.loads((HERE / f"trace_v5e_{which}_slice.json").read_text())
    want = json.loads(
        (HERE / f"trace_v5e_{which}_slice.reduced.json").read_text())
    out = trace_reduce.reduce(tr)
    assert out["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert out["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert set(out) == set(want)
    for key in ("idle_gaps", "device_phases", "program_spans"):
        assert out.get(key) == want.get(key)
    idle = sum(v for _, v in out["idle_gaps"])
    assert out["busy_s"] + idle == pytest.approx(out["window_s"], rel=1e-9)
    assert {k for k, _ in out["idle_gaps"]} <= {
        "bench.build", "bench.pack", "bench.engine", "between cells",
        "simjax.pack_batch", "simjax.init", "simjax.sync",
        "simjax.dispatch", "simjax.readback"}
    if which == "replay32":
        assert {k for k, _ in out["device_phases"]} == PHASES | {"unscoped"}
        assert sum(v for _, v in out["device_phases"]) == pytest.approx(
            out["busy_s"], rel=1e-9)
    # Top ops by self time: a loop no longer counts its body, so they
    # add up to no more than the busy time, which the loops' totals did
    # not; an op that holds none keeps its time.
    ops, old = dict(out["device_ops"]), dict(want["device_ops"])
    assert 0 < len(ops) <= trace_reduce.TOP
    assert all(v > 0 and math.isfinite(v) for v in ops.values())
    assert sum(ops.values()) <= out["busy_s"] < sum(old.values())
    for k, v in ops.items():
        if k in old:
            assert v == old[k] if not k.startswith("%while") else v < old[k]


def _nested_trace() -> dict:
    """One cell: the benchmark's spans, the engine's nested in
    ``bench.engine``, and a window program whose loops hold their
    bodies."""
    spans = [["bench.cell", 0, 100 * MS], ["bench.build", 0, 10 * MS],
             ["bench.pack", 10 * MS, 10 * MS],
             ["bench.engine", 20 * MS, 80 * MS],
             ["simjax.pack_batch", 20 * MS, 10 * MS],
             ["simjax.init", 30 * MS, 5 * MS],
             ["simjax.sync", 40 * MS, 10 * MS],
             ["simjax.dispatch", 50 * MS, 5 * MS],
             ["simjax.sync", 60 * MS, 10 * MS],
             ["simjax.readback", 90 * MS, 10 * MS]]
    ops = [["%fusion.1", 32 * MS, 10 * MS, "jit(_settle)/simjax.settle/add"],
           ["%while.9", 52 * MS, 36 * MS, "jit(_multi_step)/while"],
           ["%while.4", 53 * MS, 20 * MS, f"{LOOP}/simjax.backfill/while"],
           ["%gather.2", 54 * MS, 8 * MS,
            f"{LOOP}/simjax.backfill/while/body/gather"],
           ["%fusion.7", 63 * MS, 9 * MS,
            f"{LOOP}/simjax.backfill/while/body/min"],
           ["%fusion.3", 74 * MS, 4 * MS, f"{LOOP}/simjax.horizon/sub"],
           ["%fusion.5", 80 * MS, 6 * MS, f"{LOOP}/simjax.settle/while"],
           ["%copy.1", 87 * MS, 1 * MS, ""]]
    return {"devices": {"/device:TPU:0": ops}, "spans": spans}


def test_loops_count_their_own_time_once():
    """A ``while`` op holding its body's ops: self times, summed by
    phase and by op, add up to the busy union."""
    out = trace_reduce.reduce(_nested_trace())
    # Busy: [32, 42] and [52, 88].
    assert out["busy_s"] == pytest.approx(0.046)
    assert dict(out["device_phases"]) == pytest.approx({
        "simjax.settle": 0.016,          # [32, 42] + [80, 86]
        "simjax.backfill": 0.020,        # the loop [53, 73], body inside
        "simjax.horizon": 0.004,
        # The outer loop's control, [52, 53] [73, 74] [78, 80] [86, 87],
        # and an op with no scope, [87, 88].
        "unscoped": 0.006})
    assert sum(v for _, v in out["device_phases"]) == pytest.approx(
        out["busy_s"], rel=1e-12)
    assert dict(out["device_ops"])["%while.9"] == pytest.approx(0.005)
    assert dict(out["device_ops"])["%while.4"] == pytest.approx(0.003)
    assert sum(v for _, v in out["device_ops"]) == pytest.approx(
        out["busy_s"], rel=1e-12)


def test_idle_goes_to_the_innermost_span():
    out = trace_reduce.reduce(_nested_trace())
    assert dict(out["idle_gaps"]) == pytest.approx({
        "bench.build": 0.010, "bench.pack": 0.010,
        "simjax.pack_batch": 0.010, "simjax.init": 0.002,   # [30, 32]
        "simjax.sync": 0.008,            # [42, 50]; the second is busy
        "simjax.dispatch": 0.002,        # [50, 52]
        "bench.engine": 0.002,           # [88, 90], between the spans
        "simjax.readback": 0.010})
    idle = sum(v for _, v in out["idle_gaps"])
    assert out["busy_s"] + idle == pytest.approx(out["window_s"], rel=1e-12)
    assert out["program_spans"] == pytest.approx({
        "simjax.dispatch": 0.005, "simjax.init": 0.005,
        "simjax.pack_batch": 0.010, "simjax.readback": 0.010,
        "simjax.sync": 0.020})


def _bench_only() -> list[dict]:
    """Traces with the benchmark's spans only and unscoped ops: the
    hand-made ones above and the recorded ``mixed48.heavy8`` slice."""
    spans = [["bench.cell", 0, 100 * MS], ["bench.build", 0, 20 * MS],
             ["bench.pack", 20 * MS, 10 * MS],
             ["bench.engine", 30 * MS, 65 * MS]]
    ops = [["A", 10 * MS, 15 * MS], ["B", 22 * MS, 18 * MS],
           ["A", 60 * MS, 20 * MS], ["C", 90 * MS, 110 * MS]]
    two = {"devices": {"/device:TPU:0": [["A", 0, 100 * MS]],
                       "/device:TPU:1": [["A", 0, 50 * MS]]},
           "spans": [["bench.cell", 0, 40 * MS], ["bench.engine", 0, 10 * MS],
                     ["bench.cell", 60 * MS, 40 * MS],
                     ["bench.engine", 60 * MS, 40 * MS]]}
    sliced = json.loads((HERE / "trace_v5e_mixed48_slice.json").read_text())
    return [{"devices": {"/device:TPU:0": ops}, "spans": spans}, two, sliced]


@pytest.mark.parametrize("which", range(3))
def test_benchmark_spans_only_reduce_as_before(which):
    """Without the engine's scopes and spans a trace gives no phases
    and no program spans, its spans are their own innermost pieces, and
    busy and idle time fill the window."""
    trace = _bench_only()[which]
    out = trace_reduce.reduce(trace)
    assert set(out) == {"busy_s", "window_s", "device_ops", "idle_gaps"}
    leaves = sorted((s, s + d, n) for n, s, d in trace["spans"]
                    if n != "bench.cell")
    cells = [s for s in trace["spans"] if s[0] == "bench.cell"]
    if len(cells) == 1:
        assert trace_reduce.innermost(leaves) == leaves
    idle = sum(v for _, v in out["idle_gaps"])
    assert out["busy_s"] + idle == pytest.approx(out["window_s"], rel=1e-9)


def test_innermost_of_spans_that_do_not_nest():
    pieces = [(0, 20, "bench.build"), (20, 30, "bench.pack"),
              (30, 95, "bench.engine")]
    assert trace_reduce.innermost(pieces) == pieces


def _measurements(trace: dict | None, **counts) -> harness.Measurements:
    cell = harness.CellRecord(build_s=0.01, pack_s=0.01, engine_s=0.08,
                              end=0.1, lane_events=[10, 7, 3, 1],
                              results=[], **counts)
    return harness.Measurements(
        lanes=4, setup_s=1.0, window_start=0.0, cells=[cell],
        trace=trace and trace_reduce.reduce(trace), traced_cells=1)


def test_engine_without_counters():
    """An engine with neither counters nor scopes nor spans: every
    engine reading is left out, none raises."""
    m = _measurements(_bench_only()[0])
    assert harness.read_metrics(ENGINE_METRICS, m, {}) == {}
    assert harness.read_metrics(
        ENGINE_METRICS, _measurements(None), {}) == {}


def test_readings_of_the_hand_made_trace():
    m = _measurements(_nested_trace(), wave_iters=30, cascade_iters=20,
                      batch_steps=16, batch_syncs=2)
    got = {k: v["value"] for k, v in harness.read_metrics(
        ENGINE_METRICS, m, dict.fromkeys(ENGINE_METRICS, "")).items()}
    assert got == pytest.approx({
        "settle_ms_per_step": 1.6, "madd_ms_per_step": 0.0,
        "backfill_ms_per_step": 2.0, "horizon_ms_per_step": 0.4,
        "waves_per_step": 30 / 16, "cascade_iters_per_step": 20 / 16,
        "window_step_util": 10 / 16,
        "batch_pack_ms_per_lane": 10 / 4,
        "sync_idle_ms_per_sync": (8 + 2) / 2})


def test_load_reads_the_engine_spans(tmp_path):
    """On the CPU a trace holds no device plane; the engine's host spans
    load, nested in the benchmark's, and its counters come back."""
    pytest.importorskip("jax")
    import jax

    from repro.core import Fabric, JobDAG
    from repro.core.simjax import pack_instance, run_fifo_batch

    job = JobDAG("j0")
    job.add_metaflow("m0", [(0, 1, 2.0)])
    lane = pack_instance(Fabric(n_ports=2), [job])
    run_fifo_batch([lane])                          # compiles
    trace_reduce.start(tmp_path)
    with jax.profiler.TraceAnnotation("bench.cell"):
        with jax.profiler.TraceAnnotation("bench.engine"):
            res = run_fifo_batch([lane])
    jax.profiler.stop_trace()
    tr = trace_reduce.load(tmp_path)
    names = [n for n, _, _ in tr["spans"]]
    assert sorted(set(names)) == [
        "bench.cell", "bench.engine", "simjax.dispatch", "simjax.init",
        "simjax.pack_batch", "simjax.readback", "simjax.sync"]
    assert names.count("simjax.sync") == 2
    (_, e0, ed), = [s for s in tr["spans"] if s[0] == "bench.engine"]
    assert all(e0 <= s and s + d <= e0 + ed for n, s, d in tr["spans"]
               if n.startswith("simjax."))
    assert tr["devices"] == {}
    assert harness.counters(res) == {
        "wave_iters": 1, "cascade_iters": 4, "batch_steps": 16,
        "batch_syncs": 2}


_PROGRAM = '''HloModule jit__multi_step, entry_computation_layout={()->f64[4]{0}}

ENTRY %main.9 () -> f64[4] {
  %p = f64[4]{0} parameter(0), metadata={op_name="x"}
  ROOT %fusion.3 = f64[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_multi_step)/while/body/simjax.backfill/min"}
}
'''

_XSPACE = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 4000000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 11000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "jit__multi_step(42)" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.3 = f64[4]{0} fusion(%p)" } }
  event_metadata { key: 3 value { id: 3 name: "%copy.2 = f64[4]{0} copy(%p)" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 9000000 }
    events { metadata_id: 2 offset_ps: 500000 duration_ps: 500000 }
    events { metadata_id: 3 offset_ps: 600000 duration_ps: 100000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.cell" } }
  event_metadata { key: 2 value { id: 2 name: "simjax.sync" } }
  event_metadata { key: 3 value { id: 3 name: "PjitFunction(_multi_step)" } } }
'''


def test_load_names_ops_through_their_program(tmp_path):
    """A trace laid out as a TPU's: ops take their scope path from the
    compiled program whose run they lie in; an op the program lacks, or
    one outside every run, has none."""
    pytest.importorskip("jax")
    from jax.profiler import ProfileData

    run = tmp_path / "plugins" / "profile" / "run"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(_XSPACE))
    tr = trace_reduce.load(tmp_path, [_PROGRAM])
    assert tr["devices"] == {"/device:TPU:0": [
        ["%fusion.3", 1000.0, 2000.0,
         "jit(_multi_step)/while/body/simjax.backfill/min"],
        ["%copy.2", 4000.0, 1000.0, ""],
        ["%fusion.3", 11000.0, 1000.0, ""]]}
    assert tr["spans"] == [["bench.cell", 0.0, 9000.0],
                           ["simjax.sync", 500.0, 500.0]]
    assert {op[3] for op in trace_reduce.load(tmp_path)["devices"][
        "/device:TPU:0"]} == {""}


def test_engine_programs_name_every_phase():
    """The engine's two compiled programs (CPU backend) give every
    phase's ops a scope path."""
    pytest.importorskip("jax")
    from repro.core import Fabric, JobDAG

    job = JobDAG("j0")
    job.add_metaflow("m0", [(0, 1, 2.0), (0, 2, 1.0)])
    paths = trace_reduce.scope_map(harness.engine_programs(
        [(Fabric(n_ports=3), [job])]))
    assert {m for m, _ in paths} == {"jit__multi_step", "jit__settle"}
    phases = {m: {trace_reduce.phase_of(p) for (mm, _), p in paths.items()
                  if mm == m} for m, _ in paths}
    assert phases["jit__multi_step"] >= PHASES
    assert phases["jit__settle"] - {"unscoped"} == {"simjax.settle"}
