"""The reading of the engine's own phases, spans and counters from a
profiler trace (``bench/trace_phases.py``): on hand-made traces with
known answers, on a trace with the benchmark's spans only (which reduces
as ``bench/trace_reduce.py`` reduces it), on a slice of a trace of the
instrumented engine recorded on a TPU v5e, and on a trace of the engine
taken on the CPU."""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import pytest

from bench import trace_phases, trace_reduce

HERE = Path(__file__).resolve().parent
MS = 1e6                       # trace times are in nanoseconds
LOOP = "jit(_multi_step)/while/body/closed_call"
PHASES = {"simjax.settle", "simjax.madd", "simjax.backfill", "simjax.horizon"}


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
    phase, add up to the busy union."""
    out = trace_phases.reduce(_nested_trace())
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
    # device_ops keeps counting a loop's body inside the loop too.
    assert dict(out["device_ops"])["%while.9"] == pytest.approx(0.036)


def test_idle_goes_to_the_innermost_span():
    out = trace_phases.reduce(_nested_trace())
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
    """Traces with the benchmark's spans only and unscoped ops: the ones
    of ``test_bench_trace.py`` and the recorded ``mixed48.heavy8``
    slice."""
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
    trace = _bench_only()[which]
    out = trace_phases.reduce(trace)
    want = trace_reduce.reduce(trace)
    assert set(out) == set(want)            # no phases, no program spans
    assert out["device_ops"] == want["device_ops"]
    assert (out["busy_s"], out["window_s"]) == (want["busy_s"],
                                                want["window_s"])
    assert [k for k, _ in out["idle_gaps"]] == [k for k, _ in
                                                want["idle_gaps"]]
    assert [v for _, v in out["idle_gaps"]] == pytest.approx(
        [v for _, v in want["idle_gaps"]], rel=1e-12)


def test_innermost_of_spans_that_do_not_nest():
    pieces = [(0, 20, "bench.build"), (20, 30, "bench.pack"),
              (30, 95, "bench.engine")]
    assert trace_phases.innermost(pieces) == pieces


@dataclasses.dataclass
class _UncountedLane:
    """A lane result of an engine without counters."""

    jct: dict
    cct: dict
    makespan: float
    events: int


def test_engine_without_counters():
    """An engine with neither counters nor scopes nor spans: every
    reading that needs them is left out, none raises."""
    assert trace_phases.counters([_UncountedLane({}, {}, 1.0, 5)]) is None
    assert trace_phases.counters([]) is None
    trace = _bench_only()[0]
    assert trace_phases.readings(trace_phases.reduce(trace), [None],
                                 lanes=2) == {}


def test_readings_of_the_hand_made_trace():
    out = trace_phases.reduce(_nested_trace())
    batch = {"wave_iters": 30, "cascade_iters": 20, "batch_steps": 16,
             "batch_syncs": 2, "steps_needed": 10}
    got = trace_phases.readings(out, [batch], lanes=4)
    assert got == pytest.approx({
        "settle_ms_per_step": 1.6, "madd_ms_per_step": 0.0,
        "backfill_ms_per_step": 2.0, "horizon_ms_per_step": 0.4,
        "named_phase_share": 40 / 46,
        "waves_per_step": 30 / 16, "cascade_iters_per_step": 20 / 16,
        "window_step_util": 10 / 16,
        "batch_pack_ms_per_lane": 10 / 4,
        "sync_idle_ms_per_sync": (8 + 2) / 2,
        "engine_idle_named_share": 32 / 34})


def test_load_reads_the_engine_spans(tmp_path):
    """On the CPU a trace holds no device plane; the engine's host spans
    load, nested in the benchmark's."""
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
    tr = trace_phases.load(tmp_path)
    names = [n for n, _, _ in tr["spans"]]
    assert sorted(set(names)) == [
        "bench.cell", "bench.engine", "simjax.dispatch", "simjax.init",
        "simjax.pack_batch", "simjax.readback", "simjax.sync"]
    assert names.count("simjax.sync") == 2
    (_, e0, ed), = [s for s in tr["spans"] if s[0] == "bench.engine"]
    assert all(e0 <= s and s + d <= e0 + ed for n, s, d in tr["spans"]
               if n.startswith("simjax."))
    assert tr["devices"] == {}
    assert trace_phases.counters(res) == {
        "wave_iters": 1, "cascade_iters": 4, "batch_steps": 16,
        "batch_syncs": 2, "steps_needed": 1}


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
    tr = trace_phases.load(tmp_path, [_PROGRAM])
    assert tr["devices"] == {"/device:TPU:0": [
        ["%fusion.3", 1000.0, 2000.0,
         "jit(_multi_step)/while/body/simjax.backfill/min"],
        ["%copy.2", 4000.0, 1000.0, ""],
        ["%fusion.3", 11000.0, 1000.0, ""]]}
    assert tr["spans"] == [["bench.cell", 0.0, 9000.0],
                           ["simjax.sync", 500.0, 500.0]]
    assert {op[3] for op in trace_phases.load(tmp_path)["devices"][
        "/device:TPU:0"]} == {""}


def test_engine_programs_name_every_phase():
    """The engine's two compiled programs (CPU backend) give every
    phase's ops a scope path."""
    pytest.importorskip("jax")
    from repro.core import Fabric, JobDAG
    from repro.core import simjax

    job = JobDAG("j0")
    job.add_metaflow("m0", [(0, 1, 2.0), (0, 2, 1.0)])
    paths = trace_phases.scope_map(trace_phases.compiled_programs(
        simjax, [(Fabric(n_ports=3), [job])]))
    assert {m for m, _ in paths} == {"jit__multi_step", "jit__settle"}
    phases = {m: {trace_phases.phase_of(p) for (mm, _), p in paths.items()
                  if mm == m} for m, _ in paths}
    assert phases["jit__multi_step"] >= PHASES
    assert phases["jit__settle"] - {"unscoped"} == {"simjax.settle"}


def test_recorded_tpu_slice():
    """The first 2,000 device ops of a traced ``fb2010.replay32`` sweep
    cell of the instrumented engine on a TPU v5e (batch pack, first
    settle, the first steps of the first window), each op with the
    scope path its compiled program gives it, and the spans clipped to
    them."""
    tr = json.loads((HERE / "trace_v5e_replay32_slice.json").read_text())
    out = trace_phases.reduce(tr)
    assert {k for k, _ in out["device_phases"]} == PHASES | {"unscoped"}
    assert sum(v for _, v in out["device_phases"]) == pytest.approx(
        out["busy_s"], rel=1e-9)
    assert {k for k, _ in out["idle_gaps"]} <= {
        "bench.build", "bench.pack", "bench.engine", "between cells",
        "simjax.pack_batch", "simjax.init", "simjax.sync",
        "simjax.dispatch", "simjax.readback"}
    idle = sum(v for _, v in out["idle_gaps"])
    assert out["busy_s"] + idle == pytest.approx(out["window_s"], rel=1e-9)
    assert out == json.loads(
        (HERE / "trace_v5e_replay32_slice.reduced.json").read_text())
