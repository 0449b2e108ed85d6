"""A whole run past the look for a chip, at a tiny size on the CPU: the
window, the check against the reference, and the faults the check must
catch, each planted under the timed path."""

from __future__ import annotations

import dataclasses
import time

import pytest

pytest.importorskip("jax")

from bench import harness, readings  # noqa: E402
from repro.core import simjax  # noqa: E402

SEED = 2 ** 31 + 4242          # larger than 32 signed bits hold


@pytest.fixture(scope="module")
def tiny():
    """The cell cut to 2 lanes of 3 coflows (one small program)."""
    spec = harness.load_spec("fb2010.sweep20")
    return dict(spec, traffic=dict(spec["traffic"], lanes=2, coflows=3))


def _run(spec, trace=False):
    return harness.run_cell(spec, SEED, 0.3, trace, time.perf_counter())


def test_sound_run(tiny, capsys):
    line = _run(tiny)
    assert line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] >= 2
    assert line["attempted"] % 2 == 0
    assert set(line["metrics"]) == {"lanes_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert list(line)[-1] == "checks"
    assert line["checks"]["max_abs_diff"]["value"] <= 1e-9
    assert line["checks"]["window_traces"]["value"] == 0
    assert line["checks"]["window_lowerings"]["value"] == 0
    err = capsys.readouterr().err.strip().splitlines()
    assert err[-1].startswith("check window_lowerings: 0 (limit 0)")
    assert any(x.startswith("trace_count before the window") for x in err)


def test_same_seed_same_inputs(tiny):
    a = harness.lane_plan(SEED, tiny["traffic"], tiny["config"])
    assert a == harness.lane_plan(SEED, tiny["traffic"], tiny["config"])
    assert a != harness.lane_plan(SEED + 1, tiny["traffic"], tiny["config"])


def _wrap(monkeypatch, change):
    real = simjax.run_fifo_batch
    monkeypatch.setattr(simjax, "run_fifo_batch",
                        lambda lanes, **kw: change(real, lanes))


def test_altered_answer_fails(tiny, monkeypatch):
    def change(real, lanes):
        out = real(lanes)
        jct = dict(out[1].jct)
        jct[next(iter(jct))] += 1e-4
        return [out[0], dataclasses.replace(out[1], jct=jct)]

    _wrap(monkeypatch, change)
    line = _run(tiny)
    assert line["correct"] is False
    assert line["failed"] == line["attempted"] // 2
    assert line["checks"]["max_abs_diff"]["value"] >= 1e-4 * 0.99


def test_half_the_batch_left_out_fails(tiny, monkeypatch):
    _wrap(monkeypatch, lambda real, lanes: real(lanes[:1]))
    line = _run(tiny)
    assert line["correct"] is False
    assert line["failed"] == line["attempted"]


def test_step_that_returns_its_state_fails(tiny, monkeypatch):
    monkeypatch.setattr(simjax, "_multi_step_jit", lambda pk, s, n: s)
    _wrap(monkeypatch, lambda real, lanes: real(lanes, max_events=64))
    line = _run(tiny)
    assert line["correct"] is False
    assert line["failed"] == line["attempted"]


def test_float32_control_fails(tiny):
    with readings.control_engine(harness):
        line = _run(tiny)
    assert line["correct"] is False
    assert (line["checks"]["max_abs_diff"]["value"]
            > line["checks"]["max_abs_diff"]["limit"])


def test_traced_run_reports_per_layer_metrics(tiny, monkeypatch):
    """The profiler itself needs a chip; its reduction is fed a trace.
    The engine's counters come from the run itself, on the CPU."""
    from bench import trace_reduce

    ms = 1e6
    step = "jit(_multi_step)/while/body"
    fake = {"devices": {"/device:TPU:0": [
                ["%while.1", 10 * ms, 50 * ms, "jit(_multi_step)/while"],
                ["%fusion.2", 10 * ms, 20 * ms, f"{step}/simjax.madd/add"],
                ["%fusion.3", 30 * ms, 10 * ms, f"{step}/simjax.backfill/min"],
                ["%fusion.4", 40 * ms, 5 * ms, f"{step}/simjax.horizon/sub"],
                ["%fusion.5", 45 * ms, 5 * ms, f"{step}/simjax.settle/or"]]},
            "spans": [["bench.cell", 0, 100 * ms],
                      ["bench.build", 0, 10 * ms],
                      ["bench.engine", 10 * ms, 90 * ms],
                      ["simjax.pack_batch", 60 * ms, 10 * ms],
                      ["simjax.sync", 70 * ms, 20 * ms]]}
    monkeypatch.setattr(trace_reduce, "load", lambda d, programs=(): fake)
    monkeypatch.setattr(harness, "TRACE_SECONDS", 0.0)
    line = _run(tiny, trace=True)
    assert line["correct"] is True
    assert set(line["metrics"]) == set(tiny["per_layer"])
    assert line["metrics"]["device_idle_share"]["value"] == pytest.approx(0.5)
    for name in ("waves_per_step", "cascade_iters_per_step",
                 "window_step_util"):
        assert 0 < line["metrics"][name]["value"] < 100
    assert line["metrics"]["window_step_util"]["value"] <= 1
    assert line["device"]["busy_s"] == pytest.approx(0.05)
    assert line["device"]["window_s"] == pytest.approx(0.1)
    want = {"device_ops": {"%fusion.2": 0.02, "%fusion.3": 0.01,
                           "%while.1": 0.01, "%fusion.4": 0.005,
                           "%fusion.5": 0.005},
            "idle_gaps": {"simjax.sync": 0.02, "bench.engine": 0.01,
                          "bench.build": 0.01, "simjax.pack_batch": 0.01},
            "device_phases": {"simjax.madd": 0.02, "simjax.backfill": 0.01,
                              "unscoped": 0.01, "simjax.horizon": 0.005,
                              "simjax.settle": 0.005}}
    assert set(line["breakdown"]) == set(want)
    for key, got in line["breakdown"].items():
        assert dict(got) == pytest.approx(want[key])
        assert [v for _, v in got] == sorted((v for _, v in got),
                                             reverse=True)
    assert list(line)[-1] == "checks"
