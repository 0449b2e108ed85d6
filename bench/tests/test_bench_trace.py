"""The reduction from a profiler trace to busy time, top device ops and
idle gaps by host span: on a hand-made trace with a known answer, and
on a slice of a trace recorded on a TPU v5e; and the loading of the
benchmark's spans from a trace taken on the CPU."""

from __future__ import annotations

import json
import math
from pathlib import Path

import pytest

from bench import trace_reduce

HERE = Path(__file__).resolve().parent
MS = 1e6                       # trace times are in nanoseconds


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
    assert [k for k, _ in out["device_ops"]] == ["A", "B", "C"]
    assert [v for _, v in out["device_ops"]] == pytest.approx(
        [0.035, 0.018, 0.010])
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


def test_recorded_tpu_slice():
    """The first 3,000 device ops of a traced ``mixed48.heavy8`` run on a
    TPU v5e, with the benchmark spans clipped to them."""
    tr = json.loads((HERE / "trace_v5e_mixed48_slice.json").read_text())
    out = trace_reduce.reduce(tr)
    assert 0 < out["busy_s"] < out["window_s"]
    assert 0 < len(out["device_ops"]) <= trace_reduce.TOP
    assert all(v > 0 and math.isfinite(v) for _, v in out["device_ops"])
    assert {k for k, _ in out["idle_gaps"]} <= {
        "bench.build", "bench.pack", "bench.engine", "between cells"}
    idle = sum(v for _, v in out["idle_gaps"])
    assert out["busy_s"] + idle == pytest.approx(out["window_s"], rel=1e-9)
    assert out == json.loads(
        (HERE / "trace_v5e_mixed48_slice.reduced.json").read_text())
