"""The trace reduction: busy union, per-program and per-op device time, and
idle gaps attributed to the benchmark's host spans."""
import json
import pathlib

import pytest

from perfbench.harness import trace

DATA = pathlib.Path(__file__).resolve().parent / "data"


def _trace():
    """A window of 100 ns; two programs, overlapping ops, nested spans."""
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit__forward_blocks(123)", 10.0, 20.0],
                ["jit__jit_offload_and_cost_batch(9)", 50.0, 30.0],
                ["jit__forward_blocks(124)", 95.0, 20.0]]},   # starts late
            {"name": "XLA Ops", "events": [
                ["%fusion.3 = f32[4] fusion(x)", 10.0, 12.0],
                ["copy.1", 18.0, 12.0],                 # overlaps fusion
                ["while.2", 50.0, 30.0],                # encloses fusion.7
                ["%custom-call.4 = f32[8] custom-call()", 52.0, 0.0],
                ["fusion.7", 55.0, 20.0],
                ["fusion", 95.0, 20.0]]}]},            # cut at 100
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [
                ["bench.window", 0.0, 100.0],
                ["bench.pump", 0.0, 60.0],
                ["bench.decide", 30.0, 25.0],
                ["PjitFunction(f)", 31.0, 2.0]]},
            {"name": "producer", "events": [
                ["bench.submit", 85.0, 5.0]]}]}]}


def test_busy_is_the_union_of_ops_inside_the_window():
    r = trace.reduce(_trace())
    # [10, 30) ∪ [50, 80) ∪ [95, 100) = 55 ns
    assert r["busy_s"] == pytest.approx(55e-9)
    assert r["window_s"] == pytest.approx(100e-9)


def test_programs_count_whole_by_their_start():
    mods = trace.reduce(_trace())["modules"]
    assert mods["jit__forward_blocks"] == {"calls": 2,
                                           "seconds": pytest.approx(40e-9)}
    assert mods["jit__jit_offload_and_cost_batch"]["calls"] == 1


def test_ops_are_named_by_program_and_kind_by_self_time():
    r = trace.reduce(_trace())
    ops = dict(r["device_ops"])
    # the loop keeps what its body leaves uncovered
    assert ops["jit__jit_offload_and_cost_batch:fusion"] == \
        pytest.approx(20e-9)
    assert ops["jit__jit_offload_and_cost_batch:while"] == \
        pytest.approx(10e-9)
    # an overlap of equal-length ops goes to the one that started first
    assert ops["jit__forward_blocks:fusion"] == pytest.approx(12e-9 + 5e-9)
    assert ops["jit__forward_blocks:copy"] == pytest.approx(8e-9)
    assert sum(ops.values()) == pytest.approx(r["busy_s"])


def test_idle_gaps_go_to_the_innermost_span():
    gaps = dict(trace.reduce(_trace())["idle_gaps"])
    # idle: [0,10) pump, [30,50) pump 30..30 + decide 30..50,
    # [80,95): decide ends 55 → 80..85 outside, 85..90 submit, 90..95 out
    assert gaps["bench.pump"] == pytest.approx(10e-9)
    assert gaps["bench.decide"] == pytest.approx(20e-9)
    assert gaps["bench.submit"] == pytest.approx(5e-9)
    assert gaps[trace.OUTSIDE] == pytest.approx(10e-9)
    assert sum(gaps.values()) == pytest.approx(45e-9)


def test_no_window_or_no_device_reads_nothing():
    t = _trace()
    t["planes"][1]["lines"][0]["events"].pop(0)
    assert trace.reduce(t) is None
    assert trace.reduce({"planes": [_trace()["planes"][1]]}) is None


def test_recorded_tpu_trace_slice():
    """A slice of a traced run on a TPU v5e (pubmed300-churn), reduced."""
    raw = json.loads((DATA / "tpu_trace_slice.json").read_text())
    r = trace.reduce(raw)
    assert 0 < r["busy_s"] <= r["window_s"]
    names = set(r["modules"])
    assert any(n.startswith("jit__forward_blocks") for n in names)
    gaps = dict(r["idle_gaps"])
    idle = r["window_s"] - r["busy_s"]
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-6)
    assert all(n.startswith("bench.") or n == trace.OUTSIDE for n in gaps)
