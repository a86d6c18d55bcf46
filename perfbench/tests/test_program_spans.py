"""The readers of the program's own ``ge.*`` spans: their arithmetic on a
small synthetic trace (nested spans on two host threads, device ops), the
device-idle attribution to program spans, silence on a trace without
them, and a traced CPU run that reports them."""
import json
import pathlib
from types import SimpleNamespace

import pytest

import perfbench.run as cli
from perfbench.harness import spans as program
from perfbench.harness import trace

DATA = pathlib.Path(__file__).resolve().parent / "data"
NEW = ("decide_host_ms.steady", "device_wait_ms.steady",
       "forward_dispatch_ms.steady", "partition_miss_ms.churn")


def _trace():
    """A 1000-ns window; two cycles inside it and one before it."""
    main = [
        # before the window: counted by nothing
        ["ge.frontend.cycle", -100.0, 90.0],
        ["ge.control.decide", -90.0, 70.0],
        ["ge.control.partition", -85.0, 20.0],
        ["bench.partition", -84.0, 10.0],
        ["ge.control.wait", -60.0, 20.0],
        ["ge.forward.dispatch", -30.0, 10.0],
        # cycle 1
        ["ge.frontend.cycle", 100.0, 300.0],
        ["ge.control.decide", 110.0, 190.0],
        ["ge.control.partition", 115.0, 35.0],       # a miss: it cuts
        ["bench.partition", 120.0, 25.0],
        ["ge.control.partition", 150.0, 5.0],        # a hit
        ["ge.control.dispatch", 160.0, 20.0],
        ["ge.control.wait", 180.0, 80.0],
        ["ge.control.unpack", 260.0, 30.0],
        ["ge.forward.dispatch", 310.0, 20.0],
        ["ge.transfer.fetch", 330.0, 50.0],
        # cycle 2
        ["ge.frontend.cycle", 500.0, 200.0],
        ["ge.control.decide", 510.0, 90.0],
        ["ge.control.partition", 512.0, 3.0],        # a hit
        ["ge.control.wait", 520.0, 40.0],
        ["ge.forward.dispatch", 610.0, 30.0],
        ["ge.forward.dispatch", 640.0, 10.0],
        ["ge.transfer.fetch", 650.0, 40.0],
        ["ge.frontend.idle", 720.0, 80.0]]
    producer = [["bench.window", 0.0, 1000.0],
                ["ge.control.wait", 200.0, 10.0],     # another thread
                ["bench.submit", 450.0, 20.0]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit__jit_offload_and_cost(1)", 180.0, 80.0],
                ["jit__forward_blocks(2)", 330.0, 40.0]]},
            {"name": "XLA Ops", "events": [
                ["fusion.1", 180.0, 80.0], ["fusion.2", 330.0, 40.0],
                ["fusion.3", 520.0, 40.0], ["fusion.4", 650.0, 40.0]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": main},
            {"name": "producer", "events": producer}]}]}


def _run(raw, dispatches=(), t0=10.0, t_end=10.000001):
    cycles = SimpleNamespace(as_dict=lambda: {"slowest": [{"cycle": 7}]})
    return SimpleNamespace(
        raw_trace=raw, notes={}, t0=t0, t_end=t_end,
        frontend=SimpleNamespace(cycles=cycles),
        results=[SimpleNamespace(timing=SimpleNamespace(dispatch=d))
                 for d in dispatches])


def test_decide_host_time_leaves_out_the_wait_on_its_own_thread():
    # cycle 1: 190 − 80 (the producer's wait is not a child); cycle 2:
    # 90 − 40; the decide before the window is not counted
    got = cli.reader("decide_host_ms.steady")(_run(_trace()))
    assert got == pytest.approx(1e-6 * (110 + 50) / 2)


def test_device_wait_is_wait_and_fetch_per_cycle():
    got = cli.reader("device_wait_ms.steady")(_run(_trace()))
    assert got == pytest.approx(1e-6 * ((80 + 50) + (40 + 40)) / 2)


def test_forward_dispatch_is_per_request_dispatched_in_the_window():
    run = _run(_trace(), dispatches=(10.0000003, 10.0000003, 10.0000007,
                                     9.9, 10.5))
    got = cli.reader("forward_dispatch_ms.steady")(run)
    assert got == pytest.approx(1e-6 * (20 + 30 + 10) / 3)
    assert cli.reader("forward_dispatch_ms.steady")(_run(_trace())) is None


def test_partition_miss_is_a_span_that_cuts():
    got = cli.reader("partition_miss_ms.churn")(_run(_trace()))
    assert got == pytest.approx(1e-6 * 35)


def test_idle_gaps_go_to_the_innermost_program_span():
    run = _run(_trace())
    program.read(run)
    gaps = dict(run.notes["program_idle_gaps"])
    assert run.notes["slowest_cycles"] == [{"cycle": 7}]
    want = {trace.OUTSIDE: 420, "ge.frontend.idle": 80,
            "ge.frontend.cycle": 70, "ge.control.decide": 67,
            "ge.control.partition": 43, "ge.control.dispatch": 20,
            "ge.control.unpack": 30, "ge.forward.dispatch": 60,
            "ge.transfer.fetch": 10}
    assert gaps == pytest.approx({k: v * 1e-9 for k, v in want.items()})
    reduced = trace.reduce(_trace())
    assert sum(gaps.values()) == pytest.approx(reduced["window_s"]
                                               - reduced["busy_s"])


def test_program_spans_leave_the_reduction_as_it_was():
    bare = _trace()
    for line in bare["planes"][1]["lines"]:
        line["events"] = [e for e in line["events"]
                          if not e[0].startswith(program.PREFIX)]
    assert trace.reduce(_trace()) == trace.reduce(bare)


def test_a_program_without_spans_reads_nothing():
    """The recorded TPU slice predates the program's spans: every new
    reader stays silent and notes nothing."""
    raw = json.loads((DATA / "tpu_trace_slice.json").read_text())
    run = _run(raw, dispatches=(10.0000005,))
    for name in NEW:
        assert cli.reader(name)(run) is None
    assert run.notes == {}


def test_traced_cpu_runs_report_the_program_span_metrics(tiny_root):
    from perfbench.harness.cell import Run
    for workload, names in (("pubmed300-steady", NEW[:3]),
                            ("pubmed300-churn", NEW[3:])):
        run = Run(workload, 2**31 + 5, 30.0, True, tiny_root,
                  require_tpu=False)
        run.peak = lambda: json.loads(
            (DATA.parents[1] / "peaks.json").read_text())["TPU v5 lite"]
        # the small churn cell's 12 layouts fit the partition cache: empty
        # it, so the window re-cuts each layout once
        run.engine.controller.invalidate_partitions()
        try:
            run.window()
            line = cli.result_line(run, run.check())
        finally:
            run.close()
        for name in names:
            assert line["metrics"][name]["value"] > 0, name
        assert run.notes["slowest_cycles"]
