"""A traced run on the CPU reads its host metrics and leaves the device
metrics out: a reader that finds nothing returns nothing."""
import json
import pathlib

import perfbench.run as cli


def test_traced_run_reports_what_it_can_read(tiny_root):
    from perfbench.harness.cell import Run
    run = Run("pubmed300-steady", 11, 30.0, True, tiny_root,
              require_tpu=False)
    try:
        assert run.seconds == run.spec["trace_seconds"]
        run.peak = lambda: json.loads(
            (pathlib.Path(cli.__file__).parent / "peaks.json").read_text()
        )["TPU v5 lite"]
        run.window()
        line = cli.result_line(run, run.check())
    finally:
        run.close()
    assert line["correct"]
    names = set(line["metrics"])
    assert {"queue_wait_ms.steady", "decide_ms.steady",
            "transfer_ms.steady", "gc_pause_ms.steady",
            "serve_mfu.steady"} <= names
    # no TPU plane in a CPU trace: the device readers stay silent
    assert not names & {"offload_device_ms.steady", "idle_share.steady",
                        "forward_roofline.steady"}
    assert list(line)[-1] == "checks"


def test_cell_metrics_follow_benchmark_json():
    bench = json.loads((pathlib.Path(cli.__file__).parents[1]
                        / "BENCHMARK.json").read_text())
    for cell in bench["workloads"]:
        e2e = {m["name"] for m in cli.cell_metrics(bench, cell["name"],
                                                   False)}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = cli.cell_metrics(bench, cell["name"], True)
        assert layer
        assert all(m["moves"] in e2e for m in layer)
