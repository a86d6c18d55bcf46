"""Run one cell of the chip benchmark once.

    python3 perfbench/run.py --workload pubmed300-steady --seed 7 \
        --seconds 45 --trace 0

Reads the cell from ``BENCHMARK.json`` at the root of the checkout, its
configuration from ``perfbench/configs/`` and its traffic from
``perfbench/traffic/``, drives the program's served path for ``--seconds``
of open-loop traffic and prints, as the last line of standard output, one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` a ``breakdown`` of the device trace, and last
``checks``, each number compared beside its limit. The line before it
starts with ``window`` and names what the host did in the window. The
checks are also the last lines of standard error.

Exits 1 with no result when JAX finds no TPU or fewer chips than the cell
asks for, and 2 when the program under test is not in the checkout.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def reader(name: str):
    """The per-layer metric's reader, ``perfbench/metrics/<name>.py``."""
    path = ROOT / "perfbench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def cell_metrics(bench: dict, workload: str, trace: bool) -> list[dict]:
    """The metrics this cell reports: its end-to-end metrics, or its
    per-layer ones (listed for it, or unlisted and moving an end-to-end
    metric it reports)."""
    def mine(m):
        return "workloads" not in m or workload in m["workloads"]
    e2e = [m for m in bench["end_to_end"] if mine(m)]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if workload in m.get("workloads", ())
            or ("workloads" not in m and m["moves"] in names)]


def result_line(run, checks: dict) -> dict:
    """The result object; ``checks`` comes last."""
    import jax
    metrics = {}
    if run.trace:
        for m in cell_metrics(run.bench, run.workload, True):
            value = reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = run.end_to_end()
        for m in cell_metrics(run.bench, run.workload, False):
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    dev = run.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": run.memory_peak}
    out = {"correct": all(c["value"] <= c["limit"] for c in checks.values()),
           "attempted": len(run.stream), "failed": run.failed,
           "metrics": metrics, "device": device}
    if run.trace and run.reduced:
        device["busy_s"] = run.reduced["busy_s"]
        device["window_s"] = run.reduced["window_s"]
        out["breakdown"] = {"device_ops": run.reduced["device_ops"],
                            "idle_gaps": run.reduced["idle_gaps"]}
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    if not (ROOT / "src" / "repro").is_dir():
        print(f"perfbench: the program under test is not in {ROOT}/src",
              file=sys.stderr)
        return 2
    from perfbench.harness.cell import NoChip, Run
    try:
        run = Run(args.workload, args.seed, args.seconds, bool(args.trace),
                  ROOT)
    except NoChip as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 1
    run.window()
    checks = run.check()
    line = result_line(run, checks)
    attribution = run.attribution() | {"notes": run.notes}
    print("window " + json.dumps(attribution), flush=True)
    print(json.dumps(line), flush=True)
    for name, c in checks.items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
