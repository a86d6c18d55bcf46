"""Find a cell's knee: one set-up, then one window per offered rate.

    python3 perfbench/sweep.py --workload pubmed300-steady \
        --rates 80,120,160,200 --seconds 15 --seed 5

For each rate the cell's traffic is offered open-loop for ``--seconds``
(same layouts, features and batching; only the rate differs) and the
backlog left at the close is refused, so a rate above the knee does not
drain. Prints one JSON line per rate: offered and answered rates, the
latency median and 95th percentile over the requests answered, the backlog
at the close and the cycle statistics. The knee is the highest rate whose
answered rate keeps up with the offered one and leaves no growing backlog.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from perfbench.harness.cell import Run
    rates = [float(r) for r in args.rates.split(",")]
    run = Run(args.workload, args.seed, args.seconds, False, ROOT)
    run.shed = True
    print("setup " + json.dumps(run.setup), flush=True)
    for rate in rates:
        run.retarget(rate)
        run.window()
        checks = run.check()
        e2e = run.end_to_end()
        att = run.attribution()
        print(json.dumps({
            "workload": args.workload, "offered_rps": rate,
            "answered_rps": e2e["throughput_rps"],
            "latency_p50_ms": e2e["latency_p50_ms"],
            "latency_p95_ms": e2e["latency_p95_ms"],
            "left_queued_at_close": att["left_queued_at_close"],
            "cycles": att["cycles"], "batch_mean": len(run.results)
            / max(att["cycles"], 1),
            "cycle_median_ms": att["cycle_median_ms"],
            "cycle_longest_ms": att["cycle_longest_ms"],
            "window_compiles": att["window_compiles"],
            "gc_ms": att["gc"]["total_ms"],
            "correct": all(c["value"] <= c["limit"]
                           for c in checks.values())}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
