"""Readings that set a cell's limits: the program and its control on many
seeds, in one process.

    python3 perfbench/readings.py --workload pubmed300-steady \
        --seeds 11,12,13 --seconds 45

For each seed a full run of the cell (its own load, ``--seconds`` long) is
compared with the references twice: as the program answered, and with the
control in the program's place, the reference one precision step below the
configuration's (``Run.check(control=True)``). One JSON line per seed holds both sets of numbers
and the run's end-to-end metrics. Later seeds reuse the first one's
compiled programs.
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from perfbench.harness.cell import Run
    for seed in (int(s) for s in args.seeds.split(",")):
        run = Run(args.workload, seed, args.seconds, False, ROOT)
        run.window()
        program = run.check()
        control = run.check(control=True)
        run.close()
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "program": {k: v["value"] for k, v in program.items()},
            "control": {k: v["value"] for k, v in control.items()},
            "end_to_end": run.end_to_end(),
            "window": run.attribution()}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
