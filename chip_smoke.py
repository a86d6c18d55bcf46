"""Smoke test of the GraphEdge served path on a TPU, through its entry points.

    python chip_smoke.py             # one chip: stream + dataset phases
    python chip_smoke.py --chips 4   # four chips: 4-device stream phase and
                                     # the resident-vs-engine multihost arms

One process drives everything: the launchers' ``main(argv)`` run in-process,
so no child ever competes for the chip.

Default phases (one chip):

* ``stream`` — ``repro.launch.serve_stream``: StreamingFrontend →
  ServingEngine → GraphEdgeController (hicut_jax + greedy_jit) →
  ``gnn.distributed``. 300 users with 4,800 links (the paper's PubMed
  sample density), a 500 → 16 → 3 GCN, 4 edge servers folded onto the
  chip, 32 open-loop requests over 2 topologies under ``lyapunov``
  admission; then ``stream_faults``, a shorter run with a
  ``server_down``/``server_up`` pair.
* ``dataset`` — ``repro.launch.serve_gnn --dataset synth-pubmed``: 19,717
  vertices at the dataset's own 500 input features through the sparse plan.

``--chips 4`` runs only the ``stream`` phase over a 4-device mesh (the halo
exchange crosses chips) and ``repro.launch.serve_multihost`` in one process
with ``--arm resident`` checked against ``--arm engine`` on the same graph.

Every phase checks its outputs against the float32 reference at "highest"
matmul precision within the launchers' named bound and prints one
``phase {...}`` line: wall seconds, the part of them spent compiling,
requests served, plan-cache hits and misses, the max error and its bound.
The last line of stdout is ``{"ok": true, "device": {...}}``; it is printed
only when JAX finds a TPU and every phase passed.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parent
SRC = ROOT / "src"

# the published shapes: PubMed's 500 features and 3 classes, the paper's
# 300-document / 4,800-link sample, 4 edge servers
FULL = {"users": 300, "links": 4800, "features": 500, "hidden": 16,
        "classes": 3, "servers": 4, "count": 32, "fault_count": 16,
        "dataset": ["--dataset", "synth-pubmed"],
        "multihost": ["--vertices", "100000", "--edges", "300000",
                      "--steps", "3"]}

_COMPILE_EVENTS = "/jax/core/compile/"
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or loading from
    the persistent cache) while it is open, from ``jax.monitoring``."""

    def __enter__(self):
        import jax
        self.seconds, self.compiles = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._record)
        return self

    def _record(self, event, duration, **_):
        if event.startswith(_COMPILE_EVENTS):
            self.seconds += duration
            self.compiles += event == _BACKEND_COMPILE

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._record)


def run_phase(name: str, fn) -> None:
    """Run one phase and print its ``phase`` line; raise if it failed."""
    with CompileClock() as clock:
        t0 = time.perf_counter()
        summary = fn()
        wall = time.perf_counter() - t0
    line = {"phase": name, "wall_s": wall, "compile_s": clock.seconds,
            "compiles": clock.compiles,
            "served": summary.get("served"),
            "submitted": summary.get("submitted", summary.get("served")),
            "devices": summary.get("devices"),
            "plan_cache_hits": summary.get("plan_cache_hits"),
            "plan_cache_misses": summary.get("plan_cache_misses"),
            "max_err": summary["max_err"], "bound": summary["bound"]}
    print("phase " + json.dumps(line), flush=True)
    if not (summary["max_err"] < summary["bound"]):   # NaN fails too
        raise AssertionError(f"{name}: max error {summary['max_err']} "
                             f"not below {summary['bound']}")
    if not summary.get("served"):
        raise AssertionError(f"{name}: served no request")


def _stream_argv(cfg: dict, devices: int, count: int, seed: int) -> list:
    return ["--users", str(cfg["users"]), "--links", str(cfg["links"]),
            "--features", str(cfg["features"]),
            "--hidden", str(cfg["hidden"]), "--classes", str(cfg["classes"]),
            "--devices", str(devices), "--count", str(count),
            "--topologies", "2", "--admission", "lyapunov",
            "--arrival-rate", "50", "--deadline", "0", "--seed", str(seed)]


def stream_phases(cfg: dict, devices: int, seed: int = 0,
                  faults: bool = True) -> None:
    """The streaming front-end over ``devices`` mesh devices, and (with
    ``faults``) a shorter run with one server failing and recovering."""
    from repro.launch import serve_stream
    run_phase("stream", lambda: serve_stream.main(
        _stream_argv(cfg, devices, cfg["count"], seed)))
    if faults:
        argv = _stream_argv(cfg, devices, cfg["fault_count"], seed + 1)
        argv += ["--faults", "1:server_down:1,2:server_up:1"]
        run_phase("stream_faults", lambda: serve_stream.main(argv))


def dataset_phase(cfg: dict, seed: int = 0) -> None:
    """One large graph served whole through the sparse plan."""
    from repro.launch import serve_gnn
    argv = cfg["dataset"] + ["--devices", str(cfg["servers"]),
                             "--hidden", str(cfg["hidden"]),
                             "--seed", str(seed)]
    run_phase("dataset", lambda: serve_gnn.main(argv))


def multihost_phase(cfg: dict, devices: int, seed: int = 0) -> None:
    """``serve_multihost`` in this process: the replicate-everything
    ``engine`` arm writes the reference, the sharded ``resident`` arm is
    compared with it."""
    from repro.launch import serve_gnn, serve_multihost
    base = ["--processes", "1", "--devices", str(devices),
            "--features", str(cfg["features"]), "--hidden", str(cfg["hidden"]),
            "--classes", str(cfg["classes"]), "--seed", str(seed)]
    base += cfg["multihost"]
    bound = serve_gnn.oracle_bound()
    with tempfile.TemporaryDirectory() as td:
        ref, rec = os.path.join(td, "ref.npy"), os.path.join(td, "rec.json")

        def arm(name, extra):
            def fn():
                rc = serve_multihost.main(
                    base + ["--arm", name, "--json-out", rec] + extra)
                if rc != 0:
                    raise RuntimeError(f"serve_multihost --arm {name}: {rc}")
                with open(rec) as f:
                    r = json.load(f)
                print(f"multihost {name}: {json.dumps(r)}", flush=True)
                if r["output_devices"] != devices:
                    raise AssertionError(f"{name}: output on "
                                         f"{r['output_devices']} of "
                                         f"{devices} devices")
                return {"served": r["steps"], "devices": r["devices"],
                        "max_err": r.get("parity_max_err", 0.0),
                        "bound": bound}
            return fn

        run_phase("multihost_engine", arm("engine", ["--ref-out", ref]))
        run_phase("multihost_resident", arm("resident", ["--ref-in", ref]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the paths that span four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    from repro.launch import enable_compile_cache
    enable_compile_cache()

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devs[0].platform}",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devs)} device(s)", file=sys.stderr)
        return 1

    if args.chips == 4:
        stream_phases(FULL, devices=4, seed=args.seed, faults=False)
        multihost_phase(FULL, devices=4, seed=args.seed)
    else:
        stream_phases(FULL, devices=FULL["servers"], seed=args.seed)
        dataset_phase(FULL, seed=args.seed)

    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
