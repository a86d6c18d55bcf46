"""Multi-host SPMD serving launcher — simulated process grids on one box.

    PYTHONPATH=src python -m repro.launch.serve_multihost --processes 2 \
        --devices 4 --vertices 100000 --edges 300000 --steps 5

Promotes serving to true SPMD over a process grid (DESIGN.md §8): the
parent spawns ``--processes`` worker copies of itself, each pinned to
``--devices / --processes`` virtual CPU devices
(``--xla_force_host_platform_device_count``), wired together with
``jax.distributed.initialize`` over a local coordinator and the gloo CPU
collectives backend. Every worker builds only its own shard of the
partition plan (:func:`repro.gnn.multihost.make_partition_plan_shard`),
keeps its feature blocks resident (:func:`put_feature_blocks`), and the
forward exchanges *only halo rows* between processes — an ``all_to_all``
over exactly the cut edges (``--exchange pair``; ``gather`` serves the
all-gather layout for comparison).

Two arms share every flag:

* ``--arm resident`` — the multi-host path: sharded plan cache
  (:class:`repro.gnn.multihost.ShardedPlanCache`, keyed identically on
  every process), resident features, halo-only exchange. Outputs stay
  sharded on their owning hosts.
* ``--arm engine`` — the single-process serving engine's data path on the
  same graph (one full plan build, per-step ``plan.scatter`` → jitted
  forward on replicated blocks → ``plan.gather``): the replicate-
  everything baseline the bench compares against. Single process only.

Process 0 prints one JSON record (steps/sec, halo vs replicate bytes per
step, parity against ``--ref-in``); ``--json-out`` also writes it to a
file — that is the interface ``benchmarks/bench_serving.py``'s multihost
arm drives. ``--ref-out`` saves the gathered output for cross-host-count
parity: resident arms at different ``--processes`` must match **bitwise**
(the collectives only move rows; every per-device instruction sequence is
identical).

Importing this module has no side effects; env mutation happens inside
worker ``main`` before jax is imported (same contract as ``serve_gnn``).
With ``--processes 1`` nothing is spawned: ``main(argv)`` runs the worker
in the caller's process, so a process that already holds the chips can
call it directly.
"""
from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import time


def _parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--processes", type=int, default=1,
                    help="simulated hosts (spawned worker subprocesses)")
    ap.add_argument("--devices", type=int, default=4,
                    help="total mesh devices across all processes")
    ap.add_argument("--vertices", type=int, default=100_000)
    ap.add_argument("--edges", type=int, default=300_000)
    ap.add_argument("--cross-frac", type=float, default=0.01,
                    help="fraction of cross-community edge draws")
    ap.add_argument("--features", type=int, default=32)
    ap.add_argument("--hidden", type=int, default=16)
    ap.add_argument("--classes", type=int, default=5)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--arm", choices=("resident", "engine"),
                    default="resident")
    ap.add_argument("--exchange", choices=("pair", "gather"),
                    default="pair")
    ap.add_argument("--aggregate", default="auto")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json-out", default="",
                    help="write process 0's JSON record to this path")
    ap.add_argument("--ref-out", default="",
                    help="save the gathered output (.npy) for parity")
    ap.add_argument("--ref-in", default="",
                    help="compare the output against this .npy (max err)")
    ap.add_argument("--quick", action="store_true",
                    help="shrink to a smoke-size graph")
    # internal: set by the spawning parent for worker subprocesses
    ap.add_argument("--process-id", type=int, default=None,
                    help=argparse.SUPPRESS)
    ap.add_argument("--coordinator", default="",
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.quick:
        args.vertices = min(args.vertices, 20_000)
        args.edges = min(args.edges, 60_000)
    return args


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _spawn(args: argparse.Namespace) -> int:
    """Parent: launch one worker per simulated host and relay process 0."""
    port = _free_port()
    per = args.devices // args.processes
    assert per * args.processes == args.devices, \
        (args.devices, args.processes)
    cmd_base = [sys.executable, "-m", "repro.launch.serve_multihost",
                "--coordinator", f"127.0.0.1:{port}"]
    passthrough = ["--processes", str(args.processes),
                   "--devices", str(args.devices),
                   "--vertices", str(args.vertices),
                   "--edges", str(args.edges),
                   "--cross-frac", str(args.cross_frac),
                   "--features", str(args.features),
                   "--hidden", str(args.hidden),
                   "--classes", str(args.classes),
                   "--steps", str(args.steps),
                   "--arm", args.arm, "--exchange", args.exchange,
                   "--aggregate", args.aggregate,
                   "--seed", str(args.seed)]
    for opt, val in (("--json-out", args.json_out),
                     ("--ref-out", args.ref_out),
                     ("--ref-in", args.ref_in)):
        if val:
            passthrough += [opt, val]
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={per}"
    src = os.path.join(os.path.dirname(__file__), "..", "..")
    env["PYTHONPATH"] = os.path.abspath(src) + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    procs = [subprocess.Popen(
        cmd_base + passthrough + ["--process-id", str(i)],
        env=env, stdout=subprocess.PIPE if i else None,
        stderr=subprocess.STDOUT if i else None)
        for i in range(args.processes)]
    rc = 0
    for i, pr in enumerate(procs):
        out, _ = pr.communicate(timeout=1800)
        if pr.returncode != 0:
            rc = pr.returncode or 1
            if out:
                sys.stderr.write(out.decode(errors="replace")[-4000:])
    return rc


def _worker(args: argparse.Namespace) -> int:
    nproc = args.processes
    pid = args.process_id or 0
    if "jax" not in sys.modules and \
            "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count"
            f"={args.devices // nproc}").strip()
    import jax
    if nproc > 1:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(args.coordinator, nproc, pid)
    from repro.launch import enable_compile_cache
    enable_compile_cache()
    import numpy as np
    from jax.sharding import Mesh

    from repro.data.graphs import community_graph
    from repro.gnn.layers import gcn_init
    from repro.gnn.multihost import (ShardedPlanCache, fetch_global,
                                     put_feature_blocks)

    assert len(jax.devices()) == args.devices, \
        (len(jax.devices()), args.devices)
    mesh = Mesh(np.array(jax.devices()), ("servers",))
    n = args.vertices
    edges, assign = community_graph(n, args.edges, args.devices,
                                    cross_frac=args.cross_frac,
                                    seed=args.seed)
    rng = np.random.default_rng(args.seed + 1)
    x = rng.normal(size=(n, args.features)).astype(np.float32)
    dims = [args.features, args.hidden, args.classes]
    params = gcn_init(jax.random.PRNGKey(args.seed), dims)
    layer_widths = dims[1:]          # exchanged row width per layer (dense/
    #                                  sparse aggregate post-matmul widths)

    t0 = time.perf_counter()
    if args.arm == "resident":
        cache = ShardedPlanCache(mesh, "servers", exchange=args.exchange,
                                 aggregate=args.aggregate)
        _, shard, forward, _ = cache.entry(edges, assign, args.devices)
        plan_s = time.perf_counter() - t0
        xb = put_feature_blocks(mesh, "servers", shard, x)
        out = jax.block_until_ready(forward(xb, params))     # warm compile
        # verify the shard caches agree across hosts (keyed identically)
        _, _, _, hit = cache.entry(edges, assign, args.devices)
        assert hit, "plan shard cache must hit on the same topology"
        t0 = time.perf_counter()
        for _ in range(args.steps):
            out = jax.block_until_ready(forward(xb, params))
        dt = time.perf_counter() - t0
        gathered = shard.gather(fetch_global(out))
        halo, block = shard.halo, shard.block
        pb = shard.bytes_per_aggregate
        rb = shard.replicate_bytes_per_aggregate
    else:
        assert nproc == 1, "--arm engine is the single-process baseline"
        from repro.gnn.distributed import (make_forward_fn,
                                           make_partition_plan_sparse)
        plan = make_partition_plan_sparse(edges, assign, args.devices, n=n,
                                          exchange=args.exchange)
        forward = make_forward_fn(mesh, "servers", plan, args.aggregate)
        plan_s = time.perf_counter() - t0
        gathered = plan.gather(np.asarray(
            forward(plan.scatter(x), params)))               # warm compile
        t0 = time.perf_counter()
        for _ in range(args.steps):
            out = forward(plan.scatter(x), params)
            gathered = plan.gather(np.asarray(out))
        dt = time.perf_counter() - t0
        halo, block = plan.halo, plan.block
        pb = plan.bytes_per_aggregate
        rb = plan.replicate_bytes_per_aggregate

    rec = {
        "mode": "multihost", "arm": args.arm, "hosts": nproc,
        "devices": args.devices, "n": n, "edges": int(len(edges)),
        "exchange": args.exchange, "block": int(block), "halo": int(halo),
        "steps": args.steps, "steps_per_s": args.steps / dt,
        "output_devices": len(out.sharding.device_set),
        "plan_build_s": plan_s,
        "halo_bytes_per_step": sum(pb(w) for w in layer_widths),
        "replicate_bytes_per_step": sum(rb(w) for w in layer_widths),
    }
    rec["halo_frac"] = (rec["halo_bytes_per_step"]
                        / max(rec["replicate_bytes_per_step"], 1))
    if args.ref_in:
        ref = np.load(args.ref_in)
        rec["parity_max_err"] = float(np.abs(gathered - ref).max())
    if pid == 0:
        if args.ref_out:
            np.save(args.ref_out, gathered)
        line = json.dumps(rec)
        print(line, flush=True)
        if args.json_out:
            with open(args.json_out, "w") as f:
                f.write(line + "\n")
    return 0


def main(argv=None) -> int:
    args = _parse_args(argv)
    if args.process_id is None and args.processes > 1:
        return _spawn(args)
    return _worker(args)


if __name__ == "__main__":
    raise SystemExit(main())
