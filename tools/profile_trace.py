"""Profiling lane: capture a ``jax.profiler`` trace of a hot path.

Writes a TensorBoard-loadable trace directory (``xplane.pb`` under
``plugins/profile/<run>/``) for one of three workloads:

* ``fused_aggregate`` — the fused gather–normalize–matmul kernel vs the
  unfused gather-kernel + matmul pair on the BENCH_kernels n=5000 shape
  (jitted; compiled on a TPU, interpret mode on the CPU);
* ``kernels``        — the whole ``benchmarks/bench_kernels.py`` quick run;
* ``serving``        — the whole ``benchmarks/bench_serving.py`` quick run.

Usage (from the repo root)::

    python tools/profile_trace.py --workload fused_aggregate --out /tmp/tr
    python tools/profile_trace.py --workload serving --out /tmp/tr

The per-bench ``--profile DIR`` flags on ``benchmarks/bench_kernels.py``
and ``benchmarks/bench_serving.py`` capture the same traces without this
wrapper. Load the output with ``tensorboard --logdir DIR`` (or
``xprof``); on the CPU the trace shows XLA/interpreter op spans, on a
TPU the same lane captures device timelines.
"""
from __future__ import annotations

import argparse
import pathlib
import sys

_ROOT = pathlib.Path(__file__).resolve().parent.parent
for p in (str(_ROOT / "src"), str(_ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


def _trace_fused_aggregate(out: str) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.data.graphs import random_graph
    from repro.gnn.layers import gcn_norm_sparse
    from repro.kernels.gnn_aggregate.ops import (fused_gather_aggregate,
                                                 gather_aggregate,
                                                 sort_neighbor_slots)

    n, e, f = 5000, 50_000, 64
    rng = np.random.default_rng(0)
    g = random_graph(n, e, seed=1)
    idx, val, dinv = gcn_norm_sparse(g.edges, n)
    idx, val = sort_neighbor_slots(idx, val)
    x = jnp.asarray(rng.normal(size=(n, f)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(f, f)).astype(np.float32) * 0.1)
    ij, vj, dj = jnp.asarray(idx), jnp.asarray(val), jnp.asarray(dinv)
    impl = "interpret" if jax.default_backend() == "cpu" else "pallas"
    fused = jax.jit(lambda xx: fused_gather_aggregate(
        ij, vj, xx, dj, dj, w, impl=impl))
    unfused = jax.jit(lambda xx: gather_aggregate(
        ij, vj, xx, dj, dj, impl=impl) @ w)
    fused(x).block_until_ready()        # compile outside the trace
    unfused(x).block_until_ready()
    with jax.profiler.trace(out):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("fused_kernel"):
                fused(x).block_until_ready()
            with jax.profiler.TraceAnnotation("unfused_kernel_matmul"):
                unfused(x).block_until_ready()


def main() -> None:
    ap = argparse.ArgumentParser(
        description="capture a jax.profiler trace of a hot path")
    ap.add_argument("--workload", required=True,
                    choices=["fused_aggregate", "kernels", "serving"])
    ap.add_argument("--out", required=True, metavar="DIR",
                    help="trace output directory (TensorBoard logdir)")
    args = ap.parse_args()

    if args.workload == "fused_aggregate":
        _trace_fused_aggregate(args.out)
    elif args.workload == "kernels":
        from benchmarks import bench_kernels
        bench_kernels.run(quick=True, profile_dir=args.out)
    else:
        from benchmarks import bench_serving
        bench_serving.run(quick=True, profile_dir=args.out)

    arts = sorted(str(p.relative_to(args.out))
                  for p in pathlib.Path(args.out).rglob("*") if p.is_file())
    print(f"trace artifacts under {args.out}:")
    for a in arts:
        print(f"  {a}")


if __name__ == "__main__":
    main()
