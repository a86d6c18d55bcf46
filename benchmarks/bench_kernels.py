"""Kernel microbenchmarks — the fused aggregation kernel plus the other
Pallas kernels' XLA reference paths.

The GNN-aggregate section runs the BENCH_partition graph shapes through
every layer formulation and writes **``BENCH_kernels.json``** (schema in
BENCHMARKS.md):

* **kernel vs kernel** (jitted): the fused gather–normalize–matmul
  kernel against the unfused pair — the existing
  ``gnn_gather_aggregate_pallas`` followed by the layer matmul. On a TPU
  both run compiled (``impl="pallas"``; a kernel that does not compile
  raises); on the CPU both run in the Pallas interpreter, where the ratio
  only compares interpreter costs.
* **XLA layer paths** (compiled wall-clock): fused/unfused gather layer
  vs the dense masked-SpMM layer.
* **auto-selection**: ``resolve_aggregate`` on the real partition plan;
  ``agg_speedup`` compares the dense layer against the selected path
  (exactly 1.0 by construction when "dense" is selected — the selected
  arm *is* the dense timing then).

``--quick`` / ``--full`` pick the axis sizes.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timeit, write_bench_json
from repro.core.hicut import hicut_ref
from repro.data.graphs import random_graph
from repro.gnn.distributed import (make_partition_plan_sparse,
                                   resolve_aggregate)
from repro.gnn.layers import gcn_norm_sparse
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.gnn_aggregate.autotune import get_config
from repro.kernels.gnn_aggregate.ops import (fused_gather_aggregate,
                                             gather_aggregate,
                                             normalized_aggregate,
                                             sort_neighbor_slots)
from repro.kernels.chunk_scan.ops import ssd_chunk_scan

OUT_JSON = "BENCH_kernels.json"
FEATURE_DIM = 64
DEVICES = 4
GRAPH_SEED = 1


def _best_of(fn, repeats: int = 9) -> float:
    """Min wall time of fn() in µs — kernel-vs-kernel ratios need the
    noise floor, not the median, on a busy single-core box."""
    fn()   # warmup / compile
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def _aggregate_record(n: int, e: int, rng: np.random.Generator) -> dict:
    g = random_graph(n, e, seed=GRAPH_SEED)
    idx, val, dinv = gcn_norm_sparse(g.edges, n)
    idx, val = sort_neighbor_slots(idx, val)
    k = idx.shape[1]
    x = jnp.asarray(rng.normal(size=(n, FEATURE_DIM)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(FEATURE_DIM, FEATURE_DIM)).astype(
        np.float32) * 0.1)
    ij, vj, dj = jnp.asarray(idx), jnp.asarray(val), jnp.asarray(dinv)
    cfg = get_config(n, n, FEATURE_DIM, FEATURE_DIM, k)

    # kernel vs kernel (jitted; interpret mode only on the CPU — see
    # module docstring)
    impl = "interpret" if jax.default_backend() == "cpu" else "pallas"
    fused_k = jax.jit(lambda xx: fused_gather_aggregate(
        ij, vj, xx, dj, dj, w, impl=impl))
    unfused_k = jax.jit(lambda xx: gather_aggregate(
        ij, vj, xx, dj, dj, impl=impl) @ w)
    t_fused_k = _best_of(lambda: fused_k(x).block_until_ready())
    t_unfused_k = _best_of(lambda: unfused_k(x).block_until_ready())

    # XLA layer paths (compiled wall clock; the xla lane has no fusion
    # distinction — fused impl="xla" is exactly gather + matmul)
    fused_x = jax.jit(lambda xx: fused_gather_aggregate(
        ij, vj, xx, dj, dj, w, impl="xla"))
    unfused_x = jax.jit(lambda xx: gather_aggregate(
        ij, vj, xx, dj, dj, impl="xla") @ w)
    a_hat = jnp.asarray(g.adjacency() + np.eye(n, dtype=np.float32))
    dense_x = jax.jit(lambda xx: normalized_aggregate(
        a_hat, xx, dj, dj, impl="xla") @ w)
    t_fused_x = _best_of(lambda: fused_x(x).block_until_ready())
    t_unfused_x = _best_of(lambda: unfused_x(x).block_until_ready())
    t_dense_x = _best_of(lambda: dense_x(x).block_until_ready())

    parity = float(jnp.abs(fused_k(x) - fused_x(x)).max())

    # auto-selection on the real partition plan for this graph
    assign = hicut_ref(n, g.edges) % DEVICES
    plan = make_partition_plan_sparse(g.edges, assign, DEVICES, n=n)
    selected = resolve_aggregate(plan)
    # when "dense" is selected the selected arm IS the dense timing, so
    # agg_speedup is exactly 1.0 by construction (never < 1 from noise)
    t_selected = t_dense_x if selected == "dense" else t_fused_x

    rec = {"n": n, "e": g.num_edges, "f": FEATURE_DIM, "k": k,
           "devices": DEVICES, "config": list(cfg),
           "t_fused_kernel_us": t_fused_k,
           "t_unfused_kernel_us": t_unfused_k,
           "fused_kernel_speedup": t_unfused_k / max(t_fused_k, 1e-9),
           "t_agg_fused_xla_us": t_fused_x,
           "t_agg_unfused_xla_us": t_unfused_x,
           "t_agg_dense_us": t_dense_x,
           "selected": selected,
           "agg_speedup": t_dense_x / max(t_selected, 1e-9),
           "fused_parity_err": parity}
    emit(f"kernel_fused_aggregate_n{n}_k{k}", t_fused_k,
         f"cfg={tuple(cfg)};unfused={t_unfused_k:.0f}us;"
         f"speedup={rec['fused_kernel_speedup']:.2f}x;"
         f"parity={parity:.1e}")
    emit(f"agg_layer_n{n}_selected_{selected}", t_selected,
         f"dense={t_dense_x:.0f}us;agg_speedup={rec['agg_speedup']:.2f}x")
    return rec


def run(quick: bool = True) -> None:
    rng = np.random.default_rng(0)

    cases = [(1_000, 10_000), (5_000, 50_000)] if quick else \
        [(1_000, 10_000), (2_000, 20_000), (5_000, 50_000)]
    records = [_aggregate_record(n, e, rng) for n, e in cases]
    write_bench_json(OUT_JSON, "kernels", quick, records)

    # flash attention
    b, h, kv, s, dh = (1, 4, 2, 1024, 64) if quick else (2, 8, 2, 4096, 128)
    q = jnp.asarray(rng.normal(size=(b, h, s, dh)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, kv, s, dh)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, kv, s, dh)).astype(np.float32))
    fa = jax.jit(lambda q_, k_, v_: flash_attention(q_, k_, v_))
    fa(q, k, v).block_until_ready()
    t = timeit(lambda: fa(q, k, v).block_until_ready())
    emit(f"kernel_flash_attention_s{s}_dh{dh}", t,
         f"blocks=128x128;vmem_scratch={4 * (128 + 128 + 128 * dh)}B")

    # ssd chunk scan
    b2, s2, h2, p2, n2 = (2, 512, 4, 64, 64) if quick else (4, 2048, 8, 64, 64)
    xx = jnp.asarray(rng.normal(size=(b2, s2, h2, p2)).astype(np.float32))
    bm = jnp.asarray(rng.normal(size=(b2, s2, n2)).astype(np.float32))
    cm = jnp.asarray(rng.normal(size=(b2, s2, n2)).astype(np.float32))
    la = -jnp.asarray(rng.random((b2, s2, h2)).astype(np.float32))
    sc = jax.jit(lambda *a: ssd_chunk_scan(*a))
    sc(xx, bm, cm, la).block_until_ready()
    t = timeit(lambda: sc(xx, bm, cm, la).block_until_ready())
    emit(f"kernel_ssd_scan_s{s2}_h{h2}", t,
         f"chunk=128;state_vmem={p2 * n2 * 4}B")


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--full", action="store_true",
                    help="paper-scale axes (slow)")
    ap.add_argument("--quick", action="store_true",
                    help="small axes (the default; --full overrides)")
    args = ap.parse_args()
    run(quick=not args.full)
