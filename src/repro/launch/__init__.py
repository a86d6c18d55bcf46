"""Launchers and deployment tooling — which entry point do I want?

Two families live here: the **GraphEdge control plane** (the paper
reproduction: controller → distributed GNN serving) and the **LM framework
lane** (the transformer stack this repo also carries: training/serving
launchers plus the multi-pod dry-run and roofline tooling).

Runnable entry points (``PYTHONPATH=src python -m repro.launch.<name>``):

| entry point | lane | what it does |
|---|---|---|
| ``serve_gnn``  | GraphEdge | thin CLI over the pipelined :class:`repro.serve.ServingEngine`: control decisions (jitted for the ``JitPolicy`` entries ``greedy_jit`` [default] / ``local_jit`` / ``lyapunov``) overlap in-flight distributed GCN forwards, plans are LRU-cached on (topology, assignment, network) behind ``--plan-cache-size`` (default 16), every output checked against the single-device oracle. ``--partitioner``/``--policy`` select any registry backend (e.g. ``multilevel`` + ``lyapunov``); ``--dataset synth-pubmed`` serves a ~20k-vertex graph through the sparse O(E) plan + gather path; ``--faults`` replays a deterministic failure/churn schedule with drain-then-swap network migration |
| ``serve_multihost`` | GraphEdge | SPMD serving over a simulated process grid: spawns ``--processes`` workers (``jax.distributed`` + gloo collectives, ``--devices`` total mesh devices split evenly), each building only its shard of the partition plan (:mod:`repro.gnn.multihost`) with features resident on their owning host and halo-only ``--exchange pair`` all_to_all between processes; ``--arm resident`` vs the replicate-everything single-process ``--arm engine`` baseline, ``--vertices``/``--edges`` synthetic community graph, JSON record with steps/sec + halo vs replicate bytes (``--json-out``), cross-host-count bitwise parity via ``--ref-out``/``--ref-in`` |
| ``serve_stream`` | GraphEdge | open-loop Poisson load against the streaming front-end (:class:`repro.serve.StreamingFrontend`): ``--arrival-rate`` req/s over ``--tenants`` tenants with ``--deadline``-second SLO budgets into a ``--queue-depth``-bounded queue; continuous batching up to ``--max-batch`` on shared plan-cache entries, ``--admission lyapunov`` (``--v``/``--theta``) vs ``static`` vs ``admit_all``, prints per-phase p50/p95/p99 + sustained req/s and the conservation ledger; ``--faults`` injects server failures + user waves at pump boundaries (queued requests migrate to warm-recut plans, per-fault recovery latency reported) |
| ``train``      | LM        | training loop for a registry arch (``--reduced`` CPU dims or ``--production`` mesh shardings) |
| ``serve``      | LM        | prefill + autoregressive decode (optionally ``--kv-int8``) |
| ``dryrun``     | LM        | lower + compile one (arch × shape × mesh) combo; memory/FLOPs analysis |
| ``dryrun_all`` | LM        | sweep every combo in subprocesses, JSON per run |
| ``report``     | LM        | render the dry-run/roofline tables from the sweep JSON |

Libraries (imported, not run): ``mesh`` (production mesh shapes),
``shapes`` (assigned input shapes / abstract input specs), ``shardings``
(FSDP+TP+SP GSPMD rules), ``roofline`` (compute/memory/collective terms
from compiled HLO).

DRLGO (offloading-policy) training is not a launcher — use
``examples/train_drlgo.py`` (``--batch B`` for the vmapped batched
environment) or drive :class:`repro.core.offload.drlgo.DRLGOTrainer`
directly. See README.md for the repo-level map.

:func:`enable_compile_cache` is the one place that points JAX's persistent
compilation cache at a directory; the GraphEdge launchers and
``chip_smoke.py`` call it before their first compile.
"""
from __future__ import annotations

import os
import pathlib

# <repo>/.jax_cache — a fixed path, because the path is part of the cache key
COMPILE_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    nothing is overridden. Otherwise the cache lives in
    :data:`COMPILE_CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)
