"""Distributed GNN serving launcher — thin CLI over the serving engine.

    PYTHONPATH=src python -m repro.launch.serve_gnn --devices 4 \
        --users 48 --partitioner hicut_jax --policy greedy_jit --steps 3

End-to-end control → serving on a virtual device mesh (edge server → mesh
device), driven by :class:`repro.serve.ServingEngine`: each dynamic time
step the :class:`repro.core.api.GraphEdgeController` perceives the
perturbed user topology, partitions it (LRU-cached on the topology
fingerprint; any registry backend — ``hicut_jax``, ``multilevel``,
``multilevel_jax``, ``mincut``, …), offloads users to servers (one jitted
scan for the ``JitPolicy`` entries ``greedy_jit``/``local_jit``/
``lyapunov``), and the engine pipelines the resulting plan
+ :func:`repro.gnn.distributed.make_forward_fn` inference against the
*next* step's decision (async dispatch, bounded plan cache — DESIGN.md
§5). ``--requests-per-step`` issues several inference requests per
topology interval; repeats hit the plan cache. Every output is checked
against the single-device ``gcn_apply`` oracle.

``--faults`` arms the deterministic chaos harness (DESIGN.md §9) on the
raw engine: the schedule's *user* waves churn the request stream (applied
in the generator, request-index clock) while its *server* events drive the
engine's drain-then-swap migration — the in-flight forward completes on
the old network, then the plan caches are invalidated and every later
decision prices against the degraded topology.

``--dataset`` switches to large-graph mode (the Fig. 6 axis): serve one of
the synthetic citation datasets (``synth-pubmed`` is ~20k vertices) or a
``random`` graph of ``--vertices``/``--edges``, partitioned by HiCut on the
raw edge list and planned through the sparse O(E)
:func:`~repro.gnn.distributed.make_partition_plan_sparse` path — no dense
N×N adjacency is ever built. Outputs are verified against the dense oracle
up to 4096 vertices, and against the single-host sparse gather oracle
above that.

    PYTHONPATH=src python -m repro.launch.serve_gnn --devices 8 \
        --dataset synth-pubmed

Importing this module has no side effects: the ``XLA_FLAGS`` virtual-device
mutation happens inside :func:`main`, and only when jax has not been
imported yet. The mesh takes at most as many devices as the backend has,
and the launcher prints the size it built. ``main(argv)`` returns a
summary dict, so callers can drive it in-process. (Entry-point
orientation: see the ``repro.launch`` package docstring.)
"""
from __future__ import annotations

import argparse
import os
import sys
import time

# dense-oracle cutover: above this many vertices the check runs against the
# sparse gather oracle instead of materializing the N×N adjacency
DENSE_ORACLE_MAX_VERTICES = 4096

# Largest |served − reference| a launcher accepts. The reference runs at
# "highest" matmul precision, the served forward at the default. On the CPU
# both are float32, so the bounds cover summation order only; the dataset
# path sums over larger neighbourhoods.
ORACLE_BOUND = 1e-4
DATASET_ORACLE_BOUND = 1e-3
# On a TPU a float32 matmul at default precision is one bfloat16 pass (8-bit
# significand), so each layer's x·W carries a relative error of ~2**-9 per
# operand. At PubMed's 500 input features a TPU v5e served 1.7e-3 from the
# reference on the 300-user stream and 8.9e-3 on synth-pubmed (hub rows
# reach |y| ≈ 8); the bound keeps a factor of five over the larger.
TPU_ORACLE_BOUND = 5e-2


def oracle_bound(cpu_bound: float = ORACLE_BOUND) -> float:
    """The bound for the backend in use (:data:`TPU_ORACLE_BOUND` on a TPU,
    ``cpu_bound`` elsewhere)."""
    import jax
    return TPU_ORACLE_BOUND if jax.default_backend() == "tpu" else cpu_bound


def reference_gcn(params, x, adj, mask):
    """The float32 oracle: single-device ``gcn_apply`` at "highest" matmul
    precision, as a host array."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.gnn.layers import gcn_apply
    with jax.default_matmul_precision("highest"):
        return np.asarray(gcn_apply(params, jnp.asarray(x), jnp.asarray(adj),
                                    jnp.asarray(mask)))


def build_mesh(requested: int):
    """A 1-D ``("servers",)`` mesh over at most ``requested`` devices.

    Returns ``(mesh, devices)``. Edge servers beyond the device count fold
    onto the devices there are; the size actually built is printed."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    avail = jax.devices()
    devices = min(requested, len(avail))
    print(f"mesh: {devices} {avail[0].platform} device(s) "
          f"({avail[0].device_kind}) for {requested} edge servers")
    return Mesh(np.array(avail[:devices]), ("servers",)), devices


def _parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=4)
    ap.add_argument("--users", type=int, default=48)
    ap.add_argument("--capacity", type=int, default=0,
                    help="graph-state capacity (0 → users + 8)")
    ap.add_argument("--features", type=int, default=None,
                    help="input width (default: the dataset's own with "
                         "--dataset, else 32)")
    ap.add_argument("--hidden", type=int, default=16)
    ap.add_argument("--classes", type=int, default=None,
                    help="output width (default: the dataset's own with "
                         "--dataset, else 5)")
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--requests-per-step", type=int, default=1,
                    help="inference requests served per topology step "
                         "(repeats hit the engine's plan cache)")
    ap.add_argument("--plan-cache-size", type=int, default=16)
    ap.add_argument("--partitioner", default="hicut_jax")
    ap.add_argument("--policy", default="greedy_jit")
    ap.add_argument("--change-rate", type=float, default=0.2)
    ap.add_argument("--faults", default="",
                    help="deterministic fault schedule: comma-separated "
                         "'cycle:kind[:arg[:scale]]' items, e.g. "
                         "'1:server_down:1,2:arrive:4,4:server_up:1' "
                         "(cycles are request indices on the raw engine)")
    ap.add_argument("--faults-seed", type=int, default=0,
                    help="rng seed for fault-schedule user-churn waves")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dataset", default="",
                    help="large-graph mode: synth-citeseer | synth-cora | "
                         "synth-pubmed | random (skips the controller loop)")
    ap.add_argument("--vertices", type=int, default=20_000,
                    help="--dataset random: vertex count")
    ap.add_argument("--edges", type=int, default=200_000,
                    help="--dataset random: edge count")
    return ap.parse_args(argv)


def _serve_dataset(args) -> dict:
    """Large-graph one-shot serve: sparse plan + gather aggregation."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.hicut import hicut_ref
    from repro.data.graphs import DATASETS, make_graph, random_graph
    from repro.gnn.distributed import (distributed_gcn_forward,
                                       make_partition_plan_sparse)
    from repro.gnn.layers import gcn_init, gcn_norm_sparse
    from repro.kernels.gnn_aggregate.ops import gather_aggregate

    rng = np.random.default_rng(args.seed)
    mesh, devices = build_mesh(args.devices)
    t0 = time.perf_counter()
    if args.dataset == "random":
        g = random_graph(args.vertices, args.edges, seed=args.seed)
        spec_f, spec_c = 32, 5
    else:
        spec = DATASETS[args.dataset]
        g = make_graph(spec, seed=args.seed)
        spec_f, spec_c = spec.feature_dim, spec.num_classes
    features = args.features or spec_f
    classes = args.classes or spec_c
    n = g.num_vertices
    print(f"{g.name}: {n} vertices, {g.num_edges} edges, GCN "
          f"{features}→{args.hidden}→{classes} "
          f"(built in {time.perf_counter() - t0:.1f}s)")

    t0 = time.perf_counter()
    assign = hicut_ref(n, g.edges) % devices
    t_cut = time.perf_counter() - t0
    t0 = time.perf_counter()
    plan = make_partition_plan_sparse(g.edges, assign, devices, n=n)
    t_plan = time.perf_counter() - t0
    print(f"hicut {t_cut:.1f}s, sparse plan {t_plan:.2f}s: "
          f"block={plan.block} halo={plan.halo} max_deg={plan.max_degree} "
          f"collective={plan.bytes_per_aggregate(args.hidden)} B/layer")

    params = gcn_init(jax.random.PRNGKey(args.seed),
                      [features, args.hidden, classes])
    x = rng.normal(size=(n, features)).astype(np.float32)
    t0 = time.perf_counter()
    out = distributed_gcn_forward(mesh, "servers", plan, params, x)
    t_fwd = time.perf_counter() - t0

    if n <= DENSE_ORACLE_MAX_VERTICES:
        oracle = reference_gcn(params, x, g.adjacency(), np.ones(n))
        which = "dense gcn_apply"
    else:   # single-host sparse oracle: Â = A + I through the gather op
        idx, val, dinv = gcn_norm_sparse(g.edges, n)
        h = jnp.asarray(x)
        with jax.default_matmul_precision("highest"):
            for li, layer in enumerate(params):
                h = gather_aggregate(idx, val, h @ jnp.asarray(layer["w"]),
                                     dinv, dinv)
                if li < len(params) - 1:
                    h = jax.nn.relu(h)
        oracle = np.asarray(h)
        which = "single-host sparse gather"
    err = float(np.abs(out - oracle).max())
    bound = oracle_bound(DATASET_ORACLE_BOUND)
    print(f"forward {t_fwd:.2f}s  |serve - {which} oracle|max = {err:.2e} "
          f"(bound {bound:.0e})")
    assert err < bound, "distributed serve diverged from the oracle"
    return {"served": 1, "devices": devices, "max_err": err, "bound": bound}


def _ensure_virtual_devices(devices: int) -> None:
    """Request ``devices`` virtual CPU devices — only effective before the
    first jax import (XLA reads the flag at backend init). Importing this
    module never mutates the environment; calling main() after jax is
    already up serves on however many devices exist (:func:`build_mesh`
    prints the count)."""
    if "jax" not in sys.modules:
        os.environ.setdefault(
            "XLA_FLAGS",
            f"--xla_force_host_platform_device_count={devices}")


def main(argv=None) -> dict:
    args = _parse_args(argv)
    _ensure_virtual_devices(args.devices)
    from repro.launch import enable_compile_cache
    enable_compile_cache()

    if args.dataset:
        return _serve_dataset(args)

    import jax
    import numpy as np

    from repro.core import costs
    from repro.core.api import GraphEdgeController
    from repro.core.dynamic_graph import perturb_scenario, random_scenario
    from repro.gnn.layers import gcn_init
    from repro.serve import (FaultInjector, FaultSchedule, ServeRequest,
                             ServingEngine)

    features = args.features or 32
    rng = np.random.default_rng(args.seed)
    capacity = args.capacity or args.users + 8
    state = random_scenario(rng, capacity, args.users, 3 * args.users)
    mesh, devices = build_mesh(args.devices)
    net = costs.default_network(rng, capacity, args.devices)
    controller = GraphEdgeController(net=net, policy=args.policy,
                                     partitioner=args.partitioner)
    params = gcn_init(jax.random.PRNGKey(args.seed),
                      [features, args.hidden, args.classes or 5])
    engine = ServingEngine(controller=controller, params=params, mesh=mesh,
                           axis="servers", num_devices=devices,
                           plan_cache_size=args.plan_cache_size)

    user_inj = server_inj = None
    if args.faults:
        schedule = FaultSchedule.parse(args.faults)
        # split clocks: user waves churn the stream in the generator,
        # server events drive the engine's drain-then-swap migration
        user_inj = FaultInjector(schedule.user_events(), net,
                                 state=state, seed=args.faults_seed)
        server_inj = FaultInjector(schedule.server_events(), net)

    def requests():
        nonlocal state
        idx = 0
        for t in range(args.steps):
            if t:
                state = perturb_scenario(rng, state, args.change_rate)
            for _ in range(args.requests_per_step):
                if user_inj is not None:
                    upd = user_inj.poll(idx)
                    if upd is not None and upd.state is not None:
                        state = upd.state
                x = rng.normal(size=(capacity, features))
                yield ServeRequest(state, x.astype(np.float32))
                idx += 1

    total = args.steps * args.requests_per_step
    print(f"serving {total} requests over {args.steps} dynamic steps: "
          f"{args.users} users, {devices} mesh devices, "
          f"{args.partitioner} + {args.policy} (pipelined engine)")
    bound = oracle_bound()
    max_err = 0.0
    t0 = time.perf_counter()
    for res in engine.serve(requests(), faults=server_inj):
        st = res.request.state
        oracle = reference_gcn(params, res.request.x, st.adj, st.mask)
        served = np.nonzero(np.asarray(st.mask) > 0)[0]
        err = float(np.abs(res.output[served] - oracle[served]).max())
        max_err = max(max_err, err)
        print(f"req={res.step}: C={float(res.decision.cost.c):8.3f}  "
              f"subgraphs={res.decision.partition.num_subgraphs:3d}  "
              f"halo={res.plan.halo:3d} rows/device  "
              f"collective={res.plan.bytes_per_aggregate(args.hidden):8d} B  "
              f"plan={'hit ' if res.plan_cache_hit else 'miss'}  "
              f"|serve - oracle|max={err:.2e}")
        assert err < bound, "distributed serve diverged from the oracle"
    dt = time.perf_counter() - t0
    pc, cc = engine.plan_cache_info(), controller.cache_info()
    print(f"{total / dt:.2f} req/s  "
          f"partition cache: {cc.hits} hits / {cc.misses} misses  "
          f"plan cache: {pc.hits} hits / {pc.misses} misses "
          f"({pc.currsize}/{pc.maxsize} entries)")
    if server_inj is not None:
        applied = len(server_inj.applied) + len(user_inj.applied)
        print(f"faults: {applied} events applied  "
              f"net_swaps={engine.net_swaps}  "
              f"servers up={server_inj.num_up}/{args.devices}")
    return {"served": total, "devices": devices, "plan_cache_hits": pc.hits,
            "plan_cache_misses": pc.misses, "max_err": max_err,
            "bound": bound}


if __name__ == "__main__":
    main()
