"""Streaming serving launcher — open-loop load against the front-end.

    PYTHONPATH=src python -m repro.launch.serve_stream --devices 4 \
        --arrival-rate 50 --tenants 3 --deadline 2.0 --queue-depth 64

Drives the production-shaped request front of DESIGN.md §7
(:class:`repro.serve.StreamingFrontend` over the pipelined
:class:`repro.serve.ServingEngine`) with an **open-loop Poisson workload**:
``--count`` requests arrive at ``--arrival-rate`` req/s on their own
schedule regardless of service progress, spread over ``--tenants`` tenants
and ``--topologies`` distinct perturbed graph layouts, each carrying a
``--deadline``-second SLO budget. The front-end queues them (bounded at
``--queue-depth``, explicit ``queue_full`` backpressure), groups queued
requests sharing a cached plan into continuous batches of up to
``--max-batch``, runs the ``--admission`` controller (``lyapunov`` with
``--v``/``--theta`` drift-plus-penalty knobs, ``static`` priority, or
``admit_all``) and prints the SLO telemetry: per-phase
p50/p95/p99 latency, sustained req/s, and the conservation ledger
(admitted + rejected + deferred + migrated == submitted).

``--faults`` arms the deterministic chaos harness (DESIGN.md §9): a
comma-separated ``cycle:kind[:arg[:scale]]`` schedule of server failures /
recoveries / degradations and user arrival/departure waves, applied at
pump-cycle boundaries through :class:`repro.serve.FaultInjector`. Server
events reprice the network, migrate every queued request to a warm-recut
plan (nothing is lost — the conservation ledger still balances) and are
reported with per-fault recovery latency.

Every served output is checked against the single-device ``gcn_apply``
oracle at "highest" matmul precision, within
:func:`repro.launch.serve_gnn.oracle_bound`. ``main(argv)`` returns a
summary dict, so callers can drive it in-process. (Entry-point
orientation: see the ``repro.launch`` package docstring.)
"""
from __future__ import annotations

import argparse

from repro.launch.serve_gnn import (_ensure_virtual_devices, build_mesh,
                                    oracle_bound, reference_gcn)


def _parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=4)
    ap.add_argument("--users", type=int, default=32)
    ap.add_argument("--links", type=int, default=0,
                    help="user-graph links (0 → 3 × users)")
    ap.add_argument("--capacity", type=int, default=0,
                    help="graph-state capacity (0 → users + 8)")
    ap.add_argument("--features", type=int, default=32)
    ap.add_argument("--hidden", type=int, default=16)
    ap.add_argument("--classes", type=int, default=5)
    ap.add_argument("--arrival-rate", type=float, default=50.0,
                    help="open-loop Poisson arrival rate, requests/sec")
    ap.add_argument("--count", type=int, default=64,
                    help="total requests injected by the workload")
    ap.add_argument("--tenants", type=int, default=3,
                    help="requests round-robin over this many tenant ids")
    ap.add_argument("--deadline", type=float, default=2.0,
                    help="per-request SLO budget in seconds (0 → none)")
    ap.add_argument("--queue-depth", type=int, default=64,
                    help="bounded request queue; overflow is rejected "
                         "with reason queue_full")
    ap.add_argument("--max-batch", type=int, default=8,
                    help="continuous-batching cap (bucketed to powers "
                         "of two)")
    ap.add_argument("--topologies", type=int, default=2,
                    help="distinct perturbed graph layouts cycled through "
                         "the stream (each is one plan-cache entry)")
    ap.add_argument("--admission", default="lyapunov",
                    choices=("lyapunov", "static", "admit_all"))
    ap.add_argument("--v", type=float, default=1.0,
                    help="lyapunov drift-plus-penalty weight V")
    ap.add_argument("--theta", type=float, default=8.0,
                    help="lyapunov admission backlog bound θ")
    ap.add_argument("--tenant-weights", default="",
                    help="weighted per-tenant service shares for the "
                         "lyapunov controller, e.g. '0:3,1:1' (tenants "
                         "not listed default to weight 1)")
    ap.add_argument("--faults", default="",
                    help="deterministic fault schedule: comma-separated "
                         "'cycle:kind[:arg[:scale]]' items, e.g. "
                         "'2:server_down:1,4:arrive:6,7:server_up:1' "
                         "(kinds: server_down, server_up, degrade, arrive, "
                         "depart; cycles are pump cycles)")
    ap.add_argument("--faults-seed", type=int, default=0,
                    help="rng seed for fault-schedule user-churn waves")
    ap.add_argument("--cross-topology", action="store_true",
                    help="batch requests across topologies: one dispatch "
                         "serves different cached plans padded to a "
                         "shared shape bucket")
    ap.add_argument("--threaded", action="store_true",
                    help="concurrent intake: a producer thread injects "
                         "arrivals while the pump loop dispatches")
    ap.add_argument("--plan-cache-size", type=int, default=16)
    ap.add_argument("--partitioner", default="hicut_jax")
    ap.add_argument("--policy", default="greedy_jit")
    ap.add_argument("--change-rate", type=float, default=0.2)
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def _fmt_phase(name: str, block: dict) -> str:
    return (f"  {name:<10s} p50={block['p50'] * 1e3:8.2f}ms  "
            f"p95={block['p95'] * 1e3:8.2f}ms  "
            f"p99={block['p99'] * 1e3:8.2f}ms  "
            f"max={block['max'] * 1e3:8.2f}ms")


def main(argv=None) -> dict:
    args = _parse_args(argv)
    _ensure_virtual_devices(args.devices)
    from repro.launch import enable_compile_cache
    enable_compile_cache()

    import jax
    import numpy as np

    from repro.core import costs
    from repro.core.api import GraphEdgeController
    from repro.core.dynamic_graph import perturb_scenario, random_scenario
    from repro.gnn.layers import gcn_init
    from repro.serve import (AdmitAll, FaultInjector, FaultSchedule,
                             LyapunovAdmission, ServingEngine,
                             StaticPriorityAdmission, StreamRequest,
                             StreamingFrontend, poisson_workload)

    rng = np.random.default_rng(args.seed)
    capacity = args.capacity or args.users + 8
    mesh, devices = build_mesh(args.devices)
    net = costs.default_network(rng, capacity, args.devices)
    controller = GraphEdgeController(net=net, policy=args.policy,
                                     partitioner=args.partitioner)
    params = gcn_init(jax.random.PRNGKey(args.seed),
                      [args.features, args.hidden, args.classes])
    engine = ServingEngine(controller=controller, params=params, mesh=mesh,
                           axis="servers", num_devices=devices,
                           plan_cache_size=args.plan_cache_size)

    if args.admission == "lyapunov":
        weights = {}
        for pair in filter(None, args.tenant_weights.split(",")):
            tenant, _, w = pair.partition(":")
            weights[int(tenant)] = float(w)
        admission = LyapunovAdmission(num_tenants=args.tenants, v=args.v,
                                      theta=args.theta, weights=weights)
        if weights:
            print(f"tenant weights: {weights} (starvation bound from "
                  f"backlog θ+4: "
                  + ", ".join(
                      f"τ{t}≤{admission.starvation_bound(t, args.theta + 4)}"
                      f" cycles" for t in range(args.tenants)))
    elif args.admission == "static":
        admission = StaticPriorityAdmission()
    else:
        admission = AdmitAll()
    states = [random_scenario(rng, capacity, args.users,
                              args.links or 3 * args.users)]
    for _ in range(args.topologies - 1):
        states.append(perturb_scenario(rng, states[-1], args.change_rate))
    deadline = args.deadline if args.deadline > 0 else None

    faults = None
    if args.faults:
        faults = FaultInjector(FaultSchedule.parse(args.faults), net,
                               state=states[0], seed=args.faults_seed)
    frontend = StreamingFrontend(engine=engine,
                                 queue_depth=args.queue_depth,
                                 max_batch=args.max_batch,
                                 admission=admission,
                                 cross_topology=args.cross_topology,
                                 faults=faults)

    def make_request(i: int) -> StreamRequest:
        # under fault churn the injector's evolving layout is the request
        # source (lazy workload: snapshotted at arrival, not construction)
        state = faults.state if faults is not None and \
            faults.state is not None else states[i % len(states)]
        x = rng.normal(size=(capacity, args.features)).astype(np.float32)
        return StreamRequest(state, x,
                             tenant=i % args.tenants, deadline=deadline)

    print(f"streaming {args.count} requests @ {args.arrival_rate} req/s "
          f"(open loop): {args.tenants} tenants, {args.topologies} "
          f"topologies, deadline={args.deadline}s, "
          f"queue_depth={args.queue_depth}, max_batch={args.max_batch}, "
          f"admission={args.admission}, {devices} mesh devices")
    workload = poisson_workload(rng, args.arrival_rate, args.count,
                                make_request, lazy=faults is not None)
    results = frontend.run_threaded(workload) if args.threaded \
        else frontend.run(workload)

    err, bound = 0.0, oracle_bound()
    for res in results:
        st = res.request.state
        oracle = reference_gcn(params, res.request.x, st.adj, st.mask)
        served = np.nonzero(np.asarray(st.mask) > 0)[0]
        err = max(err, float(np.abs(res.output[served] -
                                    oracle[served]).max()))
    assert err < bound, "streamed serve diverged from the oracle"

    stats = frontend.stats.as_dict()
    summary = frontend.slo_summary()
    print(f"served {stats['served']}/{stats['submitted']} "
          f"(admitted={stats['admitted']}, "
          f"rejected={stats['rejected_total']} {stats['rejected']}, "
          f"defer_events={stats['defer_events']})  "
          f"conservation={'ok' if stats['conservation_ok'] else 'VIOLATED'}")
    print(f"batches={stats['batches']} "
          f"batched_requests={stats['batched_requests']} "
          f"cross_batches={stats['cross_batches']}  "
          f"|serve - oracle|max={err:.2e} (bound {bound:.0e})")
    cyc = frontend.cycles.as_dict()
    if cyc["cycles"]:
        print(f"cycles={cyc['cycles']} batch_hist={cyc['batch_hist']} "
              f"decide p50={cyc['decide']['p50'] * 1e3:.2f}ms "
              f"p95={cyc['decide']['p95'] * 1e3:.2f}ms")
        slow = cyc["slowest"][0]
        print(f"slowest cycle {slow['cycle']} (batch {slow['batch']}): "
              f"decide={slow['decide'] * 1e3:.2f}ms "
              f"dispatch={slow['dispatch'] * 1e3:.2f}ms "
              f"fetch={slow['fetch'] * 1e3:.2f}ms")
    if summary.get("served"):
        print(f"sustained {summary['sustained_rps']:.2f} req/s")
        for phase in ("queue_wait", "decide", "forward", "total"):
            print(_fmt_phase(phase, summary[phase]))
    pc = engine.plan_cache_info()
    print(f"plan cache: {pc.hits} hits / {pc.misses} misses "
          f"({pc.currsize}/{pc.maxsize} entries)")
    if faults is not None:
        print(f"faults: migrated={stats['requests_migrated']} "
              f"(served {stats['migrated_served']})  "
              f"net_swaps={engine.net_swaps}  "
              f"servers up={faults.num_up}/{args.devices}")
        for rec in frontend.fault_trace:
            kinds = ",".join(e["kind"] for e in rec["events"])
            print(f"  cycle {rec['cycle']}: {kinds}  "
                  f"queued={rec['queued']} migrated={rec['migrated']} "
                  f"recut={rec['recut_topologies']} "
                  f"recovery={rec.get('recovery_cycles', '-')} cycles")
    assert stats["conservation_ok"], "request accounting does not conserve"
    return {"served": stats["served"], "submitted": stats["submitted"],
            "devices": devices, "plan_cache_hits": pc.hits,
            "plan_cache_misses": pc.misses, "max_err": err, "bound": bound}


if __name__ == "__main__":
    main()
