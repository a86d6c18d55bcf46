"""Serving throughput: pipelined-jit engine vs the sequential controller
loop (ROADMAP "Async serving loop" / "Controller-in-jit").

Both arms serve the *same* pre-built request stream — a dynamic rollout
(``change_rate`` perturbations) with a few inference requests per topology
interval, ≥128 users:

* **sequential** — the pre-engine ``serve_gnn`` loop verbatim: numpy
  ``greedy`` policy walking the env user by user, a fresh
  ``Decision.to_partition_plan`` + blocking ``distributed_gcn_forward``
  per request.
* **pipelined-jit** — :class:`repro.serve.ServingEngine` with the
  ``greedy_jit`` policy: one jitted scan per decision, bounded plan cache,
  async-dispatch overlap of decision t with forward t−1.

Both warm up on a copy of the first request (compile/trace time excluded
from both arms), outputs are cross-checked against the single-device
``gcn_apply`` oracle, and the results land in machine-readable
**``BENCH_serving.json``** (steps/sec per arm, speedup, parity errors,
cache counters) so the perf trajectory — and the ≥2× acceptance bar — is
tracked across PRs. The CI serving smoke lane fails if the engine is
slower than the sequential loop or diverges from the oracle.

The file also carries the **streaming front-end records** (``"mode":
"streaming"`` — DESIGN.md §7, BENCHMARKS.md):

* ``burst_batchable`` — a burst of concurrent requests on one topology,
  served once with continuous batching (``max_batch`` ≥ 4) and once
  per-request (``max_batch=1``) through the same warm engine; the batched
  arm must clear the **≥2× throughput** acceptance bar, and every
  streamed output is checked against the no-frontend ``engine.serve``
  sequential oracle.
* ``overload_lyapunov`` / ``overload_admit_all`` — an open-loop Poisson
  stream far above service capacity with per-request deadlines; the
  Lyapunov arm must keep the *admitted* p99 bounded (CI gates
  ``p99 ≤ 2 × deadline``) with every shed request accounted
  (conservation), while the admit-all contrast arm shows the unbounded
  tail admission control removes.
* ``decide_batch`` — the batched control plane (ISSUE 8): B distinct
  perturbed topologies decided per-request (``decide_entry`` loop) vs as
  one vmapped ``decide_entries`` call on the same warm engine. CI gates
  **speedup ≥2×** and assignment-exact parity between the two roads.
* ``cross_topology`` — continuous batching *across* topologies: an
  all-at-once queue of requests spread over several perturbed layouts
  (same shape bucket), served with ``cross_topology=True`` so one padded
  multi-plan dispatch covers plan-heterogeneous batches. Records the
  sustained req/s, the speedup over the PR 6 ``burst_batchable`` record
  (``pr6_burst_rps_ref``), and the **exact** (bitwise, ``== 0``) parity
  vs the sequential no-frontend engine oracle, which CI gates.

And the **fault-injection records** (``"mode": "failure"`` — DESIGN.md §9,
the chaos harness of ``repro.serve.faults``):

* ``server_down_migration`` — a mid-stream server failure + recovery on a
  deterministic (ManualClock) streaming run: every queued request migrates
  to a warm-recut plan on the repriced network. CI gates
  ``lost_requests == 0``, conservation, ``requests_migrated > 0``,
  recovery within 3 pump cycles, a bitwise-identical fault trace across
  two identical runs (``trace_deterministic``), and output parity against
  the single-device oracle (the GCN output depends only on the topology,
  so migration must never change it).
* ``warm_recut`` — the migration re-cut itself: warm-started multilevel
  refinement (previous cut as the initial assignment, coarsening and GGGP
  skipped) vs a from-scratch re-partition on the post-fault server count,
  comparing wall time (``recut_speedup``), edge cut, and the system cost
  of the resulting offload decision (``cost_delta_vs_scratch``).
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import emit, write_bench_json

OUT_JSON = "BENCH_serving.json"
FEATURES, HIDDEN, CLASSES = 32, 16, 5


def _build_requests(rng, capacity, users, steps, repeats, change_rate):
    from repro.core.dynamic_graph import perturb_scenario, random_scenario
    from repro.serve import ServeRequest

    state = random_scenario(rng, capacity, users, 3 * users)
    reqs = []
    for t in range(steps):
        if t:
            state = perturb_scenario(rng, state, change_rate)
        for _ in range(repeats):
            x = rng.normal(size=(capacity, FEATURES)).astype(np.float32)
            reqs.append(ServeRequest(state, x))
    return reqs


def _oracle_err(params, res_out, req) -> float:
    import jax.numpy as jnp

    from repro.gnn.layers import gcn_apply
    st = req.state
    oracle = np.asarray(gcn_apply(params, jnp.asarray(req.x), st.adj,
                                  st.mask))
    served = np.nonzero(np.asarray(st.mask) > 0)[0]
    return float(np.abs(res_out[served] - oracle[served]).max())


def _sequential_pass(net, requests, mesh, params, devices):
    """The pre-engine one-decision→one-forward loop, timed verbatim."""
    from repro.core.api import GraphEdgeController
    from repro.gnn.distributed import distributed_gcn_forward

    ctrl = GraphEdgeController(net=net, policy="greedy")
    outs = []
    for req in requests:
        decision = ctrl.step(req.state)
        plan = decision.to_partition_plan(devices)
        outs.append(distributed_gcn_forward(mesh, "servers", plan, params,
                                            req.x))
    return outs


def _streaming_records(quick, mesh, devices) -> list:
    """The streaming front-end arms (``"mode": "streaming"`` records)."""
    import time as _time

    import jax

    from repro.core import costs
    from repro.core.api import GraphEdgeController
    from repro.core.dynamic_graph import perturb_scenario, random_scenario
    from repro.gnn.layers import gcn_init
    from repro.serve import (AdmitAll, LyapunovAdmission, ServeRequest,
                             ServingEngine, StreamRequest, StreamingFrontend,
                             poisson_workload)

    users = 64 if quick else 128
    capacity = users + 8
    n_burst = 16 if quick else 32
    max_batch = 8
    rng = np.random.default_rng(1)
    net = costs.default_network(rng, capacity, 4)
    params = gcn_init(jax.random.PRNGKey(1), [FEATURES, HIDDEN, CLASSES])
    state = random_scenario(rng, capacity, users, 3 * users)
    xs = [rng.normal(size=(capacity, FEATURES)).astype(np.float32)
          for _ in range(n_burst)]

    def make_engine():
        return ServingEngine(
            controller=GraphEdgeController(net=net, policy="greedy_jit"),
            params=params, mesh=mesh, num_devices=devices)

    def burst():
        return [(0.0, StreamRequest(state, x, tenant=i % 2))
                for i, x in enumerate(xs)]

    # -- no-frontend sequential oracle (the parity reference) ----------------
    oracle_engine = make_engine()
    seq_outs = [r.output for r in oracle_engine.serve_all(
        [ServeRequest(state, x) for x in xs])]
    mask_rows = np.nonzero(np.asarray(state.mask) > 0)[0]

    def run_arm(mb):
        """One timed burst pass at batch cap ``mb`` on a pre-warmed engine
        (compile/trace excluded, plan cache warm — steady-state serving)."""
        eng = make_engine()
        StreamingFrontend(engine=eng, queue_depth=n_burst + 8,
                          max_batch=mb).run(burst())          # warmup
        fe = StreamingFrontend(engine=eng, queue_depth=n_burst + 8,
                               max_batch=mb)
        t0 = _time.perf_counter()
        results = fe.run(burst())
        dt = _time.perf_counter() - t0
        err = max(float(np.abs(r.output[mask_rows]
                               - seq_outs[r.rid][mask_rows]).max())
                  for r in results)
        return fe, len(results) / dt, err

    fe1, base_rps, err1 = run_arm(1)
    feb, batch_rps, errb = run_arm(max_batch)
    records = [{
        "mode": "streaming", "workload": "burst_batchable",
        "users": users, "capacity": capacity, "devices": devices,
        "requests": n_burst, "max_batch": max_batch,
        "baseline_rps": base_rps, "batched_rps": batch_rps,
        "batch_speedup": batch_rps / base_rps,
        "batches": feb.stats.batches,
        "batched_requests": feb.stats.batched_requests,
        "parity_vs_engine_max_err": max(err1, errb),
        "conservation_ok": bool(fe1.stats.conservation_ok
                                and feb.stats.conservation_ok),
    }]
    emit(f"streaming_burst_u{users}", 1e6 / batch_rps,
         f"batched_rps={batch_rps:.2f};baseline_rps={base_rps:.2f};"
         f"batch_speedup={batch_rps / base_rps:.1f}x;"
         f"max_err={max(err1, errb):.1e}")

    # -- overload: open-loop Poisson far above capacity, with deadlines ------
    # Timed on a ManualClock (every clock read = 20 logical ms) so "service
    # capacity" is simulated and the overload regime — and therefore the CI
    # gate on the admitted p99 — is deterministic across machines. The
    # forwards still run for real; only the tick arithmetic is logical.
    from repro.serve import ManualClock

    deadline = 0.5                    # logical SLO budget (lyapunov arm)
    count = 60 if quick else 120
    rate = 100.0                      # logical arrivals/sec >> service rate
    tenants = 3
    queue_depth = 16                  # shallow: overflow → queue_full

    def overload_arm(admission, name, slo_budget):
        eng = make_engine()
        StreamingFrontend(engine=eng, queue_depth=count,
                          max_batch=max_batch).run(burst())   # warm compiles
        fe = StreamingFrontend(engine=eng, queue_depth=queue_depth,
                               max_batch=max_batch, admission=admission,
                               clock=ManualClock(tick_per_now=0.02))
        wl_rng = np.random.default_rng(2)
        fe.run(poisson_workload(
            wl_rng, rate, count,
            lambda i: StreamRequest(state, xs[i % n_burst],
                                    tenant=i % tenants,
                                    deadline=slo_budget)))
        stats, slo = fe.stats.as_dict(), fe.slo_summary()
        rec = {
            "mode": "streaming", "workload": name, "clock": "manual",
            "users": users, "capacity": capacity, "devices": devices,
            "requests": count, "arrival_rate": rate, "tenants": tenants,
            "deadline": slo_budget, "queue_depth": queue_depth,
            "max_batch": max_batch,
            "admitted": stats["admitted"],
            "rejected": stats["rejected"],
            "rejected_total": stats["rejected_total"],
            "deferred": stats["deferred"],
            "conservation_ok": stats["conservation_ok"],
            "sustained_rps": slo.get("sustained_rps", 0.0),
            "admitted_p50_s": slo.get("total", {}).get("p50"),
            "admitted_p99_s": slo.get("total", {}).get("p99"),
        }
        if name == "overload_lyapunov":
            rec["tenant_queue_max"] = admission.queue_max
        emit(f"streaming_{name}_u{users}",
             (rec["admitted_p99_s"] or 0.0) * 1e6,
             f"admitted={rec['admitted']}/{count};"
             f"rejected={rec['rejected_total']};"
             f"p99_s={rec['admitted_p99_s']:.3f};"
             f"conservation={'ok' if rec['conservation_ok'] else 'BAD'}")
        return rec

    # lyapunov enforces the SLO budget; the admit-all contrast arm runs the
    # same stream best-effort (no deadlines, no control) and shows the
    # unbounded latency tail admission control removes
    records.append(overload_arm(
        LyapunovAdmission(num_tenants=tenants), "overload_lyapunov",
        deadline))
    records.append(overload_arm(AdmitAll(), "overload_admit_all", None))

    # -- decide_batch: per-request decide loop vs one vmapped decide ---------
    # B distinct perturbed topologies, caches sized to hold them all (the
    # comparison is decide dispatch, not partition-recompute thrash).
    n_topo_decide = 32 if quick else 64
    topo_rng = np.random.default_rng(3)
    decide_states = [state]
    for _ in range(n_topo_decide - 1):
        decide_states.append(perturb_scenario(topo_rng, decide_states[-1],
                                              0.1))
    dec_eng = ServingEngine(
        controller=GraphEdgeController(net=net, policy="greedy_jit",
                                       cache_size=2 * n_topo_decide),
        params=params, mesh=mesh, num_devices=devices,
        plan_cache_size=2 * n_topo_decide)
    dec_eng.decide_entries(decide_states)            # warm the batched road
    seq_entries = [dec_eng.decide_entry(s) for s in decide_states]
    reps = 5
    t0 = _time.perf_counter()
    for _ in range(reps):
        for s in decide_states:
            dec_eng.decide_entry(s)
    t_seq_dec = (_time.perf_counter() - t0) / reps
    t0 = _time.perf_counter()
    for _ in range(reps):
        bat_entries = dec_eng.decide_entries(decide_states)
    t_bat_dec = (_time.perf_counter() - t0) / reps
    assign_exact = all(
        np.array_equal(eb[0].servers, es[0].servers)
        for eb, es in zip(bat_entries, seq_entries))
    rec = {
        "mode": "streaming", "workload": "decide_batch",
        "users": users, "capacity": capacity, "devices": devices,
        "batch": n_topo_decide,
        "seq_decides_per_sec": n_topo_decide / t_seq_dec,
        "batch_decides_per_sec": n_topo_decide / t_bat_dec,
        "decide_batch_speedup": t_seq_dec / t_bat_dec,
        "assign_exact": bool(assign_exact),
    }
    records.append(rec)
    emit(f"streaming_decide_batch_b{n_topo_decide}",
         t_bat_dec / n_topo_decide * 1e6,
         f"batch_decides_per_sec={rec['batch_decides_per_sec']:.1f};"
         f"speedup={rec['decide_batch_speedup']:.2f}x;"
         f"assign_exact={assign_exact}")

    # -- cross_topology: one dispatch serves plan-heterogeneous batches ------
    # All requests queued up front (closed-loop drain — pure service rate),
    # spread over several perturbed layouts sharing one shape bucket, with
    # cross_topology batching and the vmapped decide_entries control plane.
    n_cross = 256 if quick else 512
    n_topo_cross = 4
    mb_cross = 128
    cross_states = [state]
    for _ in range(n_topo_cross - 1):
        cross_states.append(perturb_scenario(topo_rng, cross_states[-1],
                                             0.1))
    cross_xs = [rng.normal(size=(capacity, FEATURES)).astype(np.float32)
                for _ in range(n_cross)]
    cross_eng = make_engine()
    cross_outs = [r.output for r in cross_eng.serve_all(
        [ServeRequest(cross_states[i % n_topo_cross], x)
         for i, x in enumerate(cross_xs)])]   # sequential oracle (+ warmup)

    def cross_load():
        return [(0.0, StreamRequest(cross_states[i % n_topo_cross], x))
                for i, x in enumerate(cross_xs)]

    StreamingFrontend(engine=cross_eng, queue_depth=n_cross,
                      max_batch=mb_cross, cross_topology=True
                      ).run(cross_load())              # warm padded plans
    fe_x = StreamingFrontend(engine=cross_eng, queue_depth=n_cross,
                             max_batch=mb_cross, cross_topology=True)
    t0 = _time.perf_counter()
    cross_results = fe_x.run(cross_load())
    t_cross = _time.perf_counter() - t0
    cross_rows = [np.nonzero(np.asarray(s.mask) > 0)[0]
                  for s in cross_states]
    cross_err = max(
        float(np.abs(r.output[cross_rows[r.rid % n_topo_cross]]
                     - cross_outs[r.rid][cross_rows[r.rid % n_topo_cross]]
                     ).max())
        for r in cross_results)
    pr6_burst_rps_ref = 2792.697862932865   # PR 6 burst_batchable record
    cyc = fe_x.cycles.as_dict()
    rec = {
        "mode": "streaming", "workload": "cross_topology",
        "users": users, "capacity": capacity, "devices": devices,
        "requests": n_cross, "topologies": n_topo_cross,
        "max_batch": mb_cross,
        "sustained_rps": len(cross_results) / t_cross,
        "pr6_burst_rps_ref": pr6_burst_rps_ref,
        "speedup_vs_pr6_burst": (len(cross_results) / t_cross
                                 / pr6_burst_rps_ref),
        "cross_batches": fe_x.stats.cross_batches,
        "cross_batched_requests": fe_x.stats.cross_batched_requests,
        "batch_hist": cyc["batch_hist"],
        "decide_p50_s": cyc["decide"]["p50"],
        "parity_vs_engine_max_err": cross_err,
        "conservation_ok": bool(fe_x.stats.conservation_ok),
    }
    records.append(rec)
    emit(f"streaming_cross_topology_u{users}",
         t_cross / n_cross * 1e6,
         f"sustained_rps={rec['sustained_rps']:.1f};"
         f"speedup_vs_pr6_burst={rec['speedup_vs_pr6_burst']:.2f}x;"
         f"max_err={cross_err:.1e};"
         f"conservation={'ok' if rec['conservation_ok'] else 'BAD'}")
    return records


def _failure_records(quick, mesh, devices) -> list:
    """The fault-injection arms (``"mode": "failure"`` records).

    ``server_down_migration`` runs the exact fault drill CI gates: a
    mid-stream ``server_down`` + ``server_up`` on a ManualClock streaming
    run, executed **twice** with identical seeds so the fault trace, the
    stats ledger and every served output can be checked for bitwise
    determinism. ``warm_recut`` isolates the migration re-cut cost."""
    import time as _time
    import types

    import jax

    from repro.core import costs
    from repro.core.api import GraphEdgeController, state_edges
    from repro.core.dynamic_graph import random_scenario
    from repro.core.multilevel import multilevel_partition
    from repro.gnn.layers import gcn_init
    from repro.serve import (AdmitAll, FaultInjector, FaultSchedule,
                             ManualClock, ServingEngine, StreamRequest,
                             StreamingFrontend, poisson_workload)

    users = 64 if quick else 128
    capacity = users + 8
    count = 24 if quick else 48
    spec = "2:server_down:1,5:server_up:1"
    rng = np.random.default_rng(5)
    net = costs.default_network(rng, capacity, 4)
    params = gcn_init(jax.random.PRNGKey(5), [FEATURES, HIDDEN, CLASSES])
    state = random_scenario(rng, capacity, users, 3 * users)
    xs = [rng.normal(size=(capacity, FEATURES)).astype(np.float32)
          for _ in range(count)]

    # -- server_down_migration: the gated fault drill, twice -----------------
    def fault_pass():
        eng = ServingEngine(
            controller=GraphEdgeController(net=net, policy="greedy_jit"),
            params=params, mesh=mesh, num_devices=devices)
        inj = FaultInjector(FaultSchedule.parse(spec), net, seed=0)
        fe = StreamingFrontend(engine=eng, queue_depth=count, max_batch=4,
                               admission=AdmitAll(), faults=inj,
                               clock=ManualClock(tick_per_now=0.02))
        wl = poisson_workload(
            np.random.default_rng(4), rate=5.0, count=count,
            make_request=lambda i: StreamRequest(state=state, x=xs[i]))
        t0 = _time.perf_counter()
        results = fe.run(wl)
        return fe, results, _time.perf_counter() - t0

    fe_a, res_a, _ = fault_pass()          # also warms the compiles
    fe_b, res_b, t_run = fault_pass()
    out_a = {r.rid: r.output for r in res_a}
    out_b = {r.rid: r.output for r in res_b}
    trace_det = bool(
        fe_a.fault_trace == fe_b.fault_trace
        and fe_a.stats.as_dict() == fe_b.stats.as_dict()
        and out_a.keys() == out_b.keys()
        and all(np.array_equal(out_a[rid], out_b[rid]) for rid in out_a))
    parity = max(
        _oracle_err(params, r.output,
                    types.SimpleNamespace(state=state, x=xs[r.rid]))
        for r in res_b)
    stats = fe_b.stats.as_dict()
    lost = stats["submitted"] - stats["served"] - stats["rejected_total"]
    recovery = max((t["recovery_cycles"] for t in fe_b.fault_trace
                    if "recovery_cycles" in t), default=0)
    rec = {
        "mode": "failure", "workload": "server_down_migration",
        "users": users, "capacity": capacity, "devices": devices,
        "requests": count, "faults": spec, "clock": "manual",
        "max_batch": 4,
        "submitted": stats["submitted"], "served": stats["served"],
        "lost_requests": int(lost),
        "requests_migrated": stats["requests_migrated"],
        "migrated_served": stats["migrated_served"],
        "recovery_cycles": int(recovery),
        "net_swaps": fe_b.engine.net_swaps,
        "fault_events": sum(len(t["events"]) for t in fe_b.fault_trace),
        "conservation_ok": bool(stats["conservation_ok"]),
        "trace_deterministic": trace_det,
        "parity_vs_oracle_max_err": parity,
    }
    records = [rec]
    emit(f"failure_server_down_migration_u{users}", t_run / count * 1e6,
         f"migrated={rec['requests_migrated']};lost={rec['lost_requests']};"
         f"recovery_cycles={rec['recovery_cycles']};"
         f"deterministic={trace_det};max_err={parity:.1e}")

    # -- warm_recut: warm-started migration re-cut vs from-scratch -----------
    edges = state_edges(state)
    active = np.asarray(state.mask) > 0
    n = state.capacity
    cold = multilevel_partition(n, edges, 4, active=active)
    reps = 3 if quick else 5
    warm = scratch = None
    multilevel_partition(n, edges, 3, active=active, initial=cold)
    t0 = _time.perf_counter()
    for _ in range(reps):
        warm = multilevel_partition(n, edges, 3, active=active, initial=cold)
    t_warm = (_time.perf_counter() - t0) / reps
    multilevel_partition(n, edges, 3, active=active)
    t0 = _time.perf_counter()
    for _ in range(reps):
        scratch = multilevel_partition(n, edges, 3, active=active)
    t_scratch = (_time.perf_counter() - t0) / reps

    def cut(assign):
        a, b = assign[edges[:, 0]], assign[edges[:, 1]]
        return int(np.sum((a >= 0) & (b >= 0) & (a != b)))

    # system cost of the offload decision each cut leads to on the
    # post-fault (server 1 down) pricing
    m = int(net.f_k.shape[0])
    prof = costs.ServerProfile.healthy(m)
    deg = costs.degrade_network(net, prof._replace(up=prof.up.at[1].set(0.0)))
    ctrl_warm = GraphEdgeController(net=deg, policy="greedy_jit")
    ctrl_warm.recut_warm(state, cold, num_parts=3)
    c_warm = float(ctrl_warm.step(state).cost.c)
    ctrl_scratch = GraphEdgeController(net=deg, policy="greedy_jit",
                                       partitioner="multilevel",
                                       partitioner_kwargs={"num_parts": 3})
    c_scratch = float(ctrl_scratch.step(state).cost.c)
    rec = {
        "mode": "failure", "workload": "warm_recut",
        "users": users, "capacity": capacity,
        "parts_before": 4, "parts_after": 3,
        "t_warm_ms": t_warm * 1e3, "t_scratch_ms": t_scratch * 1e3,
        "recut_speedup": t_scratch / t_warm,
        "cut_warm": cut(warm), "cut_scratch": cut(scratch),
        "cost_warm": c_warm, "cost_scratch": c_scratch,
        "cost_delta_vs_scratch": (c_warm - c_scratch) / c_scratch,
    }
    records.append(rec)
    emit(f"failure_warm_recut_u{users}", t_warm * 1e6,
         f"recut_speedup={rec['recut_speedup']:.2f}x;"
         f"cut_warm={rec['cut_warm']};cut_scratch={rec['cut_scratch']};"
         f"cost_delta={rec['cost_delta_vs_scratch']:+.4f}")
    return records


def _multihost_records(quick, devices) -> list:
    """The multi-host SPMD arms (``"mode": "multihost"`` records).

    Drives ``repro.launch.serve_multihost`` over ``devices`` mesh devices:
    a replicate-everything single-process **engine** baseline, then the
    **resident** sharded path at 1, 2 (and ``--full`` 4) simulated hosts
    on the same ``community_graph`` — 10⁶ vertices under ``--full``,
    smoke-size under quick. The 1-process arms run in this process (a
    process holding the chip cannot hand it to a child); the multi-host
    arms spawn one ``jax.distributed`` worker per host, so they run only
    on the CPU, where no chip is held. hosts=1 writes the reference output;
    every other arm must match it **bitwise** (``parity_max_err == 0``,
    CI-gated), and halo bytes must stay strictly under the replicate
    baseline's transfer."""
    import json as _json
    import os
    import tempfile

    import jax

    from repro.launch import serve_multihost

    n, e, steps = (20_000, 60_000, 3) if quick else (1_000_000, 3_000_000, 5)
    host_counts = [1]
    if jax.default_backend() == "cpu":
        host_counts += [h for h in ([2] if quick else [2, 4])
                        if devices % h == 0]

    with tempfile.TemporaryDirectory() as td:
        ref, out = os.path.join(td, "ref.npy"), os.path.join(td, "rec.json")

        def launch(extra, hosts):
            rc = serve_multihost.main(
                ["--processes", str(hosts), "--devices", str(devices),
                 "--vertices", str(n), "--edges", str(e),
                 "--steps", str(steps), "--json-out", out] + extra)
            if rc != 0:
                raise RuntimeError(f"serve_multihost {extra} failed ({rc})")
            with open(out) as f:
                return _json.loads(f.read())

        eng = launch(["--arm", "engine", "--exchange", "gather"], 1)
        records = []
        for hosts in host_counts:
            parity = ["--ref-out", ref] if hosts == 1 else ["--ref-in", ref]
            rec = launch(["--arm", "resident"] + parity, hosts)
            rec["engine_steps_per_s"] = eng["steps_per_s"]
            rec["speedup_vs_engine"] = (rec["steps_per_s"]
                                        / eng["steps_per_s"])
            records.append(rec)
            emit(f"multihost_resident_h{hosts}_n{n}",
                 1e6 / rec["steps_per_s"],
                 f"steps_per_s={rec['steps_per_s']:.2f};"
                 f"speedup_vs_engine={rec['speedup_vs_engine']:.2f}x;"
                 f"halo_frac={rec['halo_frac']:.4f};"
                 f"parity_max_err={rec.get('parity_max_err', 0.0):.1e}")
    eng["engine_steps_per_s"] = eng["steps_per_s"]
    eng["speedup_vs_engine"] = 1.0
    records.append(eng)
    emit(f"multihost_engine_h1_n{n}", 1e6 / eng["steps_per_s"],
         f"steps_per_s={eng['steps_per_s']:.2f};"
         f"halo_frac={eng['halo_frac']:.4f}")
    return records


def run(quick: bool = True) -> None:
    import jax
    from jax.sharding import Mesh

    from repro.core import costs
    from repro.core.api import GraphEdgeController
    from repro.gnn.layers import gcn_init
    from repro.serve import ServingEngine

    cases = ([(128, 5, 2)] if quick else
             [(128, 8, 4), (256, 8, 4)])   # (users, topo steps, reqs/topo)
    devices = min(4, len(jax.devices()))
    mesh = Mesh(np.array(jax.devices()[:devices]), ("servers",))
    records = []
    for users, steps, repeats in cases:
        capacity = users + 8
        rng = np.random.default_rng(0)
        net = costs.default_network(rng, capacity, 4)
        params = gcn_init(jax.random.PRNGKey(0),
                          [FEATURES, HIDDEN, CLASSES])
        requests = _build_requests(rng, capacity, users, steps, repeats,
                                   change_rate=0.2)
        n_req = len(requests)

        # -- warmup both arms on the first request (compile/trace excluded)
        warm = [requests[0]]
        _sequential_pass(net, warm, mesh, params, devices)
        engine = ServingEngine(
            controller=GraphEdgeController(net=net, policy="greedy_jit"),
            params=params, mesh=mesh, num_devices=devices)
        engine.serve_all(warm)

        # -- sequential loop (fresh controller so its caches start cold)
        t0 = time.perf_counter()
        seq_outs = _sequential_pass(net, requests, mesh, params, devices)
        t_seq = time.perf_counter() - t0

        # -- pipelined-jit engine (fresh caches, jit compiles stay warm)
        engine = ServingEngine(
            controller=GraphEdgeController(net=net, policy="greedy_jit"),
            params=params, mesh=mesh, num_devices=devices)
        t0 = time.perf_counter()
        results = engine.serve_all(requests)
        t_eng = time.perf_counter() - t0

        eng_err = max(_oracle_err(params, r.output, r.request)
                      for r in results)
        seq_err = max(_oracle_err(params, o, r)
                      for o, r in zip(seq_outs, requests))
        pc, cc = engine.plan_cache_info(), engine.controller.cache_info()
        rec = {
            "users": users, "capacity": capacity, "devices": devices,
            "requests": n_req, "topology_steps": steps,
            "requests_per_topology": repeats,
            "seq_steps_per_sec": n_req / t_seq,
            "engine_steps_per_sec": n_req / t_eng,
            "speedup": t_seq / t_eng,
            "seq_oracle_max_err": seq_err,
            "engine_oracle_max_err": eng_err,
            "plan_cache": {"hits": pc.hits, "misses": pc.misses},
            "partition_cache": {"hits": cc.hits, "misses": cc.misses},
        }
        records.append(rec)
        emit(f"serving_sequential_u{users}", t_seq / n_req * 1e6,
             f"steps_per_sec={rec['seq_steps_per_sec']:.2f}")
        emit(f"serving_pipelined_jit_u{users}", t_eng / n_req * 1e6,
             f"steps_per_sec={rec['engine_steps_per_sec']:.2f};"
             f"speedup={rec['speedup']:.1f}x;"
             f"max_err={eng_err:.1e}")

    records.extend(_streaming_records(quick, mesh, devices))
    records.extend(_failure_records(quick, mesh, devices))
    records.extend(_multihost_records(quick, devices))
    write_bench_json(OUT_JSON, "serving", quick, records)


if __name__ == "__main__":
    import argparse

    from repro.launch.serve_gnn import _ensure_virtual_devices
    _ensure_virtual_devices(4)     # 4 virtual CPU devices; no-op on a TPU
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--full", action="store_true",
                    help="paper-scale axes (slow)")
    ap.add_argument("--quick", action="store_true",
                    help="small axes (the default; --full overrides)")
    args = ap.parse_args()
    run(quick=not args.full)
