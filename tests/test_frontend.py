"""Streaming front-end: bounded-queue backpressure, continuous batching
parity against the oracle, Lyapunov/static admission under simulated
overload, deadline shedding, the conservation invariant, and the SLO
telemetry plumbing (repro.serve.frontend / repro.serve.metrics)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.core import costs
from repro.core.api import GraphEdgeController
from repro.core.dynamic_graph import perturb_scenario, random_scenario
from repro.core.offload.lyapunov import virtual_queue_update
from repro.gnn.layers import gcn_apply, gcn_init
from repro.serve import (AdmitAll, LyapunovAdmission, ManualClock,
                         RequestTiming, ServingEngine,
                         StaticPriorityAdmission, StreamRequest,
                         StreamingFrontend, poisson_workload)
from repro.serve.frontend import (REJECT_ADMISSION, REJECT_DEADLINE,
                                  REJECT_QUEUE_FULL, _bucket)
from repro.serve.metrics import CycleTelemetry, percentiles, summarize


def make_engine(seed=0, capacity=24, users=18, m=3, e=40, **engine_kw):
    rng = np.random.default_rng(seed)
    state = random_scenario(rng, capacity, users, e)
    net = costs.default_network(rng, capacity, m)
    ctrl = GraphEdgeController(net=net, policy="greedy_jit")
    params = gcn_init(jax.random.PRNGKey(seed), [8, 6, 4])
    mesh = Mesh(np.array(jax.devices()[:1]), ("servers",))
    engine = ServingEngine(controller=ctrl, params=params, mesh=mesh,
                           **engine_kw)
    return engine, state, rng


def req(state, rng, tenant=0, deadline=None):
    x = rng.normal(size=(state.capacity, 8)).astype(np.float32)
    return StreamRequest(state, x, tenant=tenant, deadline=deadline)


def oracle_err(engine, res):
    st = res.request.state
    oracle = np.asarray(gcn_apply(engine.params, jnp.asarray(res.request.x),
                                  st.adj, st.mask))
    served = np.nonzero(np.asarray(st.mask) > 0)[0]
    return float(np.abs(res.output[served] - oracle[served]).max())


# -- bounded queue / backpressure ---------------------------------------------

def test_queue_full_backpressure_is_explicit():
    """Overflowing the bounded queue rejects with reason queue_full —
    counted, recorded, never silently dropped — and conservation holds at
    every instant."""
    engine, state, rng = make_engine()
    fe = StreamingFrontend(engine=engine, queue_depth=2,
                           clock=ManualClock(tick_per_now=0.01))
    assert fe.submit(req(state, rng))
    assert fe.stats.conservation_ok
    assert fe.submit(req(state, rng))
    assert not fe.submit(req(state, rng, tenant=7))    # full → backpressure
    assert fe.stats.submitted == 3
    assert fe.stats.rejected == {REJECT_QUEUE_FULL: 1}
    assert fe.stats.deferred == 2                      # still queued
    assert fe.stats.conservation_ok
    rej = fe.rejections[0]
    assert (rej.tenant, rej.reason) == (7, REJECT_QUEUE_FULL)
    fe.pump()
    assert fe.stats.served == 2 and fe.stats.deferred == 0
    assert fe.stats.conservation_ok


# -- continuous batching ------------------------------------------------------

def test_burst_batches_and_matches_oracle():
    """A same-topology burst forms real batches (one plan-cache entry, one
    decide per batch) and every member matches the single-device oracle."""
    engine, state, rng = make_engine()
    fe = StreamingFrontend(engine=engine, queue_depth=16, max_batch=4)
    results = fe.run([(0.0, req(state, rng)) for _ in range(6)])
    assert len(results) == 6
    assert fe.stats.batches == 2                       # 4 + 2
    assert sorted(r.batch_size for r in results) == [2, 2, 4, 4, 4, 4]
    assert fe.stats.batched_requests == 6
    assert engine.plan_cache_info().misses == 1        # one shared plan
    for r in results:
        assert oracle_err(engine, r) < 1e-4
    assert fe.stats.conservation_ok and fe.stats.deferred == 0
    slo = fe.slo_summary()
    assert slo["served"] == 6 and slo["sustained_rps"] > 0


def test_batch_groups_only_matching_topology():
    """The batch former only pulls queued requests sharing the head's
    topology fingerprint; others stay queued (not deferred, not rejected)
    for a later cycle."""
    engine, state, rng = make_engine()
    other = perturb_scenario(rng, state, 0.6)
    fe = StreamingFrontend(engine=engine, queue_depth=16, max_batch=8,
                           clock=ManualClock(tick_per_now=0.01))
    for s in (state, state, other, state):
        assert fe.submit(req(s, rng))
    first = fe.pump()
    assert len(first) == 3                             # the three on `state`
    assert all(r.batch_size == 3 for r in first)
    assert len(fe.queue) == 1 and fe.stats.defer_events == 0
    second = fe.pump()
    assert len(second) == 1 and second[0].request.state is other
    assert fe.stats.conservation_ok and fe.stats.deferred == 0
    for r in first + second:
        assert oracle_err(engine, r) < 1e-4


def test_batched_forward_matches_per_request_forward():
    """The batched dispatch path (scatter_batch → vmapped forward →
    gather_batch, with power-of-two padding) is numerically identical to
    serving each member through the plan's single-request forward."""
    engine, state, rng = make_engine()
    decision, entry, _ = engine.decide_entry(state)
    xs = [rng.normal(size=(state.capacity, 8)).astype(np.float32)
          for _ in range(3)]
    batched = engine.batched_forward(entry)
    blocks = entry.plan.scatter_batch(xs, pad_to=4)    # bucket pads 3 → 4
    outs = entry.plan.gather_batch(
        np.asarray(batched(blocks, engine.params)), count=3)
    for x, out in zip(xs, outs):
        single = entry.plan.gather(np.asarray(
            entry.forward(entry.plan.scatter(x), engine.params)))
        np.testing.assert_allclose(out, single, atol=1e-6)


# -- deadlines ---------------------------------------------------------------

def test_expired_deadline_rejected_not_served():
    """A queued request whose absolute deadline tick has passed is shed
    with reason deadline before any service is spent on it."""
    engine, state, rng = make_engine()
    clock = ManualClock(tick_per_now=0.0)
    fe = StreamingFrontend(engine=engine, queue_depth=8, clock=clock)
    assert fe.submit(req(state, rng, deadline=0.5))
    clock.advance(1.0)                                 # blow the budget
    assert fe.pump() == []
    assert fe.stats.rejected == {REJECT_DEADLINE: 1}
    assert fe.stats.served == 0 and fe.stats.conservation_ok


# -- admission control --------------------------------------------------------

def test_static_priority_sheds_low_ranks_over_high_water():
    """Above the high-water backlog only tenants ranked <= keep_rank keep
    admitting; everyone else is rejected outright (the ablation arm)."""
    engine, state, rng = make_engine()
    fe = StreamingFrontend(
        engine=engine, queue_depth=16,
        admission=StaticPriorityAdmission(high_water=2, keep_rank=0),
        clock=ManualClock(tick_per_now=0.01))
    for tenant in (0, 1, 1, 1):
        assert fe.submit(req(state, rng, tenant=tenant))
    results = fe.pump()
    assert [r.request.tenant for r in results] == [0]
    assert fe.stats.rejected == {REJECT_ADMISSION: 3}
    assert fe.stats.conservation_ok and fe.stats.deferred == 0


def test_lyapunov_defers_over_theta_then_drains():
    """Best-effort requests over the backlog bound are deferred (never
    rejected), the idle drain keeps the virtual queues decaying, and the
    whole queue eventually serves — no deadlock, nothing lost."""
    engine, state, rng = make_engine()
    adm = LyapunovAdmission(num_tenants=1, theta=0.5, idle_drain=1.0)
    fe = StreamingFrontend(engine=engine, queue_depth=16, admission=adm,
                           clock=ManualClock(tick_per_now=0.01))
    for _ in range(4):
        assert fe.submit(req(state, rng))              # one tenant floods
    served = []
    for _ in range(32):
        served.extend(fe.pump())
        if not len(fe.queue):
            break
    assert len(served) == 4                            # all eventually run
    assert fe.stats.defer_events > 0
    assert fe.stats.rejected == {}
    assert fe.stats.conservation_ok and fe.stats.deferred == 0
    assert adm.queue_max <= adm.theta + 1.0            # boundedness


def test_lyapunov_bounds_admitted_tail_under_overload():
    """Simulated overload (ManualClock: arrivals far above service): the
    Lyapunov arm sheds load with fully-accounted rejects while the
    *admitted* p99 stays within the SLO budget regime."""
    engine, state, rng = make_engine()
    deadline, tenants = 0.5, 3
    adm = LyapunovAdmission(num_tenants=tenants)
    fe = StreamingFrontend(engine=engine, queue_depth=8, max_batch=4,
                           admission=adm,
                           clock=ManualClock(tick_per_now=0.02))
    wl = poisson_workload(
        np.random.default_rng(3), rate=100.0, count=40,
        make_request=lambda i: req(state, rng, tenant=i % tenants,
                                   deadline=deadline))
    results = fe.run(wl)
    stats = fe.stats
    assert stats.submitted == 40
    assert stats.rejected_total > 0                    # overload sheds
    assert stats.conservation_ok and stats.deferred == 0
    assert stats.admitted == len(results)
    slo = fe.slo_summary()
    assert slo["total"]["p99"] <= 2 * deadline         # bounded tail
    for r in results:
        assert oracle_err(engine, r) < 1e-4


def test_virtual_queue_update_shared_recursion():
    """The front-end's admission controller runs on the same recursion as
    the per-server offload scheduler: Q ← max(Q + a − μ, 0)."""
    assert virtual_queue_update(0.0, 1.0, 0.0, xp=np) == 1.0
    assert virtual_queue_update(1.0, 0.0, 0.4, xp=np) == pytest.approx(0.6)
    assert virtual_queue_update(0.2, 0.0, 1.0, xp=np) == 0.0   # floor at 0
    adm = LyapunovAdmission(num_tenants=2, idle_drain=1.0)
    adm.q = {0: 1.0, 1: 0.25}
    adm.on_cycle(served=0, now=0.0)                    # idle drain μ = 0.5
    assert adm.q[0] == pytest.approx(0.5)
    assert adm.q[1] == 0.0


# -- telemetry ----------------------------------------------------------------

def test_request_timing_phases_and_percentiles():
    t = RequestTiming(arrival=1.0, admit=1.5, dispatch=1.75, done=2.0)
    assert t.phases() == {"queue_wait": 0.5, "decide": 0.25,
                          "forward": 0.25, "total": 1.0}
    p = percentiles([1.0, 2.0, 3.0, 4.0])
    assert p["p50"] == 2.5 and p["max"] == 4.0 and p["mean"] == 2.5
    s = summarize([t, RequestTiming(arrival=2.0, admit=2.0, dispatch=2.5,
                                    done=3.0)])
    assert s["served"] == 2
    assert s["sustained_rps"] == pytest.approx(1.0)    # span 1.0→3.0
    assert s["total"]["max"] == 1.0
    assert summarize([]) == {"served": 0, "sustained_rps": 0.0}


def test_bucket_cap_semantics():
    """Property-pinned _bucket contract: the result is always ≥ b (the cap
    bounds padding — it must never shrink a batch below the members
    already in it), never exceeds max(b, max_batch), and for b within the
    front-end's own limit it is the smallest power of two ≥ b capped at
    max_batch."""
    for max_batch in (1, 2, 3, 4, 8, 12, 16):
        prev = 0
        for b in range(1, 3 * max_batch + 2):
            got = _bucket(b, max_batch)
            assert got >= b                          # never truncates
            assert got <= max(b, max_batch)          # cap honored
            assert got >= prev                       # monotone in b
            prev = got
            if b <= max_batch:
                assert got <= max_batch
                pow2 = 1 << (b - 1).bit_length()
                assert got == min(pow2, max_batch)
            else:
                assert got == b                      # oversize passes thru


# -- cross-topology batching --------------------------------------------------

def test_cross_topology_single_dispatch_serves_mixed_batch():
    """With cross_topology=True one pump cycle serves requests on
    different (same-bucket) topologies as ONE cross dispatch — and each
    member still matches its own topology's oracle."""
    engine, state, rng = make_engine()
    others = [perturb_scenario(rng, state, 0.2) for _ in range(2)]
    fe = StreamingFrontend(engine=engine, queue_depth=16, max_batch=8,
                           cross_topology=True,
                           clock=ManualClock(tick_per_now=0.01))
    for s in (state, others[0], state, others[1]):
        assert fe.submit(req(s, rng))
    results = fe.pump()
    assert len(results) == 4
    assert fe.stats.cross_batches == 1
    assert fe.stats.cross_batched_requests == 4
    assert len(fe.queue) == 0 and fe.stats.conservation_ok
    for r in results:
        assert oracle_err(engine, r) < 1e-4


def test_cross_topology_run_matches_sequential_engine_exactly():
    """End to end: a stream alternating over perturbed topologies served
    cross-topology is bit-exact against the sequential ServingEngine
    oracle (aggregate pinned so both sides run the identical kernel)."""
    engine, state, rng = make_engine(aggregate="fused")
    topos = [state] + [perturb_scenario(rng, state, 0.25)
                       for _ in range(3)]
    reqs = [req(topos[i % len(topos)], rng) for i in range(12)]
    fe = StreamingFrontend(engine=engine, queue_depth=32, max_batch=8,
                           cross_topology=True)
    results = fe.run([(0.0, r) for r in reqs])
    assert len(results) == 12 and fe.stats.cross_batches >= 1
    from repro.serve.engine import ServeRequest
    oracle_engine, _, _ = make_engine(aggregate="fused")
    by_rid = {r.rid: r for r in results}
    seq = oracle_engine.serve_all(
        [ServeRequest(r.state, r.x) for r in reqs])
    for rid, res in enumerate(seq):
        assert float(np.abs(by_rid[rid].output - res.output).max()) == 0.0
    assert fe.stats.conservation_ok and fe.stats.deferred == 0


def test_cross_topology_off_keeps_topology_gate():
    """cross_topology=False (the default) preserves the PR 6 behavior:
    only the head's topology joins a cycle."""
    engine, state, rng = make_engine()
    other = perturb_scenario(rng, state, 0.6)
    fe = StreamingFrontend(engine=engine, queue_depth=16, max_batch=8,
                           clock=ManualClock(tick_per_now=0.01))
    for s in (state, other, state):
        assert fe.submit(req(s, rng))
    assert len(fe.pump()) == 2 and fe.stats.cross_batches == 0
    assert len(fe.queue) == 1


# -- weighted tenant shares ---------------------------------------------------

def test_lyapunov_weighted_shares_drain_proportionally():
    adm = LyapunovAdmission(num_tenants=2, idle_drain=1.0,
                            weights={0: 3.0, 1: 1.0})
    adm.q = {0: 1.0, 1: 1.0}
    adm.on_cycle(served=0, now=0.0)       # capacity 1.0 split 3:1
    assert adm.q[0] == pytest.approx(0.25)
    assert adm.q[1] == pytest.approx(0.75)
    with pytest.raises(ValueError):
        LyapunovAdmission(weights={0: 0.0})


def test_lyapunov_starvation_bound_holds():
    """A deferred tenant re-enters the admit region within the analytic
    starvation bound even when every cycle is idle (worst case: drain is
    only the guaranteed minimum share)."""
    adm = LyapunovAdmission(num_tenants=3, theta=1.0, idle_drain=1.0,
                            weights={2: 0.5})
    start = 6.0
    adm.q = {2: start}
    adm.queue_max = start
    bound = adm.starvation_bound(2)
    assert bound == int(np.ceil((start - adm.theta)
                                / (1.0 * 0.5 / 2.5)))
    cycles = 0
    while adm.q[2] > adm.theta:
        adm.on_cycle(served=0, now=float(cycles))
        cycles += 1
        assert cycles <= bound
    assert cycles <= bound
    # a heavier tenant's bound is proportionally tighter
    assert adm.starvation_bound(0, backlog=start) < bound


def test_lyapunov_weighted_tenant_admits_more_under_contention():
    """Under a symmetric two-tenant flood, the weight-4 tenant's admitted
    share exceeds the weight-1 tenant's."""
    engine, state, rng = make_engine()
    adm = LyapunovAdmission(num_tenants=2, theta=1.5, idle_drain=1.0,
                            weights={0: 4.0, 1: 1.0})
    fe = StreamingFrontend(engine=engine, queue_depth=64, max_batch=2,
                           admission=adm,
                           clock=ManualClock(tick_per_now=0.01))
    served = {0: 0, 1: 0}
    for cycle in range(30):
        for tenant in (0, 1):
            fe.submit(req(state, rng, tenant=tenant))
        for r in fe.pump():
            served[r.request.tenant] += 1
    assert served[0] > served[1] > 0
    assert fe.stats.conservation_ok


# -- decide-stage telemetry ---------------------------------------------------

def test_cycle_telemetry_histogram_and_decide_percentiles():
    t = CycleTelemetry()
    for c, (b, d, disp, f) in enumerate(((4, 0.2, 0.01, 0.05),
                                         (4, 0.4, 0.03, 0.01),
                                         (2, 0.1, 0.02, 0.5),
                                         (1, 0.3, 0.04, 0.02))):
        t.record(10 + c, b, d, disp, f)
    d = t.as_dict()
    assert d["cycles"] == 4
    assert d["batch_hist"] == {"1": 1, "2": 1, "4": 2}
    assert d["batch_mean"] == pytest.approx(2.75)
    assert d["decide"]["p50"] == pytest.approx(0.25)
    assert d["decide"]["p95"] == pytest.approx(
        float(np.percentile([0.2, 0.4, 0.1, 0.3], 95)))
    assert d["decide_per_request"]["max"] == pytest.approx(0.3)
    # the other two stages, and every cycle kept whole, slowest first
    assert d["dispatch"]["p50"] == pytest.approx(0.025)
    assert d["fetch"]["p95"] == pytest.approx(
        float(np.percentile([0.05, 0.01, 0.5, 0.02], 95)))
    assert [s["cycle"] for s in d["slowest"]] == [12, 11, 13, 10]
    assert d["slowest"][0] == {"cycle": 12, "batch": 2, "decide": 0.1,
                               "dispatch": 0.02, "fetch": 0.5,
                               "total": pytest.approx(0.62)}


def test_cycle_telemetry_keeps_only_the_slowest_cycles():
    from repro.serve.metrics import SLOWEST_KEPT
    t = CycleTelemetry()
    totals = [0.3, 0.1, 0.9, 0.5, 0.2, 0.8, 0.7, 0.4, 0.6, 1.0, 0.05, 0.95]
    for c, total in enumerate(totals):
        t.record(c, 1, total, 0.0, 0.0)
    slow = t.as_dict()["slowest"]
    assert len(slow) == SLOWEST_KEPT == 8
    assert [s["total"] for s in slow] == sorted(totals, reverse=True)[:8]
    assert slow[0]["cycle"] == totals.index(1.0)
    assert t.as_dict()["cycles"] == len(totals)


def test_frontend_records_cycle_telemetry_under_manual_clock():
    """The front-end logs one telemetry sample per non-empty cycle with
    deterministic ManualClock decide latencies (admit→dispatch = the
    fixed per-now tick) and the per-cycle batch sizes."""
    engine, state, rng = make_engine()
    fe = StreamingFrontend(engine=engine, queue_depth=16, max_batch=4,
                           clock=ManualClock(tick_per_now=0.01))
    for _ in range(6):
        assert fe.submit(req(state, rng))
    fe.pump()
    fe.pump()
    d = fe.cycles.as_dict()
    assert d["cycles"] == 2
    assert d["batch_hist"] == {"2": 1, "4": 1}
    # ManualClock: every now() call advances 0.01; the decide phase spans
    # a fixed number of calls, so p50 == p95 deterministically
    assert d["decide"]["p50"] == pytest.approx(d["decide"]["p95"])
    assert d["decide"]["p50"] > 0
    assert fe.stats_dict()["cycles"]["cycles"] == 2
    # decide (admit → decided) no longer counts the dispatch stage; each
    # served request's timing names the cycle whose record holds its split
    assert d["decide"]["p50"] == pytest.approx(0.01)
    assert d["dispatch"]["p50"] == pytest.approx(0.01)
    assert d["fetch"]["p50"] > 0
    slow = {s["cycle"]: s for s in d["slowest"]}
    assert sorted(slow) == [0, 1]
    for s in slow.values():
        assert s["total"] == pytest.approx(s["decide"] + s["dispatch"]
                                           + s["fetch"])
    assert {slow[c]["batch"] for c in slow} == {2, 4}
    assert [t.cycle for t in fe.timings] == [0] * 4 + [1] * 2


# -- concurrent intake --------------------------------------------------------

def test_run_threaded_overlaps_intake_and_serves_everything():
    """The threaded driver (producer thread + pump loop) drains a Poisson
    workload with full conservation and oracle-correct outputs."""
    engine, state, rng = make_engine()
    other = perturb_scenario(rng, state, 0.3)
    fe = StreamingFrontend(engine=engine, queue_depth=64, max_batch=8,
                           cross_topology=True)
    wl = poisson_workload(
        rng, rate=500.0, count=30,
        make_request=lambda i: req((state, other)[i % 2], rng,
                                   tenant=i % 3))
    results = fe.run_threaded(wl)
    assert len(results) == 30
    assert fe.stats.submitted == 30
    assert sorted(r.rid for r in results) == list(range(30))
    assert fe.stats.conservation_ok and fe.stats.deferred == 0
    assert max(oracle_err(engine, r) for r in results) < 1e-4


def test_run_drains_open_loop_poisson_workload():
    """End to end on the wall clock: a Poisson stream over two topologies
    and three tenants drains, serves in batches, and conserves."""
    engine, state, rng = make_engine()
    other = perturb_scenario(rng, state, 0.4)
    fe = StreamingFrontend(engine=engine, queue_depth=64, max_batch=8,
                           admission=AdmitAll())
    wl = poisson_workload(
        rng, rate=400.0, count=24,
        make_request=lambda i: req((state, other)[i % 2], rng,
                                   tenant=i % 3))
    results = fe.run(wl)
    assert len(results) == 24
    assert fe.stats.conservation_ok and fe.stats.deferred == 0
    assert fe.stats.batches < 24                       # batching happened
    assert engine.plan_cache_info().misses == 2        # one per topology
    assert max(oracle_err(engine, r) for r in results) < 1e-4


# -- amortized admission-time service estimate --------------------------------

def test_est_service_amortizes_decide_over_backlog():
    """The batched decide is a per-cycle cost: the admission-time estimate
    spreads it over the batch the backlog supports (capped at max_batch)
    and charges the per-request forward cost whole — a deep backlog must
    never look *slower* per request than a shallow one."""
    engine, state, rng = make_engine()
    fe = StreamingFrontend(engine=engine, queue_depth=32, max_batch=8,
                           clock=ManualClock(tick_per_now=0.01))
    for _ in range(4):
        fe.submit(req(state, rng))
    fe.pump()
    assert fe._est_decide > 0.0 and fe._est_forward > 0.0
    # amortization: decide cost split max_batch ways at deep backlog
    deep = fe.est_service(backlog=fe.max_batch)
    shallow = fe.est_service(backlog=1)
    assert deep == fe._est_decide / fe.max_batch + fe._est_forward
    assert shallow == fe._est_decide + fe._est_forward
    assert deep < shallow
    # backlog beyond max_batch can't amortize further (one cycle's batch)
    assert fe.est_service(backlog=100) == deep
    assert fe.est_service(backlog=0) == shallow         # empty queue: 1
    stats = fe.stats_dict()
    assert stats["est_decide"] == fe._est_decide
    assert stats["est_forward"] == fe._est_forward
    assert stats["est_service"] == fe.est_service(len(fe.queue))


def test_admission_sees_amortized_not_full_cycle_cost():
    """The controller's ``decide`` receives the amortized estimate — the
    decide cost split over the backlog's batch, not the full cycle cost
    per request (the old, systematically pessimistic behaviour that shed
    deadlines the batched cycle would comfortably meet)."""
    class Recorder(AdmitAll):
        def __init__(self):
            self.seen = []

        def decide(self, entry, now, backlog, est_service):
            self.seen.append((backlog, est_service))
            return super().decide(entry, now, backlog, est_service)

    engine, state, rng = make_engine()
    rec = Recorder()
    fe = StreamingFrontend(engine=engine, queue_depth=32, max_batch=8,
                           admission=rec,
                           clock=ManualClock(tick_per_now=0.01))
    for _ in range(8):
        fe.submit(req(state, rng))
    fe.pump()                                   # estimates now warm
    rec.seen.clear()
    d0, f0 = fe._est_decide, fe._est_forward    # pre-cycle EWMA state
    assert d0 > 0.0 and f0 > 0.0
    for _ in range(8):
        fe.submit(req(state, rng))
    fe.pump()
    backlog, est = rec.seen[0]
    assert backlog == 8
    assert est == d0 / 8 + f0 < d0 + f0
    # every candidate of the cycle saw the same (cycle-scoped) estimate
    assert all(e == est for _, e in rec.seen)
