"""Streaming request front-end: queue, admission control, continuous batching.

The pipelined :class:`~repro.serve.engine.ServingEngine` serves one
pre-materialized request stream at depth 1 — nothing models concurrent
users pushing requests faster than the engine drains them. This module is
the production-shaped front of the serving tier (the Fograph
fog-serving architecture, arXiv:2307.01684, is the reference shape):

* :class:`RequestQueue` — a **bounded** queue of :class:`StreamRequest`
  (tenant id, arrival tick, deadline). Backpressure is explicit: when the
  queue is full, ``submit`` rejects with reason ``"queue_full"`` and the
  rejection is counted and recorded — requests are *never* silently
  dropped, and ``admitted + rejected + deferred == submitted`` holds at
  every instant (the conservation invariant CI gates on).
* **Continuous batching** — each scheduling cycle groups queued requests
  that share the head-of-line request's *topology fingerprint* (and
  therefore its ``(topology_key, assignment_digest)`` plan-cache entry):
  one control decision, one ``plan.scatter_batch`` to [P, B, L, F], one
  dispatch of the cached plan's batched forward
  (:func:`repro.gnn.distributed.make_batched_forward_fn`). B concurrent
  requests on an unchanged topology cost one XLA dispatch instead of B.
  Batch sizes are padded to power-of-two buckets so compiles stay bounded.
  The GCN output depends only on the topology (adjacency + mask) and the
  features — never on the offload placement — so members of a batch are
  exactly the requests whose output the head's plan computes correctly.
* **Lyapunov admission control** — :class:`LyapunovAdmission` keeps one
  virtual queue per *tenant*, reusing the drift-plus-penalty update
  :func:`repro.core.offload.lyapunov.virtual_queue_update` (the same
  recursion the per-server offload scheduler scans): admitting a tenant's
  request is an arrival on its queue, every serviced batch drains all
  queues by the fair per-tenant share, and the admit/defer/reject decision
  minimizes ``Q_tenant + V · (projected latency / deadline)`` against the
  backlog bound θ. A flooding tenant builds backlog and gets rejected or
  deferred while light tenants keep admitting, so the *admitted* p99
  stays bounded under overload. :class:`StaticPriorityAdmission` is the
  ablation baseline (fixed tenant ranks, no queue state, no deadlines);
  :class:`AdmitAll` is the no-control arm.
* **SLO telemetry** — every request is stamped on the injectable tick
  clock (``repro.serve.metrics``) at arrival/admit/dispatch/done;
  ``stats()`` aggregates p50/p95/p99 per phase and sustained requests/sec
  in the ``BENCH_serving.json`` streaming-record shape.

``StreamingFrontend.run(workload)`` drives an **open-loop** workload (a
sorted ``(arrival_offset, request)`` iterable — see
:func:`poisson_workload`): arrivals are injected on schedule regardless of
service progress, so overload manifests as queue growth → backpressure,
exactly the regime the admission controller is for.
``repro.launch.serve_stream`` is the CLI.
"""
from __future__ import annotations

import math
import threading
from dataclasses import dataclass, field
from typing import Iterable, Iterator, Protocol, runtime_checkable

import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.api import LruCache, topology_key
from repro.core.dynamic_graph import GraphState
from repro.core.offload.lyapunov import virtual_queue_update
from repro.gnn.distributed import gather_multi, scatter_multi
from repro.serve.engine import ServingEngine
from repro.serve.faults import FaultInjector
from repro.serve.metrics import (CycleTelemetry, ManualClock, MonotonicClock,
                                 RequestTiming, summarize)

# rejection reasons (the only terminal states besides "served")
REJECT_QUEUE_FULL = "queue_full"     # bounded-queue backpressure at submit
REJECT_ADMISSION = "admission"       # admission controller said no
REJECT_DEADLINE = "deadline"         # SLO budget already (or provably) blown

ADMIT, DEFER, REJECT = "admit", "defer", "reject"


@dataclass(frozen=True)
class StreamRequest:
    """One streamed inference request.

    ``deadline`` is a *relative* SLO budget in clock ticks (seconds on the
    default monotonic clock) from arrival; ``None`` = best effort.
    ``rid`` is stamped by the front-end at submit when not provided."""
    state: GraphState
    x: np.ndarray                    # [N, F_in] vertex features
    tenant: int = 0
    deadline: float | None = None
    rid: int | None = None


@dataclass
class _Entry:
    """A queued request + its bookkeeping (timing stamps, lazy topo key)."""
    req: StreamRequest
    rid: int
    timing: RequestTiming
    deadline_tick: float | None      # absolute tick, None = best effort
    topo: str | None = None
    defers: int = 0
    migrations: int = 0              # network swaps survived while queued

    def topo_key(self) -> str:
        if self.topo is None:
            self.topo = topology_key(self.req.state)
        return self.topo


@dataclass(frozen=True)
class Rejection:
    """One rejected request — every non-served request gets exactly one."""
    rid: int
    tenant: int
    reason: str
    tick: float
    defers: int = 0


@dataclass(frozen=True)
class StreamResult:
    """One served request. ``decision`` is the control decision of the
    request's *own* topology (decided in the cycle's batched controller
    call); ``batch_size`` is the size of the dispatch group that served
    it."""
    rid: int
    request: StreamRequest
    output: np.ndarray               # [N, F_out] gathered global output
    timing: RequestTiming
    batch_size: int
    plan_cache_hit: bool
    decision: object = None


class RequestQueue:
    """Bounded FIFO of queued entries with explicit backpressure: ``offer``
    returns False (and the front-end records a ``queue_full`` rejection)
    instead of ever dropping silently."""

    def __init__(self, depth: int):
        self.depth = int(depth)
        self._q: list[_Entry] = []

    def offer(self, entry: _Entry) -> bool:
        if len(self._q) >= self.depth:
            return False
        self._q.append(entry)
        return True

    def replace(self, entries: list[_Entry]) -> None:
        """Install the survivors of a scheduling pass (FIFO order kept)."""
        self._q = entries

    def __len__(self) -> int:
        return len(self._q)

    def __iter__(self) -> Iterator[_Entry]:
        return iter(self._q)


# ---------------------------------------------------------------------------
# admission controllers
# ---------------------------------------------------------------------------

@runtime_checkable
class AdmissionController(Protocol):
    """Admit/defer/reject decision per candidate request, once per cycle.

    ``decide`` sees the candidate entry, the current tick, the queue
    backlog and the front-end's *amortized* per-request service-time
    estimate (the batched ``decide_entries`` cycle cost spread over the
    batch the backlog supports, plus the per-request forward cost —
    :meth:`StreamingFrontend.est_service`);
    ``on_cycle(served, now)`` is called once per scheduling cycle with the
    number of requests just serviced (0 for an idle/all-deferred cycle) so
    queue-state controllers can drain."""

    def decide(self, entry: _Entry, now: float, backlog: int,
               est_service: float) -> str: ...

    def on_cycle(self, served: int, now: float) -> None: ...


class AdmitAll:
    """No admission control: everything the bounded queue accepted runs."""
    name = "admit_all"

    def decide(self, entry, now, backlog, est_service) -> str:
        return ADMIT

    def on_cycle(self, served, now) -> None:
        pass


class StaticPriorityAdmission:
    """Static-priority baseline (the ablation arm): tenants carry fixed
    ranks (default: tenant id — lower is more important). Below the
    ``high_water`` backlog everyone admits; above it only tenants ranked
    ``<= keep_rank`` do, everyone else is rejected outright. No queue
    state, no deadline awareness — under overload the admitted latency of
    the privileged tenants is protected but nothing bounds anyone's p99."""
    name = "static_priority"

    def __init__(self, high_water: int = 32, keep_rank: int = 0,
                 priority: dict[int, int] | None = None):
        self.high_water = int(high_water)
        self.keep_rank = int(keep_rank)
        self.priority = dict(priority or {})

    def rank(self, tenant: int) -> int:
        return self.priority.get(tenant, tenant)

    def decide(self, entry, now, backlog, est_service) -> str:
        if backlog <= self.high_water:
            return ADMIT
        return ADMIT if self.rank(entry.req.tenant) <= self.keep_rank \
            else REJECT

    def on_cycle(self, served, now) -> None:
        pass


class LyapunovAdmission:
    """Per-tenant virtual-queue drift-plus-penalty admission control.

    The same recursion as the per-server offload scheduler
    (``repro.core.offload.lyapunov``), lifted to the serving tier:

    * admitting a request from tenant τ is an **arrival** on Q_τ
      (``Q_τ ← max(Q_τ + 1 − 0, 0)`` via :func:`virtual_queue_update`);
    * every scheduling cycle **drains** all queues by the fair per-tenant
      service share ``μ_τ = max(served, idle_drain) / T`` — a serviced
      batch is capacity actually delivered, an idle cycle still offers
      ``idle_drain`` of it (so an all-deferred queue always makes
      progress: Q decays until someone admits again);
    * the decision minimizes the drift-plus-penalty score
      ``Q_τ + V · (wait + est_service) / deadline`` against the backlog
      bound ``theta``: admit below it, defer above it while the deadline
      still has slack for another cycle, reject otherwise. A request whose
      budget is already un-meetable (``wait + est_service > deadline``)
      is rejected immediately — admitting it would burn service on a
      guaranteed SLO miss.

    ``theta`` bounds every tenant's admitted-but-unserved backlog, so the
    *admitted* latency tail stays bounded no matter how hard one tenant
    floods; ``V`` trades fairness pressure against deadline pressure
    (``V = 0`` → pure per-tenant fair queueing).

    Per-tenant **service weights** skew the fair share: ``weights[τ]``
    (default 1.0) scales tenant τ's drain rate to
    ``μ_τ = max(served, idle_drain) · w_τ / Σw``, so a weight-3 tenant
    drains — and therefore admits — 3× as fast as a weight-1 tenant under
    contention, while every tenant keeps a *guaranteed* minimum drain of
    ``idle_drain · w_τ / Σw`` per cycle. That minimum gives the starvation
    bound (:meth:`starvation_bound`): a tenant deferred at backlog Q re-
    enters the admit region ``Q ≤ θ`` within ``⌈(Q − θ)·Σw/(d·w_τ)⌉``
    cycles no matter what the other tenants do."""
    name = "lyapunov"

    def __init__(self, num_tenants: int = 1, v: float = 1.0,
                 theta: float = 8.0, idle_drain: float = 1.0,
                 weights: dict[int, float] | None = None):
        self.num_tenants = max(1, int(num_tenants))
        self.v = float(v)
        self.theta = float(theta)
        self.idle_drain = float(idle_drain)
        self.weights = {int(k): float(v_) for k, v_ in
                        (weights or {}).items()}
        if any(w <= 0 for w in self.weights.values()):
            raise ValueError(f"tenant weights must be > 0: {self.weights}")
        self.q: dict[int, float] = {}
        self.queue_max = 0.0          # boundedness certificate for tests

    def weight(self, tenant: int) -> float:
        return self.weights.get(tenant, 1.0)

    def total_weight(self) -> float:
        return sum(self.weight(t) for t in range(self.num_tenants))

    def starvation_bound(self, tenant: int, backlog: float | None = None
                         ) -> int:
        """Worst-case cycles until tenant τ's virtual queue re-enters the
        admit region ``Q_τ ≤ θ`` from ``backlog`` (default: the largest
        backlog any tenant has ever reached). Every ``on_cycle`` drains
        Q_τ by at least ``idle_drain · w_τ / Σw`` — even an idle or
        all-deferred cycle — so no admissible tenant is starved longer
        than ``⌈(Q − θ) · Σw / (idle_drain · w_τ)⌉`` cycles, whatever the
        other tenants submit (tested in ``tests/test_frontend.py``)."""
        q0 = self.queue_max if backlog is None else float(backlog)
        mu_min = self.idle_drain * self.weight(tenant) / self.total_weight()
        return int(math.ceil(max(q0 - self.theta, 0.0) / mu_min))

    def decide(self, entry, now, backlog, est_service) -> str:
        tenant = entry.req.tenant
        wait = now - entry.timing.arrival
        deadline = entry.req.deadline
        projected = wait + est_service
        if deadline is not None and projected > deadline:
            return REJECT             # provably un-meetable SLO
        q_t = self.q.get(tenant, 0.0)
        penalty = (projected / deadline) if deadline else 0.0
        if q_t + self.v * penalty <= self.theta:
            q_t = float(virtual_queue_update(q_t, 1.0, 0.0, xp=np))
            self.q[tenant] = q_t
            self.queue_max = max(self.queue_max, q_t)
            return ADMIT
        # over the backlog bound: hold the request while its budget still
        # has slack for (at least) one more service cycle, else shed it
        if deadline is None or projected + est_service <= deadline:
            return DEFER
        return REJECT

    def on_cycle(self, served, now) -> None:
        cap = max(float(served), self.idle_drain) / self.total_weight()
        for tenant, q_t in self.q.items():
            mu = cap * self.weight(tenant)
            self.q[tenant] = float(virtual_queue_update(q_t, 0.0, mu,
                                                        xp=np))


# ---------------------------------------------------------------------------
# the front-end
# ---------------------------------------------------------------------------

def _bucket(b: int, max_batch: int) -> int:
    """Smallest power-of-two ≥ b, capped at ``max_batch`` — the batch axis
    is padded to these buckets so each plan compiles O(log max_batch)
    times. The cap bounds *padding*, never the members already in the
    batch: a ``b > max_batch`` (callers that batch beyond the front-end's
    own limit) keeps its exact size rather than being truncated below b,
    so the result is always ≥ b and ≤ max(b, max_batch)."""
    p = 1
    while p < b:
        p <<= 1
    return max(b, min(p, max_batch))


@dataclass
class FrontendStats:
    """Terminal-state counters. The conservation invariant —
    ``admitted + rejected + deferred + migrated == submitted`` — holds at
    every instant: ``deferred`` and ``migrated`` together are the requests
    still queued (their decision deferred to a later cycle; ``migrated``
    counts the queued requests that have survived ≥ 1 network swap and
    will be re-planned against the new pricing); at the end of a drained
    run both are 0 and every request is accounted admitted or rejected —
    fault migrations lose nothing."""
    submitted: int = 0
    admitted: int = 0
    served: int = 0
    deferred: int = 0                 # currently queued (non-terminal)
    migrated: int = 0                 # queued across ≥1 net swap (non-term.)
    defer_events: int = 0             # total individual defer decisions
    requests_migrated: int = 0        # distinct requests ever migrated
    migrated_served: int = 0          # migrated requests that reached serve
    rejected: dict[str, int] = field(default_factory=dict)
    batches: int = 0
    batched_requests: int = 0         # requests served in batches of ≥ 2
    cross_batches: int = 0            # dispatches spanning > 1 cached plan
    cross_batched_requests: int = 0

    @property
    def rejected_total(self) -> int:
        return sum(self.rejected.values())

    @property
    def conservation_ok(self) -> bool:
        return self.admitted + self.rejected_total + self.deferred \
            + self.migrated == self.submitted

    def as_dict(self) -> dict:
        return {"submitted": self.submitted, "admitted": self.admitted,
                "served": self.served, "deferred": self.deferred,
                "migrated": self.migrated,
                "defer_events": self.defer_events,
                "requests_migrated": self.requests_migrated,
                "migrated_served": self.migrated_served,
                "rejected": dict(self.rejected),
                "rejected_total": self.rejected_total,
                "batches": self.batches,
                "batched_requests": self.batched_requests,
                "cross_batches": self.cross_batches,
                "cross_batched_requests": self.cross_batched_requests,
                "conservation_ok": self.conservation_ok}


@dataclass
class StreamingFrontend:
    """Bounded queue + admission + continuous batching over a
    :class:`~repro.serve.engine.ServingEngine`.

    ``pump()`` runs one scheduling cycle (admission pass → batch former →
    ONE vmapped control decision for the whole cycle → one batched
    dispatch per plan/bucket group) and returns the served results;
    ``run()`` drives a whole open-loop workload to drain and
    ``run_threaded()`` overlaps arrival and dispatch with a concurrent
    producer thread (``submit`` is thread-safe). The engine's plan cache
    is the batching substrate: with ``cross_topology=False`` the batch
    key is the head's plan-cache key (only same-topology requests group);
    with ``cross_topology=True`` the key is the plan's *shape bucket*
    (:meth:`ServingEngine.entry_bucket`) and one dispatch of the
    multi-plan forward serves requests resolved against different cached
    plans (:meth:`ServingEngine.cross_batched_forward`)."""
    engine: ServingEngine
    queue_depth: int = 64
    max_batch: int = 8
    admission: AdmissionController = field(default_factory=AdmitAll)
    clock: MonotonicClock | ManualClock = field(
        default_factory=MonotonicClock)
    service_ewma: float = 0.2        # EWMA weight of new service samples
    cross_topology: bool = False
    faults: FaultInjector | None = None

    def __post_init__(self):
        self.queue = RequestQueue(self.queue_depth)
        self.stats = FrontendStats()
        self.rejections: list[Rejection] = []
        self.timings: list[RequestTiming] = []
        self.cycles = CycleTelemetry()
        self._est_decide = 0.0       # per-CYCLE batched decide cost (EWMA)
        self._est_forward = 0.0      # per-REQUEST dispatch+fetch cost (EWMA)
        self._next_rid = 0
        self._lock = threading.Lock()   # guards queue + stats + telemetry
        self._topo_memo = LruCache(1024)
        self._cycle = 0              # logical pump clock (drives faults)
        self.fault_trace: list[dict] = []
        self._awaiting_recovery: list[dict] = []
        self._last_subgraph = LruCache(256)   # topo → last decided subgraph

    def _ewma(self, old: float, sample: float) -> float:
        return sample if old == 0.0 else \
            (1 - self.service_ewma) * old + self.service_ewma * sample

    def est_service(self, backlog: int) -> float:
        """Amortized per-request service estimate at the given backlog.

        The cycle's ONE vmapped ``decide_entries`` call costs the same
        whether it decides 1 or ``max_batch`` requests, so its EWMA
        (``_est_decide``, per cycle) is spread over the batch the current
        backlog supports — charging every candidate the *full* decide
        cost (the old behaviour) made admission under overload
        systematically pessimistic, shedding requests whose deadline the
        batched cycle would comfortably meet. The per-request
        dispatch+fetch cost (``_est_forward``) is genuinely per request
        and is charged whole."""
        share = min(max(backlog, 1), self.max_batch)
        return self._est_decide / share + self._est_forward

    def _topo_key_of(self, state: GraphState) -> str:
        """Topology fingerprint, memoized on state *identity*: streaming
        workloads reuse a handful of state objects across thousands of
        requests, and hashing the edge list per request (~70 µs) would
        dominate the batched cycle. The cached value keeps a reference to
        its state, so a recycled ``id`` can never alias a dead object."""
        got = self._topo_memo.get(id(state))
        if got is not None and got[0] is state:
            return got[1]
        key = topology_key(state)
        self._topo_memo.put(id(state), (state, key))
        return key

    # -- intake --------------------------------------------------------------
    def submit(self, req: StreamRequest) -> bool:
        """Enqueue a request; False = backpressure (``queue_full`` reject,
        counted and recorded — never a silent drop). Thread-safe: producer
        threads submit concurrently with the pump loop."""
        with self._lock:
            now = self.clock.now()
            rid = req.rid if req.rid is not None else self._next_rid
            self._next_rid = max(self._next_rid, rid) + 1
            self.stats.submitted += 1
            deadline_tick = None if req.deadline is None \
                else now + float(req.deadline)
            entry = _Entry(req, rid, RequestTiming(arrival=now),
                           deadline_tick, topo=self._topo_key_of(req.state))
            if not self.queue.offer(entry):
                self._reject(entry, REJECT_QUEUE_FULL, now)
                self._sync_queue_stats()
                return False
            self._sync_queue_stats()
            return True

    def _reject(self, entry: _Entry, reason: str, tick: float) -> None:
        self.stats.rejected[reason] = self.stats.rejected.get(reason, 0) + 1
        self.rejections.append(Rejection(entry.rid, entry.req.tenant,
                                         reason, tick, entry.defers))

    def _sync_queue_stats(self) -> None:
        """Recount the non-terminal states from the queue itself (the
        conservation invariant's ``deferred + migrated`` is always derived,
        never incrementally drifted)."""
        mig = sum(1 for e in self.queue if e.migrations)
        self.stats.migrated = mig
        self.stats.deferred = len(self.queue) - mig

    # -- fault injection -----------------------------------------------------
    def _poll_faults(self) -> None:
        """Apply due fault events at a pump boundary (nothing in flight).

        On a server event the engine's network is swapped FIRST (which
        flushes the controller's topology-keyed partition cache) and every
        still-queued topology with a recorded previous cut is then
        warm-recut (:meth:`~repro.core.api.GraphEdgeController.recut_warm`)
        against the surviving servers, so the next cycle's decisions start
        from the migrated plan instead of a cold re-partition. Queued
        requests are marked migrated — never dropped — and the migration
        is appended to :attr:`fault_trace`."""
        if self.faults is None:
            return
        update = self.faults.poll(self._cycle)
        if update is None:
            return
        trace = {"cycle": self._cycle,
                 "events": [ev._asdict() for ev in update.events],
                 "num_up": update.num_up, "queued": len(self.queue),
                 "migrated": 0, "recut_topologies": 0}
        if update.net is not None:
            for e in self.queue:
                if e.migrations == 0:
                    self.stats.requests_migrated += 1
                e.migrations += 1
            trace["migrated"] = len(self.queue)
            # swap (flushes partition cache) BEFORE installing warm cuts
            self.engine.swap_network(update.net)
            seen: set[str] = set()
            for e in self.queue:
                topo = e.topo_key()
                if topo in seen:
                    continue
                seen.add(topo)
                prev = self._last_subgraph.get(topo)
                if prev is None:
                    continue
                self.engine.controller.recut_warm(
                    e.req.state, prev, num_parts=max(1, update.num_up))
                trace["recut_topologies"] += 1
            self._awaiting_recovery.append(trace)
            self._sync_queue_stats()
        self.fault_trace.append(trace)

    def _mark_recovered(self) -> None:
        """Stamp recovery latency (in pump cycles, inclusive) on every
        pending migration once a cycle serves results again."""
        for rec in self._awaiting_recovery:
            rec["recovery_cycles"] = self._cycle - rec["cycle"] + 1
        self._awaiting_recovery.clear()

    # -- one scheduling cycle ------------------------------------------------
    def pump(self) -> list[StreamResult]:
        """Admission pass + batch former + one batched cycle dispatch.

        Walks the queue in FIFO order: expired requests are rejected
        (``deadline``) and each candidate passes its own admission check.
        With ``cross_topology=False`` the first admissible request becomes
        the batch head and only later requests sharing its topology
        fingerprint join (others simply stay queued — only an explicit
        controller decision defers or rejects); with
        ``cross_topology=True`` every admissible request joins up to
        ``max_batch``, whatever its topology. The whole batch is then
        decided in ONE vmapped controller call and dispatched per
        plan/bucket group (:meth:`_serve_cycle`). Returns the served
        results of this cycle (possibly []). A non-empty cycle is the
        ``ge.frontend.cycle`` profiler span (its stats: ``cycle``,
        ``batch``, ``topologies``, ``groups``)."""
        with TraceAnnotation("ge.frontend.admit"), self._lock:
            now = self.clock.now()
            self._poll_faults()       # pump boundary: nothing in flight
            backlog = len(self.queue)
            est_service = self.est_service(backlog)
            batch: list[_Entry] = []
            survivors: list[_Entry] = []
            head_topo: str | None = None
            for entry in self.queue:
                if entry.deadline_tick is not None \
                        and now > entry.deadline_tick:
                    self._reject(entry, REJECT_DEADLINE, now)
                    continue
                if len(batch) >= self.max_batch or (
                        not self.cross_topology
                        and head_topo is not None
                        and entry.topo_key() != head_topo):
                    survivors.append(entry)
                    continue
                verdict = self.admission.decide(entry, now, backlog,
                                                est_service)
                if verdict == ADMIT:
                    entry.timing.admit = now
                    batch.append(entry)
                    head_topo = entry.topo_key()
                elif verdict == DEFER:
                    entry.defers += 1
                    self.stats.defer_events += 1
                    survivors.append(entry)
                else:
                    self._reject(entry, REJECT_ADMISSION, now)
            self.queue.replace(survivors)
            self._sync_queue_stats()
        if not batch:
            self.admission.on_cycle(0, now)
            self._cycle += 1
            return []
        with TraceAnnotation("ge.frontend.cycle", cycle=self._cycle,
                             batch=len(batch)) as span:
            results = self._serve_cycle(batch, span)
            self.admission.on_cycle(len(batch), self.clock.now())
        if results:
            self._mark_recovered()
        self._cycle += 1
        return results

    def _serve_cycle(self, batch: list[_Entry], span: TraceAnnotation
                     ) -> list[StreamResult]:
        """Serve one admitted cycle: ONE vmapped control decision over the
        cycle's unique topologies (:meth:`ServingEngine.decide_entries`),
        then one asynchronous dispatch per plan group — same-plan groups
        through the plan's batched forward, mixed groups sharing a shape
        bucket through the multi-plan cross-topology forward — and only
        then the blocking output fetches, so every group's device work
        overlaps the others' host-side prep. Each host↔device crossing is
        its own profiler span: ``ge.transfer.scatter``,
        ``ge.forward.dispatch``, ``ge.transfer.fetch``,
        ``ge.transfer.gather``."""
        t_admit = batch[0].timing.admit
        # 1. one batched decide over the cycle's unique topologies
        by_topo: dict[str, list[_Entry]] = {}
        for e in batch:
            by_topo.setdefault(e.topo_key(), []).append(e)
        topos = list(by_topo)
        span.set_metadata(topologies=len(topos))
        decided = dict(zip(topos, self.engine.decide_entries(
            [by_topo[t][0].req.state for t in topos])))
        for t in topos:
            # remembered as the warm-start seed for fault-time re-cuts
            self._last_subgraph.put(
                t, np.asarray(decided[t][0].partition.subgraph))
        t_decided = self.clock.now()
        # 2. group members by plan (same-topo mode) or shape bucket
        groups: dict[tuple, list[_Entry]] = {}
        for e in batch:
            pe = decided[e.topo_key()][1]
            gk = self.engine.entry_bucket(pe) if self.cross_topology \
                else pe.key
            groups.setdefault(gk, []).append(e)
        span.set_metadata(groups=len(groups))
        # 3. dispatch every group before fetching any output
        inflight = []
        for members in groups.values():
            entries = [decided[e.topo_key()][1] for e in members]
            xs = [e.req.x for e in members]
            bsz = len(members)
            pad = _bucket(bsz, self.max_batch)
            if len({pe.key for pe in entries}) == 1:
                plan = entries[0].plan
                if bsz == 1:
                    fwd = entries[0].forward
                    with TraceAnnotation("ge.transfer.scatter", requests=1):
                        x_blocks = plan.scatter(np.asarray(xs[0], np.float32))
                    gather = (lambda h, p=plan: [p.gather(h)])
                else:
                    fwd = self.engine.batched_forward(entries[0])
                    with TraceAnnotation("ge.transfer.scatter",
                                         requests=bsz):
                        x_blocks = plan.scatter_batch(xs, pad_to=pad)
                    gather = (lambda h, p=plan, b=bsz:
                              p.gather_batch(h, count=b))
                cross = False
            else:
                # pad the member list to the batch bucket by repeating the
                # tail entry (pad slots carry zero features; outputs are
                # dropped by count=bsz), so compile counts stay bounded
                padded = entries + [entries[-1]] * (pad - bsz)
                plans, fwd = self.engine.cross_batched_forward(padded)
                with TraceAnnotation("ge.transfer.scatter", requests=bsz):
                    x_blocks = scatter_multi(plans, xs, pad_to=pad)
                gather = (lambda h, ps=plans, b=bsz:
                          gather_multi(ps, h, count=b))
                cross = True
            with TraceAnnotation("ge.forward.dispatch", requests=bsz,
                                 cross=int(cross)):
                out = fwd(x_blocks, self.engine.params)
            inflight.append((members, out, gather, cross))
        t_dispatch = self.clock.now()
        all_results: list[StreamResult] = []
        with self._lock:
            for members, out, gather, cross in inflight:
                bsz = len(members)
                with TraceAnnotation("ge.transfer.fetch", requests=bsz):
                    host = np.asarray(out)   # blocks on this group's fetch
                with TraceAnnotation("ge.transfer.gather"):
                    outputs = gather(host)
                self.stats.batches += 1
                if bsz >= 2:
                    self.stats.batched_requests += bsz
                if cross:
                    self.stats.cross_batches += 1
                    self.stats.cross_batched_requests += bsz
                t_done = self.clock.now()
                for e, output in zip(members, outputs):
                    decision, pe, hit = decided[e.topo_key()]
                    e.timing.dispatch = t_dispatch
                    e.timing.done = t_done
                    e.timing.cycle = self._cycle
                    if e.migrations:
                        self.stats.migrated_served += 1
                    self.timings.append(e.timing)
                    all_results.append(StreamResult(
                        e.rid, e.req, output, e.timing, bsz, hit,
                        decision))
            t_done = self.clock.now()
            bsz = len(batch)
            # service-time estimates feeding the admission controller:
            # the batched decide is a per-CYCLE cost (amortized at decide
            # time over the backlog — est_service()), the dispatch+fetch
            # a per-REQUEST one
            self._est_decide = self._ewma(self._est_decide,
                                          t_decided - t_admit)
            self._est_forward = self._ewma(self._est_forward,
                                           (t_done - t_decided) / bsz)
            self.stats.admitted += bsz
            self.stats.served += bsz
            self.cycles.record(self._cycle, bsz, t_decided - t_admit,
                               t_dispatch - t_decided, t_done - t_dispatch)
        return all_results

    # -- open-loop workload driver -------------------------------------------
    def run(self, workload: Iterable[tuple[float, StreamRequest]]
            ) -> list[StreamResult]:
        """Drive a sorted ``(arrival_offset, request)`` workload to drain.

        Open loop: requests are injected once their offset (relative to the
        start tick) has passed, regardless of how far serving has fallen
        behind — a rate above the service capacity fills the queue and
        surfaces as backpressure/admission rejections, never as slowed-down
        arrivals. Returns every served result (submission order within a
        batch; batches in service order)."""
        t0 = self.clock.now()
        it = iter(workload)
        nxt = next(it, None)
        results: list[StreamResult] = []
        while nxt is not None or len(self.queue):
            now = self.clock.now() - t0
            while nxt is not None and nxt[0] <= now:
                self.submit(nxt[1])
                nxt = next(it, None)
            if not len(self.queue):
                if nxt is not None:   # idle until the next arrival is due
                    self.clock.sleep(nxt[0] - (self.clock.now() - t0))
                continue
            results.extend(self.pump())
        return results

    def run_threaded(self, workload: Iterable[tuple[float, StreamRequest]],
                     idle_wait: float = 1e-4) -> list[StreamResult]:
        """Concurrent-intake twin of :meth:`run`: a producer thread injects
        the workload's arrivals on schedule through the thread-safe
        ``submit`` while this thread pumps continuously — arrival and
        dispatch overlap instead of strictly alternating, so a long
        in-flight batch no longer delays intake (and the next cycle's
        batch is already formed when the dispatch returns). Wall-clock
        (``MonotonicClock``) only: a shared logical clock would make the
        producer's schedule depend on pump timing."""
        t0 = self.clock.now()
        done = threading.Event()

        def produce():
            try:
                for offset, req in workload:
                    dt = offset - (self.clock.now() - t0)
                    if dt > 0:
                        self.clock.sleep(dt)
                    self.submit(req)
            finally:
                done.set()

        producer = threading.Thread(target=produce, daemon=True)
        producer.start()
        results: list[StreamResult] = []
        while not (done.is_set() and not len(self.queue)):
            if not len(self.queue):
                with TraceAnnotation("ge.frontend.idle"):
                    self.clock.sleep(idle_wait)
                continue
            results.extend(self.pump())
        producer.join()
        return results

    # -- telemetry -----------------------------------------------------------
    def slo_summary(self) -> dict:
        """p50/p95/p99/mean/max per phase + sustained requests/sec."""
        return summarize(self.timings)

    def stats_dict(self) -> dict:
        return {**self.stats.as_dict(), "slo": self.slo_summary(),
                "cycles": self.cycles.as_dict(),
                "est_service": self.est_service(len(self.queue)),
                "est_decide": self._est_decide,
                "est_forward": self._est_forward,
                "plan_cache": self.engine.plan_cache_info()._asdict()}


def poisson_workload(rng: np.random.Generator, rate: float, count: int,
                     make_request, lazy: bool = False
                     ) -> Iterable[tuple[float, StreamRequest]]:
    """Open-loop Poisson-process workload: ``count`` arrivals at ``rate``
    requests/tick (exponential inter-arrival gaps), each request built by
    ``make_request(i)``. The standard "millions of independent users"
    arrival model — bursts and lulls included. With ``lazy=True`` the
    requests are built one-by-one *at injection time* (a generator), so a
    ``make_request`` that snapshots a mutating state — e.g. the fault
    injector's churned user graph — sees the state as of each arrival
    instead of as of workload construction."""
    gaps = rng.exponential(1.0 / float(rate), size=count)
    offsets = np.cumsum(gaps)
    if lazy:
        return ((float(offsets[i]), make_request(i)) for i in range(count))
    return [(float(offsets[i]), make_request(i)) for i in range(count)]
