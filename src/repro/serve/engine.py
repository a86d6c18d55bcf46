"""Pipelined GNN serving engine (ROADMAP "Async serving loop").

The paper's per-time-step loop — perceive, HiCut, offload, serve (Fig. 2,
Eqs. 12–14) — ran strictly sequentially in ``repro.launch.serve_gnn``: one
controller decision, one blocking ``distributed_gcn_forward``, repeat. That
puts the whole decision latency on the serving critical path even though
the two stages use disjoint resources (host Python/XLA-control vs the
device computation). This engine rebuilds serving as a request pipeline:

1. **decide** — ``GraphEdgeController.step`` (jitted end to end for
   :class:`~repro.core.api.JitPolicy` policies such as ``greedy_jit``).
2. **plan** — topology-delta detection via the controller's
   ``topology_key`` + a bounded LRU **plan cache**: the key is
   ``(topology fingerprint, offload-assignment digest)`` and the value is
   the built :class:`~repro.gnn.distributed.PartitionPlan` *and* its
   prepared forward (``make_forward_fn`` — normalization scales, extended
   adjacency, jitted shard_map closure). Requests on an unchanged topology
   with an unchanged assignment skip plan construction and forward prep
   entirely.
3. **dispatch** — the forward is dispatched asynchronously (JAX async
   dispatch); the engine immediately starts step t+1's decision while step
   t's inference is in flight, and blocks only when fetching t's output.

Depth-1 pipelining is deliberate: one in-flight forward keeps the device
busy while the host decides, without reordering results or holding >2
request buffers. ``serve`` is a generator that preserves request order.
"""
from __future__ import annotations

import hashlib
from dataclasses import dataclass, field
from typing import Callable, Iterable, Iterator, Sequence

import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh

from repro.core.api import (CacheInfo, Decision, GraphEdgeController,
                            LruCache, topology_key)
from repro.core.dynamic_graph import GraphState
from repro.gnn.distributed import (PLAN_BUCKET_QUANTUM, PartitionPlan,
                                   PlanConsts, _ceil_to,
                                   make_batched_forward_fn, make_forward_fn,
                                   make_multi_forward_fn, pad_plan_to_bucket,
                                   plan_bucket, prepare_plan_consts,
                                   resolve_aggregate)

# adaptive bucket quantums: per-family quantums double up to this cap
PLAN_BUCKET_QUANTUM_CAP = 64
_FAMILY_HIST_MAX = 64                # distinct halo widths kept per family


@dataclass(frozen=True)
class ServeRequest:
    """One inference request: the perceived layout + per-vertex features."""
    state: GraphState
    x: np.ndarray                 # [N, F_in] vertex features


@dataclass(frozen=True)
class ServeResult:
    """One served request, in submission order."""
    step: int
    request: ServeRequest
    decision: Decision
    plan: PartitionPlan
    output: np.ndarray            # [N, F_out] gathered global output
    plan_cache_hit: bool


def _assignment_digest(servers: np.ndarray) -> str:
    h = hashlib.blake2b(digest_size=16)
    h.update(np.ascontiguousarray(servers, np.int64).tobytes())
    return h.hexdigest()


def network_digest(net) -> str:
    """Fingerprint of an :class:`~repro.core.costs.EdgeNetwork`'s pricing
    surface (capacities, rates, energy constants). Part of the plan-cache
    key: two identical (topology, assignment) pairs priced under different
    networks must NOT share a plan entry — a capacity swap (fault event,
    degradation) would otherwise keep serving plans whose placement the
    live network can no longer host. Cheap: only recomputed on
    :meth:`ServingEngine.swap_network`, never per request."""
    h = hashlib.blake2b(digest_size=16)
    for leaf in net:
        h.update(np.ascontiguousarray(
            np.asarray(leaf, np.float64)).tobytes())
    return h.hexdigest()


@dataclass
class PlanEntry:
    """One plan-cache value: the plan, its prepared single-request forward,
    and — built lazily, only once a continuous batch actually forms on this
    plan — the prepared batched forward (``make_batched_forward_fn``) plus,
    for cross-topology batches, the plan padded to its shape bucket with
    its stackable forward constants (``padded``: bucket → (plan, consts)).
    All lazily-built members stay with the entry, so they age out of the
    LRU together with the plan. ``bucket`` memoizes the shape bucket along
    with the family quantum it was computed at (``bucket_quantum``), so the
    engine can re-bucket the entry when its family's quantum adapts."""
    key: tuple[str, str, str]     # (topology, assignment, network) digests
    plan: PartitionPlan
    forward: Callable
    batched: Callable | None = None
    bucket: tuple | None = None
    bucket_quantum: int | None = None
    padded: dict = field(default_factory=dict)


@dataclass
class BucketFamily:
    """Running halo histogram + adaptive quantum for one ``(P, n, block')``
    plan-shape family (:meth:`ServingEngine.entry_bucket`)."""
    hist: dict = field(default_factory=dict)   # halo width → count
    quantum: int = PLAN_BUCKET_QUANTUM

    def observe(self, halo: int) -> int:
        """Record a halo width; returns the (possibly widened) quantum.

        The quantum doubles (cap :data:`PLAN_BUCKET_QUANTUM_CAP`) until
        the family's observed min/max halo land in ONE bucket. Doubling
        only ever *merges* buckets — two widths sharing a ceiling at
        quantum q share it at 2q — so adaptation never splits a batch
        group that already formed, and re-bucketed entries join, never
        leave, their hot family bucket."""
        if halo not in self.hist and len(self.hist) >= _FAMILY_HIST_MAX:
            self.hist.pop(min(self.hist, key=self.hist.get))
        self.hist[halo] = self.hist.get(halo, 0) + 1
        lo, hi = min(self.hist), max(self.hist)
        while _ceil_to(lo, self.quantum) != _ceil_to(hi, self.quantum) \
                and self.quantum < PLAN_BUCKET_QUANTUM_CAP:
            self.quantum *= 2
        return self.quantum


@dataclass
class ServingEngine:
    """Controller + mesh + params → pipelined request server.

    ``num_devices`` defaults to the mesh size; plans fold server ids onto
    that many devices (``Decision.to_partition_plan``). ``plan_cache_size``
    bounds the LRU of (plan, prepared forward) entries.
    """
    controller: GraphEdgeController
    params: list                  # GCN layer params (repro.gnn.layers)
    mesh: Mesh
    axis: str = "servers"
    num_devices: int | None = None
    plan_cache_size: int = 16
    aggregate: str = "auto"
    exchange: str = "gather"      # halo layout: "gather" | "pair"
                                  # (pair = cut-edges-only all_to_all, the
                                  # multi-host wire — repro.gnn.multihost)

    def __post_init__(self):
        if self.num_devices is None:
            self.num_devices = int(np.prod(list(self.mesh.shape.values())))
        self._plan_cache = LruCache(self.plan_cache_size)
        self._multi_cache = LruCache(self.plan_cache_size)
        self._bucket_families: dict[tuple, BucketFamily] = {}
        self._net_key = network_digest(self.controller.net)
        self.net_swaps = 0

    # -- control + plan stage ------------------------------------------------
    def _plan_for(self, decision: Decision) -> tuple[PlanEntry, bool]:
        """Plan + prepared forward for a decision, through the LRU cache.

        Keyed on (topology fingerprint, assignment digest, network
        digest): the plan is a pure function of the edge list and the
        user→server placement, so repeated requests on an unchanged
        topology whose policy reproduces the same assignment reuse both
        the plan and its jitted forward. The network digest rotates on
        :meth:`swap_network`, so entries priced under a stale network
        (pre-fault capacities) can never be served again — see the
        regression test in ``tests/test_faults.py``. Profiler spans:
        ``ge.plan.lookup`` (stat ``hit``), then on a miss
        ``ge.plan.build``."""
        with TraceAnnotation("ge.plan.lookup") as span:
            topo = decision.topo_key or topology_key(decision.state)
            key = (topo, _assignment_digest(decision.servers), self._net_key)
            hit = self._plan_cache.get(key)
            span.set_metadata(hit=int(hit is not None))
        if hit is not None:
            return hit, True
        with TraceAnnotation("ge.plan.build"):
            plan = decision.to_partition_plan(self.num_devices,
                                              exchange=self.exchange)
            forward = make_forward_fn(self.mesh, self.axis, plan,
                                      self.aggregate)
            entry = PlanEntry(key, plan, forward)
            self._plan_cache.put(key, entry)
        return entry, False

    def decide_entry(self, state: GraphState
                     ) -> tuple[Decision, PlanEntry, bool]:
        """The full control stage for one request (no inference): one
        controller step + the (topology, assignment)-keyed plan LRU."""
        decision = self.controller.step(state)
        entry, hit = self._plan_for(decision)
        return decision, entry, hit

    def decide_entries(self, states: Sequence[GraphState]
                       ) -> list[tuple[Decision, PlanEntry, bool]]:
        """The control stage for a whole scheduling cycle: ALL states are
        decided in one vmapped XLA call (``GraphEdgeController.step_batch``
        — scene build + policy + exact cost stacked over the batch), then
        each decision goes through the plan LRU. This is the batched-decide
        hot path of the streaming front-end's pump loop; per-request decide
        pays one dispatch per request, this pays one per cycle. The
        ``ge.control.decide`` profiler span (stat ``batch``)."""
        with TraceAnnotation("ge.control.decide", batch=len(states)):
            decisions = self.controller.step_batch(states)
            return [(d,) + self._plan_for(d) for d in decisions]

    def decide(self, state: GraphState
               ) -> tuple[Decision, PartitionPlan, Callable, bool]:
        """Back-compat surface of :meth:`decide_entry`."""
        decision, entry, hit = self.decide_entry(state)
        return decision, entry.plan, entry.forward, hit

    def batched_forward(self, entry: PlanEntry) -> Callable:
        """The prepared *batched* forward of a cached plan, built lazily on
        the first continuous batch that forms on it (the per-plan numpy
        prep runs once; jit then compiles once per batch-size bucket). The
        streaming front-end's dispatch hook (``repro.serve.frontend``)."""
        if entry.batched is None:
            entry.batched = make_batched_forward_fn(self.mesh, self.axis,
                                                    entry.plan,
                                                    self.aggregate)
        return entry.batched

    # -- cross-topology batching ---------------------------------------------
    def entry_bucket(self, entry: PlanEntry) -> tuple:
        """The entry's shape bucket (:func:`plan_bucket`) — the batch key
        for cross-topology continuous batching.

        The quantum is **adaptive per plan-shape family** ``(P, n,
        block')``: each family keeps a small running histogram of the halo
        widths it has served (:class:`BucketFamily`) and doubles its
        quantum until the observed spread fits one bucket — a hot family
        whose halos straddle a fixed ``PLAN_BUCKET_QUANTUM`` boundary
        (e.g. 7 vs 9) no longer splits into two buckets and halves its
        batch size. Memoized on the entry together with the quantum it
        was computed at, so entries re-bucket when their family adapts."""
        plan = entry.plan
        fam_key = (plan.num_devices, plan.n,
                   _ceil_to(plan.block, PLAN_BUCKET_QUANTUM))
        fam = self._bucket_families.setdefault(fam_key, BucketFamily())
        if entry.bucket is None:
            fam.observe(plan.halo)        # first sighting joins the family
        if entry.bucket_quantum != fam.quantum:
            entry.bucket = plan_bucket(plan, fam.quantum)
            entry.bucket_quantum = fam.quantum
        return entry.bucket

    def _padded_member(self, entry: PlanEntry, bucket: tuple
                       ) -> tuple[PartitionPlan, PlanConsts]:
        """The entry's plan padded to ``bucket`` plus its stackable forward
        constants, built once per (entry, bucket). Padding appends inert
        slots only, so the padded forward is bitwise-identical to the
        original plan's (``pad_plan``); the aggregate is resolved on the
        *padded* shapes so every bucket member picks the same kernel."""
        got = entry.padded.get(bucket)
        if got is None:
            plan = pad_plan_to_bucket(entry.plan, bucket)
            agg = resolve_aggregate(plan, self.aggregate)
            got = (plan, prepare_plan_consts(plan, agg), agg)
            entry.padded[bucket] = got
        return got[0], got[1]

    def cross_batched_forward(self, entries: Sequence[PlanEntry]
                              ) -> tuple[list[PartitionPlan], Callable]:
        """One dispatchable forward serving requests resolved against
        *different* cached plans.

        The entries must share a shape bucket (``entry_bucket``). Returns
        the per-member padded plans — whose ``scatter``/``gather`` lay out
        each member's features by its own perm (``scatter_multi``) — and
        the stacked multi-plan forward over [P, B, L, F] blocks. The
        stacked closure is LRU-cached on the ordered member keys: steady
        streams cycling over a hot set of topologies rebuild nothing, and
        the jit cache underneath keys on the bucket shapes, so even a cold
        member set of a warm bucket skips compilation. A miss (padding,
        constants, their stacking) is the ``ge.plan.stack`` profiler span
        (stat ``members``)."""
        bucket = self.entry_bucket(entries[0])
        assert all(self.entry_bucket(e) == bucket for e in entries), \
            [self.entry_bucket(e) for e in entries]
        key = (bucket, tuple(e.key for e in entries))
        hit = self._multi_cache.get(key)
        if hit is not None:
            return hit
        with TraceAnnotation("ge.plan.stack", members=len(entries)):
            members = [self._padded_member(e, bucket) for e in entries]
            plans = [m[0] for m in members]
            agg = entries[0].padded[bucket][2]
            forward = make_multi_forward_fn(self.mesh, self.axis, agg,
                                            [m[1] for m in members])
            self._multi_cache.put(key, (plans, forward))
        return plans, forward

    # -- network swap (fault migration) --------------------------------------
    def swap_network(self, net) -> None:
        """Install a repriced :class:`~repro.core.costs.EdgeNetwork` (fault
        event: server down/up, degradation). Rotates the plan-cache network
        digest so every entry built against the old pricing misses from now
        on (cross-topology stacked forwards key on entry keys, so they
        rotate with it), and flushes the controller's partition cache —
        cached cuts may target a server count the new network no longer
        has. Callers that want warm-started re-cuts install them afterwards
        via ``controller.recut_warm`` (see ``repro.serve.frontend``)."""
        self.controller.net = net
        self.controller.invalidate_partitions()
        self._net_key = network_digest(net)
        self.net_swaps += 1

    # -- serving -------------------------------------------------------------
    def serve(self, requests: Iterable[ServeRequest], faults=None
              ) -> Iterator[ServeResult]:
        """Serve a request stream, pipelined at depth 1.

        For each request the engine runs the control stage and dispatches
        the forward, then yields the *previous* request's result — so step
        t's decision overlaps step t−1's in-flight device computation. The
        final result is flushed after the stream ends; order is preserved.

        A failing request never loses the one already in flight: if the
        decide/dispatch of request t raises (bad state, failing policy,
        poisoned iterator), request t−1's pending result is flushed to the
        consumer first and the exception re-raised on the next pull.

        ``faults`` (a :class:`repro.serve.faults.FaultInjector`) is polled
        once per request with the request index as the logical clock. When
        an update reprices the network, the engine **drains then swaps**:
        the in-flight forward (built against the old plan) is finished and
        yielded first, then :meth:`swap_network` installs the new pricing —
        so no request is ever served against a plan/network mix and none is
        lost (DESIGN.md §9)."""
        pending = None
        it = enumerate(requests)
        while True:
            try:
                try:
                    t, req = next(it)
                except StopIteration:
                    break
                update = faults.poll(t) if faults is not None else None
                if update is not None and update.net is not None:
                    if pending is not None:   # drain before repricing
                        res, pending = self._finish(*pending), None
                        yield res
                    self.swap_network(update.net)
                decision, plan, forward, hit = self.decide(req.state)
                x_blocks = plan.scatter(np.asarray(req.x, np.float32))
                out = forward(x_blocks, self.params)    # async dispatch
            except BaseException:
                if pending is not None:     # flush t−1 before propagating
                    res, pending = self._finish(*pending), None
                    yield res
                raise
            if pending is not None:
                yield self._finish(*pending)
            pending = (t, req, decision, plan, out, hit)
        if pending is not None:
            yield self._finish(*pending)

    def serve_all(self, requests: Iterable[ServeRequest], faults=None
                  ) -> list[ServeResult]:
        return list(self.serve(requests, faults=faults))

    def _finish(self, t, req, decision, plan, out, hit) -> ServeResult:
        output = plan.gather(np.asarray(out))       # blocks on fetch only
        return ServeResult(t, req, decision, plan, output, hit)

    # -- introspection -------------------------------------------------------
    def plan_cache_info(self) -> CacheInfo:
        return self._plan_cache.info()
