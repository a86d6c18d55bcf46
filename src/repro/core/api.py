"""GraphEdge control-plane API: pluggable perceive → partition → offload → serve.

The paper's architecture (Figs. 1–2) is a single control loop — perceive the
user topology, cut it with HiCut (§4), offload with DRLGO (§5), serve the
distributed GNN inference and account the exact system cost (Eqs. 12–14).
This module exposes that loop behind three swappable pieces:

* :class:`Partitioner` — ``partition(state) -> Partition``; implementations
  are registered by name (``hicut_jax`` [default, jit-able], ``hicut_ref``,
  ``mincut``, ``multilevel``, ``multilevel_jax``, ``none``) and selected
  with :func:`get_partitioner`. Partitioners whose cut is a pure jnp
  function additionally satisfy :class:`JitPartitioner`
  (``cut(state) -> [N] i32``) and power the end-to-end jitted step.
* :class:`OffloadPolicy` — ``policy(env) -> Assignment``; registered names
  are ``drlgo``, ``ppo``, ``greedy``, ``random``, ``local``, plus the
  pure-jnp ``greedy_jit`` / ``local_jit`` / ``lyapunov``
  (:func:`get_offload_policy`).
* :class:`JitPolicy` — the protocol extension for policies whose decision
  rule is a pure jnp function over an
  :class:`~repro.core.offload.batched_env.EnvScene`
  (``decide(scene) -> (assign, reward)``). For these the controller skips
  the per-user numpy env entirely: ``step()`` runs one jitted
  ``scene → offload → exact cost`` call, and :meth:`GraphEdgeController.
  jit_step_fn` closes the loop end to end (HiCut partition included) as a
  pure function usable inside ``jax.jit`` / ``lax.scan``.
* :class:`GraphEdgeController` — composes the two. ``step(state)`` runs one
  control step and returns a :class:`Decision` carrying the assignment, the
  partition and the full :class:`~repro.core.costs.SystemCost`; ``rollout``
  drives multiple steps through the dynamic-graph event model (§3.2).
  Partitions are cached across steps whose topology (mask + adjacency) is
  unchanged — a bounded LRU keyed by :func:`topology_key`, so pure mobility
  steps never re-run the cut and long dynamic rollouts cannot grow the
  cache without limit (``cache_info()`` reports hits/misses/size).

For training-scale workloads, :meth:`GraphEdgeController.make_batched_env`
stacks B scenarios into one vmapped
:class:`~repro.core.offload.batched_env.BatchedOffloadEnv` with the
controller's partitioner and reward constants (DESIGN.md §3).

A :class:`Decision` bridges directly into serving:
``decision.to_partition_plan(P)`` feeds
:func:`repro.gnn.distributed.make_partition_plan` →
:func:`~repro.gnn.distributed.distributed_gcn_forward`
(see ``repro.launch.serve_gnn`` and DESIGN.md for the full data path).

Registries are plain dicts of factories; third-party strategies plug in with
:func:`register_partitioner` / :func:`register_offload_policy`.
"""
from __future__ import annotations

import hashlib
from collections import OrderedDict
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, NamedTuple, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import costs
from repro.core.dynamic_graph import GraphState, perturb_scenario
from repro.core.hicut import cut_metrics, hicut_jax, hicut_ref
from repro.core.offload.batched_env import (BatchedOffloadEnv, EnvScene,
                                            _scene_core, stack_states)
from repro.core.offload.env import OffloadEnv


def state_edges(state: GraphState) -> np.ndarray:
    """Upper-triangular edge list [(i, j)] of the (masked) layout G(t).

    One pass over the dense layout (GraphState stores adj dense), but no
    N×N temporary — the old ``np.triu`` copy doubled peak memory."""
    i, j = np.nonzero(np.asarray(state.adj))
    keep = i < j
    return np.stack([i[keep], j[keep]], axis=1)


def topology_key(state: GraphState) -> str:
    """Topology fingerprint: hash of (capacity, mask, sorted edge list).

    Keyed off the edge list rather than the dense adjacency bytes: the
    hashed payload scales with E, not N² (the scan over GraphState's dense
    adj is unavoidable, but allocates only O(E)), and sparse- and dense-
    derived layouts of the same graph share cache entries. ``state_edges``
    emits edges in sorted (row-major upper-triangular) order, making the
    key canonical."""
    edges = np.ascontiguousarray(state_edges(state), np.int64)
    h = hashlib.blake2b(digest_size=16)
    h.update(np.int64(state.capacity).tobytes())
    h.update(np.asarray(state.mask, np.float32).tobytes())
    h.update(edges.tobytes())
    return h.hexdigest()


# ---------------------------------------------------------------------------
# partitioning (subproblem P1)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Partition:
    """Result of graph-layout optimization: vertex → subgraph ids."""
    subgraph: np.ndarray          # [N] int64 subgraph id (−1 = inactive)
    method: str                   # registry name that produced it
    cut_metrics: dict = field(default_factory=dict)

    @property
    def num_subgraphs(self) -> int:
        ids = self.subgraph[self.subgraph >= 0]
        return int(len(np.unique(ids)))

    def to_device_assignment(self, num_devices: int) -> np.ndarray:
        """Subgraph ids → device/server ids (id mod P; −1 preserved)."""
        out = np.asarray(self.subgraph, np.int64).copy()
        out[out >= 0] %= num_devices
        return out


@runtime_checkable
class Partitioner(Protocol):
    """Graph-layout optimizer: ``G(t) → G_sub`` (paper §4, P1)."""
    name: str

    def __call__(self, state: GraphState) -> Partition: ...


@runtime_checkable
class JitPartitioner(Protocol):
    """Partitioner whose cut is a *pure jnp* function of the layout.

    ``cut(state) -> [N] int32`` must be traceable (no numpy, no host
    round-trips) so :meth:`GraphEdgeController.jit_step_fn` can close it
    into the end-to-end jitted ``partition → offload → cost`` step.
    Implementations keep the plain ``__call__(state) -> Partition``
    surface for every eager caller. The mirror of :class:`JitPolicy` on
    the partition side: ``hicut_jax``, ``none`` and ``multilevel_jax``
    satisfy it (DESIGN.md §6 walks through adding another).
    """
    name: str

    def cut(self, state: GraphState) -> jnp.ndarray: ...


_PARTITIONERS: dict[str, Callable[..., Partitioner]] = {}


def register_partitioner(name: str):
    """Register a partitioner factory under ``name`` (decorator)."""
    def deco(factory: Callable[..., Partitioner]):
        _PARTITIONERS[name] = factory
        return factory
    return deco


def available_partitioners() -> tuple[str, ...]:
    return tuple(sorted(_PARTITIONERS))


def get_partitioner(name: str, **kwargs: Any) -> Partitioner:
    """Instantiate a registered partitioner by name."""
    try:
        factory = _PARTITIONERS[name]
    except KeyError:
        raise ValueError(f"unknown partitioner {name!r}; available: "
                         f"{available_partitioners()}") from None
    return factory(**kwargs)


def _finish(state: GraphState, assigned: np.ndarray, method: str) -> Partition:
    assigned = np.asarray(assigned, np.int64)
    metrics = cut_metrics(state.capacity, state_edges(state), assigned)
    return Partition(assigned, method, metrics)


@register_partitioner("hicut_jax")
class _HiCutJax:
    """Fixed-shape jit-able HiCut (Algorithm 1) on the masked dense layout."""
    name = "hicut_jax"

    def __call__(self, state: GraphState) -> Partition:
        assigned = np.asarray(self.cut(state))
        return _finish(state, assigned, self.name)

    def cut(self, state: GraphState) -> jnp.ndarray:
        return hicut_jax(state.adj, state.mask)


@register_partitioner("hicut_ref")
class _HiCutRef:
    """Numpy adjacency-list transcription of Algorithm 1 (the oracle)."""
    name = "hicut_ref"

    def __call__(self, state: GraphState) -> Partition:
        active = np.asarray(state.mask) > 0
        assigned = hicut_ref(state.capacity, state_edges(state), active=active)
        return _finish(state, assigned, self.name)


@register_partitioner("mincut")
class _MinCut:
    """Iterated pairwise max-flow min-cut baseline (Zeng et al. [36])."""
    name = "mincut"

    def __init__(self, num_parts: int = 4, seed: int = 0,
                 weight_range: tuple[int, int] = (1, 100)):
        self.num_parts = num_parts
        self.seed = seed
        self.weight_range = weight_range

    def __call__(self, state: GraphState) -> Partition:
        from repro.core.mincut_baseline import mincut_partition_state
        assigned = mincut_partition_state(state, self.num_parts,
                                          seed=self.seed,
                                          weight_range=self.weight_range)
        return _finish(state, assigned, self.name)


@register_partitioner("none")
class _NoPartition:
    """Every active vertex its own subgraph — the DRL-only ablation (Fig 12)."""
    name = "none"

    def __call__(self, state: GraphState) -> Partition:
        assigned = np.arange(state.capacity, dtype=np.int64)
        assigned[np.asarray(state.mask) <= 0] = -1
        return _finish(state, assigned, self.name)

    def cut(self, state: GraphState) -> jnp.ndarray:
        return jnp.where(state.mask > 0,
                         jnp.arange(state.mask.shape[0], dtype=jnp.int32),
                         -1)


@register_partitioner("multilevel")
class _Multilevel:
    """METIS-style multilevel k-way cut: heavy-edge-matching coarsening,
    greedy balanced initial partition, boundary KL refinement
    (repro.core.multilevel; the Zeng et al. arXiv:2210.17281 family)."""
    name = "multilevel"

    def __init__(self, num_parts: int = 4, coarsen_to: int | None = None,
                 sweeps: int = 4, imbalance: float = 1.1):
        self.num_parts = num_parts
        self.coarsen_to = coarsen_to
        self.sweeps = sweeps
        self.imbalance = imbalance

    def __call__(self, state: GraphState) -> Partition:
        from repro.core.multilevel import multilevel_partition_state
        assigned = multilevel_partition_state(
            state, self.num_parts, coarsen_to=self.coarsen_to,
            sweeps=self.sweeps, imbalance=self.imbalance)
        return _finish(state, assigned, self.name)


@register_partitioner("multilevel_jax")
class _MultilevelJax:
    """Fixed-shape jnp refinement stage of the multilevel pipeline — a
    :class:`JitPartitioner`, so it also runs inside ``jit_step_fn()``."""
    name = "multilevel_jax"

    def __init__(self, num_parts: int = 4, moves: int | None = None,
                 imbalance: float = 1.1):
        self.num_parts = num_parts
        self.moves = moves                 # None → 2·capacity at call time
        self.imbalance = imbalance

    def _moves(self, state: GraphState) -> int:
        return 2 * state.capacity if self.moves is None else int(self.moves)

    def cut(self, state: GraphState) -> jnp.ndarray:
        from repro.core.multilevel import multilevel_jax
        return multilevel_jax(state.adj, state.mask, self.num_parts,
                              self._moves(state), self.imbalance)

    def __call__(self, state: GraphState) -> Partition:
        return _finish(state, np.asarray(self.cut(state)), self.name)


# ---------------------------------------------------------------------------
# offloading (subproblem P2)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Assignment:
    """Graph offloading decision w: user → edge server (C1 holds)."""
    servers: np.ndarray           # [N] int64 server id (−1 = inactive)
    reward: float = 0.0           # Σ per-step rewards (Eq. 23)
    stats: dict = field(default_factory=dict)

    def onehot(self, m: int) -> jnp.ndarray:
        return costs.assignment_onehot(jnp.asarray(self.servers), m)


@runtime_checkable
class OffloadPolicy(Protocol):
    """Task scheduler: rolls an :class:`OffloadEnv` episode → Assignment."""
    name: str

    def __call__(self, env: OffloadEnv) -> Assignment: ...


_POLICIES: dict[str, Callable[..., OffloadPolicy]] = {}


def register_offload_policy(name: str):
    def deco(factory: Callable[..., OffloadPolicy]):
        _POLICIES[name] = factory
        return factory
    return deco


def available_offload_policies() -> tuple[str, ...]:
    return tuple(sorted(_POLICIES))


def get_offload_policy(name: str, **kwargs: Any) -> OffloadPolicy:
    """Instantiate a registered offloading policy by name."""
    try:
        factory = _POLICIES[name]
    except KeyError:
        raise ValueError(f"unknown offload policy {name!r}; available: "
                         f"{available_offload_policies()}") from None
    return factory(**kwargs)


def _episode_assignment(env: OffloadEnv, stats: dict, name: str) -> Assignment:
    return Assignment(env.assign.copy(), float(stats.get("reward", 0.0)),
                      dict(stats))


@register_offload_policy("greedy")
class _Greedy:
    """GM: each user to the nearest non-full edge server (§6.1)."""
    name = "greedy"

    def __call__(self, env: OffloadEnv) -> Assignment:
        from repro.core.offload.baselines import run_greedy
        return _episode_assignment(env, run_greedy(env), self.name)


@register_offload_policy("random")
class _Random:
    """RM: each user to a uniformly random server (§6.1)."""
    name = "random"

    def __init__(self, seed: int = 0):
        self.seed = seed

    def __call__(self, env: OffloadEnv) -> Assignment:
        from repro.core.offload.baselines import run_random
        return _episode_assignment(env, run_random(env, seed=self.seed),
                                   self.name)


@register_offload_policy("local")
class _Local:
    """LM: each user to its geographically nearest server, ignoring load."""
    name = "local"

    def __call__(self, env: OffloadEnv) -> Assignment:
        from repro.core.offload.baselines import run_local
        return _episode_assignment(env, run_local(env), self.name)


@register_offload_policy("drlgo")
class _DRLGO:
    """The paper's MADDPG policy; wraps a (trained) DRLGOTrainer's actors."""
    name = "drlgo"

    def __init__(self, trainer):
        self.trainer = trainer

    def __call__(self, env: OffloadEnv) -> Assignment:
        stats = self.trainer.run_episode(env, explore=False, learn=False)
        return _episode_assignment(env, stats, self.name)


@runtime_checkable
class JitPolicy(Protocol):
    """Offload policy whose decision rule is a *pure jnp* episode rollout.

    ``decide`` must be traceable (an :class:`EnvScene` in, the final
    ``(assign [N] i32, Σreward)`` out) and hashable-stable (a module-level
    function, not a per-instance closure) so the controller can close it
    into one jitted ``scene → offload → cost`` step. Implementations also
    keep the plain ``OffloadPolicy`` ``__call__(env)`` surface so every
    existing env-driven caller (benchmarks, trainers) works unchanged.
    """
    name: str

    def decide(self, scene: EnvScene) -> tuple[jnp.ndarray, jnp.ndarray]: ...


@partial(jax.jit, static_argnames=("decide", "gnn", "m"))
def _jit_offload_and_cost(net: costs.EdgeNetwork, state: GraphState,
                          subgraph: jnp.ndarray, zeta_sp, sub_w, cost_scale,
                          gnn: costs.GNNCostParams, decide, m: int):
    """The controller's jitted decision hot path: build the scene from the
    (already-partitioned) layout, roll the policy's scan, and account the
    exact Eqs. (12)–(14) cost — one XLA computation, zero numpy."""
    scene = _scene_core(net, state, subgraph, zeta_sp, sub_w, cost_scale,
                        gnn)
    assign, reward = decide(scene)
    w = costs.assignment_onehot(assign, m)
    return assign, reward, costs.system_cost(net, state, w, gnn)


@partial(jax.jit, static_argnames=("decide", "gnn", "m"))
def _jit_offload_and_cost_batch(net: costs.EdgeNetwork, states: GraphState,
                                subgraphs: jnp.ndarray, zeta_sp, sub_w,
                                cost_scale, gnn: costs.GNNCostParams, decide,
                                m: int):
    """Batched twin of :func:`_jit_offload_and_cost`: ``states`` is a
    stacked [B, ...] GraphState pytree (``batched_env.stack_states``) and
    ``subgraphs`` [B, N] i32. One vmapped XLA call builds all B
    :class:`EnvScene` pytrees, rolls the policy's decision scan per scene
    and accounts the exact Eqs. (12)–(14) cost — the whole scheduling
    cycle's control work in a single dispatch, no per-request host
    round-trips."""
    def one(state, subgraph):
        scene = _scene_core(net, state, subgraph, zeta_sp, sub_w,
                            cost_scale, gnn)
        assign, reward = decide(scene)
        w = costs.assignment_onehot(assign, m)
        return assign, reward, costs.system_cost(net, state, w, gnn)

    return jax.vmap(one)(states, subgraphs)


def _jit_decide(decide, net: costs.EdgeNetwork, state: GraphState, subgraph,
                zeta_sp, sub_w, cost_scale, gnn: costs.GNNCostParams,
                m: int) -> tuple[Assignment, costs.SystemCost]:
    """Run the jitted hot path and package the standard episode stats —
    the one place the (assignment, stats, cost) post-processing lives for
    both the ``__call__(env)`` surface and ``GraphEdgeController.step``.
    Profiler spans: ``ge.control.dispatch`` (the call and its argument
    copies), ``ge.control.wait`` (the first host read, which waits for the
    device), ``ge.control.unpack`` (the remaining reads)."""
    with TraceAnnotation("ge.control.dispatch", batch=1):
        assign, reward, sc = _jit_offload_and_cost(
            net, state, jnp.asarray(subgraph, jnp.int32), zeta_sp, sub_w,
            cost_scale, gnn, decide, m)
    with TraceAnnotation("ge.control.wait"):
        first = float(reward)
    with TraceAnnotation("ge.control.unpack"):
        stats = {"reward": first, "system_cost": float(sc.c),
                 "t_all": float(sc.t_all), "i_all": float(sc.i_all),
                 "cross_bits": float(sc.cross_bits.sum())}
        return (Assignment(np.asarray(assign, np.int64), float(reward),
                           stats), sc)


def _jit_policy_call(policy: JitPolicy, env: OffloadEnv) -> Assignment:
    """OffloadPolicy surface for jit policies: one jitted episode over the
    env's scenario (the env object is only read, never stepped)."""
    assignment, _ = _jit_decide(
        type(policy).decide, env.net, env.state, env.subgraph, env.zeta_sp,
        1.0 if env.use_subgraph_reward else 0.0, env.cost_scale, env.gnn,
        env.m)
    return assignment


@register_offload_policy("greedy_jit")
class _GreedyJit:
    """GM decision rule as a pure-jnp scan (zero numpy round-trips)."""
    name = "greedy_jit"

    @staticmethod
    def decide(scene: EnvScene):
        from repro.core.offload.baselines import greedy_rollout_jit
        return greedy_rollout_jit(scene)

    def __call__(self, env: OffloadEnv) -> Assignment:
        return _jit_policy_call(self, env)


@register_offload_policy("local_jit")
class _LocalJit:
    """LM decision rule as a pure-jnp scan (zero numpy round-trips)."""
    name = "local_jit"

    @staticmethod
    def decide(scene: EnvScene):
        from repro.core.offload.baselines import local_rollout_jit
        return local_rollout_jit(scene)

    def __call__(self, env: OffloadEnv) -> Assignment:
        return _jit_policy_call(self, env)


@register_offload_policy("lyapunov")
class _Lyapunov:
    """Queue-aware drift-plus-penalty scheduler (ACE-GNN-style system-aware
    scheduling): per-server virtual queues + marginal-cost penalty, rolled
    as one pure-jnp scan (repro.core.offload.lyapunov)."""
    name = "lyapunov"

    @staticmethod
    def decide(scene: EnvScene):
        from repro.core.offload.lyapunov import lyapunov_rollout_jit
        return lyapunov_rollout_jit(scene)

    def __call__(self, env: OffloadEnv) -> Assignment:
        return _jit_policy_call(self, env)


@register_offload_policy("ppo")
class _PPO:
    """PTOM baseline: single-agent PPO over the global state (§6.1)."""
    name = "ppo"

    def __init__(self, agent=None, seed: int = 0):
        self.agent = agent
        self.seed = seed

    def __call__(self, env: OffloadEnv) -> Assignment:
        if self.agent is None:        # lazily size the nets from the env
            from repro.core.offload.env import OBS_DIM
            from repro.core.offload.ppo import PPOConfig, PTOMAgent
            self.agent = PTOMAgent(PPOConfig(state_dim=env.m * OBS_DIM,
                                             n_actions=env.m), seed=self.seed)
        stats = self.agent.run_episode(env, learn=False, explore=False)
        return _episode_assignment(env, stats, self.name)


# ---------------------------------------------------------------------------
# the controller
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Decision:
    """One control step's output: who runs where, and what it costs."""
    state: GraphState
    partition: Partition
    assignment: Assignment
    cost: costs.SystemCost
    topo_key: str | None = None   # topology fingerprint (when cached)

    @property
    def servers(self) -> np.ndarray:
        return self.assignment.servers

    def to_partition_plan(self, num_devices: int | None = None,
                          exchange: str = "gather"):
        """Bridge into serving: decision → halo-exchange PartitionPlan.

        The offload assignment (user → server) becomes the vertex → device
        placement (server ids folded mod P when P differs from M), ready for
        :func:`repro.gnn.distributed.distributed_gcn_forward`. Plans are
        built through the sparse O(E) edge-list path — no N×N work — so
        serving stays viable at PubMed-scale layouts; the forward picks the
        gather aggregation automatically for such plans. ``exchange``
        selects the halo layout: ``"gather"`` (all_gather of each device's
        boundary union — the single-host default) or ``"pair"`` (all_to_all
        over exactly the cut edges — the multi-host wire format, see
        ``repro.gnn.multihost``)."""
        from repro.gnn.distributed import make_partition_plan_sparse
        m = int(np.asarray(self.cost.t_tran).shape[0])
        p = m if num_devices is None else num_devices
        assign = np.asarray(self.servers, np.int64).copy()
        assign[assign >= 0] %= p
        return make_partition_plan_sparse(state_edges(self.state), assign,
                                          p, n=self.state.capacity,
                                          exchange=exchange)

    def summary(self) -> dict:
        """Flat dict in the legacy ``GraphEdge.offload`` result format."""
        return {
            "assignment": self.servers.copy(),
            "subgraphs": self.partition.subgraph.copy(),
            "num_subgraphs": self.partition.num_subgraphs,
            "reward": self.assignment.reward,
            "system_cost": float(self.cost.c),
            "t_all": float(self.cost.t_all),
            "i_all": float(self.cost.i_all),
            "cross_bits": float(self.cost.cross_bits.sum()),
            **{k: v for k, v in self.assignment.stats.items()
               if k not in ("reward", "system_cost", "t_all", "i_all",
                            "cross_bits")},
        }


class CacheInfo(NamedTuple):
    """``functools.lru_cache``-style counters (partition + plan caches)."""
    hits: int
    misses: int
    maxsize: int
    currsize: int


class LruCache:
    """Tiny bounded LRU with hit/miss counters — shared by the controller's
    topology-keyed partition cache and the serving engine's plan cache."""

    def __init__(self, maxsize: int):
        self.maxsize = int(maxsize)
        self._data: OrderedDict = OrderedDict()
        self.hits = 0
        self.misses = 0

    def get(self, key):
        """Cached value (refreshing recency) or None; counts the lookup."""
        val = self._data.get(key)
        if val is None:
            self.misses += 1
            return None
        self._data.move_to_end(key)
        self.hits += 1
        return val

    def put(self, key, value) -> None:
        self._data[key] = value
        while len(self._data) > self.maxsize:
            self._data.popitem(last=False)          # evict LRU entry

    def __len__(self) -> int:
        return len(self._data)

    def clear(self) -> None:
        """Drop every entry (counters survive — they describe lookups,
        not contents). Used when cached values are invalidated wholesale,
        e.g. a fault event changes the effective server set."""
        self._data.clear()

    def info(self) -> CacheInfo:
        return CacheInfo(self.hits, self.misses, self.maxsize,
                         len(self._data))


class JitStepResult(NamedTuple):
    """All-jnp control-step output (the ``jit_step_fn`` return pytree)."""
    subgraph: jnp.ndarray         # [N] i32 — partition ids (−1 inactive)
    servers: jnp.ndarray          # [N] i32 — offload assignment (−1 inactive)
    reward: jnp.ndarray           # []  f32 — Σ per-step rewards (Eq. 23)
    cost: costs.SystemCost


@dataclass
class GraphEdgeController:
    """EC controller: perceive → partition → offload → account, pluggable.

    ``partitioner`` / ``policy`` accept either registry names or instances;
    kwargs for name-based construction go in ``partitioner_kwargs`` /
    ``policy_kwargs`` (e.g. ``policy="drlgo",
    policy_kwargs={"trainer": trainer}``).

    With a :class:`JitPolicy` (``greedy_jit`` / ``local_jit``), ``step()``
    runs the offload + cost accounting as a single jitted XLA call instead
    of walking the numpy env user by user; learned / numpy policies keep
    the env-stepping path. ``jit_step_fn()`` returns the fully-pure
    ``state → JitStepResult`` closure (partition included) for callers that
    put whole rollouts under ``jax.jit`` / ``lax.scan``.
    """
    net: costs.EdgeNetwork
    policy: OffloadPolicy | str = "greedy"
    partitioner: Partitioner | str = "hicut_jax"
    policy_kwargs: dict = field(default_factory=dict)
    partitioner_kwargs: dict = field(default_factory=dict)
    gnn: costs.GNNCostParams = field(default_factory=costs.GNNCostParams)
    zeta_sp: float = 0.1          # ζ (Eq. 25)
    cost_scale: float = 1.0       # reward normalizer
    use_subgraph_reward: bool | None = None   # None → auto (off for "none")
    cache_partitions: bool = True
    cache_size: int = 64          # LRU bound on distinct cached topologies

    def __post_init__(self):
        if isinstance(self.partitioner, str):
            self.partitioner = get_partitioner(self.partitioner,
                                               **self.partitioner_kwargs)
        if isinstance(self.policy, str):
            self.policy = get_offload_policy(self.policy,
                                             **self.policy_kwargs)
        if self.use_subgraph_reward is None:
            self.use_subgraph_reward = self.partitioner.name != "none"
        self._partition_cache = LruCache(self.cache_size)

    # -- perceive + partition (cached on topology) --------------------------
    def _partition_cached(self, state: GraphState
                          ) -> tuple[Partition, str | None]:
        """(partition, topology key) — key is None when caching is off.
        The ``ge.control.partition`` profiler span, with stat ``hit``."""
        with TraceAnnotation("ge.control.partition") as span:
            if not self.cache_partitions:
                span.set_metadata(hit=0)
                return self.partitioner(state), None
            key = topology_key(state)
            part = self._partition_cache.get(key)
            span.set_metadata(hit=int(part is not None))
            if part is None:
                part = self.partitioner(state)
                self._partition_cache.put(key, part)
            return part, key

    def partition(self, state: GraphState) -> Partition:
        """Run (or reuse) the partitioner. The cut depends only on the
        topology (mask + adjacency), so pure-mobility steps hit the cache —
        a bounded LRU (``cache_size`` entries) keyed by ``topology_key``."""
        return self._partition_cached(state)[0]

    def cache_info(self) -> CacheInfo:
        """Partition-cache counters (``functools.lru_cache`` convention)."""
        return self._partition_cache.info()

    def invalidate_partitions(self) -> None:
        """Flush the topology-keyed partition cache. Call when cached cuts
        stop being the ones you want for their topology — e.g. a fault
        event changed the live server count so re-cuts should target a
        different number of parts (DESIGN.md §9)."""
        self._partition_cache.clear()

    def recut_warm(self, state: GraphState, previous: np.ndarray,
                   num_parts: int | None = None, sweeps: int = 4,
                   imbalance: float = 1.1) -> Partition:
        """Warm-started multilevel re-cut seeded from ``previous`` (the
        last decision's subgraph ids for this topology) — the migration
        path after a fault event (DESIGN.md §9). Skips coarsening and the
        initial cut entirely: the previous assignment is projected onto
        ``num_parts`` parts (default: the number of distinct previous
        parts) and boundary-refined, so the re-cut costs one
        :func:`~repro.core.multilevel.refine` pass instead of a full
        pipeline. The result is installed in the partition cache under the
        state's topology key, so subsequent :meth:`step` calls on the same
        topology reuse it."""
        from repro.core.multilevel import multilevel_partition
        prev = np.asarray(previous, np.int64)
        if num_parts is None:
            live = np.unique(prev[prev >= 0])
            num_parts = max(1, len(live))
        assigned = multilevel_partition(
            state.capacity, state_edges(state), int(num_parts),
            active=np.asarray(state.mask) > 0, sweeps=sweeps,
            imbalance=imbalance, initial=prev)
        part = _finish(state, assigned, "multilevel_warm")
        if self.cache_partitions:
            self._partition_cache.put(topology_key(state), part)
        return part

    @property
    def cache_hits(self) -> int:
        return self._partition_cache.hits

    @property
    def cache_misses(self) -> int:
        return self._partition_cache.misses

    def make_env(self, state: GraphState,
                 partition: Partition | None = None) -> OffloadEnv:
        part = self.partition(state) if partition is None else partition
        return OffloadEnv(self.net, state, part, gnn=self.gnn,
                          zeta_sp=self.zeta_sp,
                          use_subgraph_reward=bool(self.use_subgraph_reward),
                          cost_scale=self.cost_scale)

    def make_batched_env(self, states: list[GraphState],
                         partitions: list[Partition] | None = None
                         ) -> BatchedOffloadEnv:
        """B scenarios (same capacity) → one vmapped
        :class:`~repro.core.offload.batched_env.BatchedOffloadEnv` with this
        controller's partitioner and reward constants. Used by the batched
        DRLGO/PTOM trainers; see DESIGN.md "Batched environment"."""
        if partitions is None:
            partitions = [self.partition(s) for s in states]
        return BatchedOffloadEnv.from_scenarios(
            self.net, states, partitions, gnn=self.gnn, zeta_sp=self.zeta_sp,
            use_subgraph_reward=bool(self.use_subgraph_reward),
            cost_scale=self.cost_scale)

    # -- one control step ----------------------------------------------------
    def step(self, state: GraphState) -> Decision:
        """Perceive → HiCut (or plug-in) → offload → exact cost accounting.

        :class:`JitPolicy` instances dispatch to one jitted
        ``scene → offload → cost`` XLA call (the partition still goes
        through the LRU cache); everything else steps the numpy env."""
        part, key = self._partition_cached(state)
        if isinstance(self.policy, JitPolicy):
            assignment, sc = _jit_decide(
                type(self.policy).decide, self.net, state, part.subgraph,
                self.zeta_sp, 1.0 if self.use_subgraph_reward else 0.0,
                self.cost_scale, self.gnn,
                int(self.net.server_pos.shape[0]))
            return Decision(state, part, assignment, sc, topo_key=key)
        env = self.make_env(state, part)
        assignment = self.policy(env)
        w = assignment.onehot(int(self.net.server_pos.shape[0]))
        sc = costs.system_cost(self.net, state, w, self.gnn)
        return Decision(state, part, assignment, sc, topo_key=key)

    def step_batch(self, states: list[GraphState]) -> list[Decision]:
        """Batched control step: B same-capacity layouts → B Decisions.

        The serving-tier hot path (ISSUE 8 / ROADMAP "batch the controller
        step too"): partitions are looked up per layout through the
        topology-keyed LRU exactly as in :meth:`step`, then the offload
        decision + exact cost for *all* B layouts runs as **one** vmapped
        jitted XLA call (:func:`_jit_offload_and_cost_batch`) instead of B
        sequential dispatches — the per-request decide cost is amortized
        across the whole scheduling cycle. Requires a :class:`JitPolicy`;
        other policies (and B = 1) fall back to per-state :meth:`step`.
        Results are positionally aligned with ``states``."""
        if not states:
            return []
        cap = states[0].capacity
        if len(states) == 1 or not isinstance(self.policy, JitPolicy) \
                or any(s.capacity != cap for s in states):
            return [self.step(s) for s in states]
        looked_up = [self._partition_cached(s) for s in states]
        parts = [p for p, _ in looked_up]
        with TraceAnnotation("ge.control.dispatch", batch=len(states)):
            subs = jnp.asarray(np.stack([np.asarray(p.subgraph, np.int32)
                                         for p in parts]))
            assign_b, reward_b, sc_b = _jit_offload_and_cost_batch(
                self.net, stack_states(list(states)), subs, self.zeta_sp,
                1.0 if self.use_subgraph_reward else 0.0, self.cost_scale,
                self.gnn, type(self.policy).decide,
                int(self.net.server_pos.shape[0]))
        # one host fetch for the whole batch, then pure numpy unpacking
        with TraceAnnotation("ge.control.wait"):
            assign_np = np.asarray(assign_b, np.int64)
        with TraceAnnotation("ge.control.unpack"):
            reward_np = np.asarray(reward_b, np.float64)
            sc_np = jax.tree_util.tree_map(np.asarray, sc_b)
            decisions = []
            for b, (state, (part, key)) in enumerate(zip(states,
                                                         looked_up)):
                sc = jax.tree_util.tree_map(lambda leaf: leaf[b], sc_np)
                stats = {"reward": float(reward_np[b]),
                         "system_cost": float(sc.c),
                         "t_all": float(sc.t_all),
                         "i_all": float(sc.i_all),
                         "cross_bits": float(sc.cross_bits.sum())}
                assignment = Assignment(assign_np[b], float(reward_np[b]),
                                        stats)
                decisions.append(Decision(state, part, assignment, sc,
                                          topo_key=key))
        return decisions

    def jit_step_batch_fn(self) -> Callable[[GraphState], JitStepResult]:
        """Batched twin of :meth:`jit_step_fn`: a pure traceable closure
        over a **stacked** [B, ...] GraphState pytree
        (``batched_env.stack_states``) returning a stacked
        :class:`JitStepResult` — partition (re-cut inside the trace, like
        ``jit_step_fn``), offload scan and exact cost, vmapped so a whole
        scheduling cycle is one XLA computation. Same :class:`JitPolicy` /
        :class:`JitPartitioner` requirements as :meth:`jit_step_fn`."""
        return jax.vmap(self.jit_step_fn())

    def jit_step_fn(self) -> Callable[[GraphState], JitStepResult]:
        """Pure ``state → JitStepResult`` closure over this controller's
        network/constants: partition (a :class:`JitPartitioner`:
        ``hicut_jax``, ``none`` or ``multilevel_jax``) → jit-policy scan →
        exact Eqs. (12)–(14) cost. The
        returned function is traceable — wrap it in ``jax.jit`` or drive a
        whole rollout through ``lax.scan`` with zero host round-trips.
        (No partition caching: inside a trace every step re-cuts.)"""
        if not isinstance(self.policy, JitPolicy):
            raise TypeError(
                f"policy {self.policy.name!r} has no pure decide(); "
                f"jit_step_fn needs a JitPolicy "
                f"(e.g. greedy_jit/local_jit/lyapunov)")
        if not isinstance(self.partitioner, JitPartitioner):
            raise ValueError(
                f"partitioner {self.partitioner.name!r} is not jnp-pure; "
                f"jit_step_fn needs a JitPartitioner with a traceable "
                f"cut() (e.g. hicut_jax, none, multilevel_jax)")
        part_fn = self.partitioner.cut
        net, gnn = self.net, self.gnn
        zeta_sp, cost_scale = self.zeta_sp, self.cost_scale
        sub_w = 1.0 if self.use_subgraph_reward else 0.0
        decide = type(self.policy).decide
        m = int(net.server_pos.shape[0])

        def step_fn(state: GraphState) -> JitStepResult:
            subgraph = part_fn(state).astype(jnp.int32)
            scene = _scene_core(net, state, subgraph, zeta_sp, sub_w,
                                cost_scale, gnn)
            assign, reward = decide(scene)
            w = costs.assignment_onehot(assign, m)
            return JitStepResult(subgraph, assign, reward,
                                 costs.system_cost(net, state, w, gnn))
        return step_fn

    # -- multi-step control --------------------------------------------------
    def rollout(self, state: GraphState, steps: int,
                rng: np.random.Generator | None = None,
                change_rate: float = 0.2) -> list[Decision]:
        """Drive ``steps`` control steps through the dynamic-graph event
        model (§3.2 / §6.4): each step perturbs user count, positions and
        associations at ``change_rate``, then runs :meth:`step`."""
        rng = np.random.default_rng(0) if rng is None else rng
        decisions = []
        for _ in range(steps):
            state = perturb_scenario(rng, state, change_rate)
            decisions.append(self.step(state))
        return decisions
