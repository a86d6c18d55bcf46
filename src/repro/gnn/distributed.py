"""Distributed GNN inference over a device mesh (shard_map halo exchange).

TPU-native mapping of the paper's multi-edge-server GNN inference (Fig. 1):
edge server → mesh device, cross-server message passing → halo-exchange
all-gather over the mesh axis. The HiCut-optimized layout (few cross-
subgraph edges) directly shrinks the halo buffer — the static per-device
bound ``halo`` below — and therefore the collective bytes, realizing the
paper's objective P1 (Eq. 15) in ICI bytes.

Vertices are permuted so each device owns a contiguous, equally-padded
block. Each layer: (1) every device publishes its *boundary rows* (owned
rows with a cross-partition edge) into a fixed [halo, F] buffer,
(2) ``all_gather`` over the axis, (3) blocked aggregation against the
device's extended adjacency slice [L, L + P·halo].

Plans are built **sparse-first**: :func:`make_partition_plan_sparse` is
vectorized numpy over a COO edge list — O(E) work and memory, no N×N array
anywhere — and stores the extended adjacency as blocked-sparse padded
neighbor lists (``nbr_idx``/``nbr_val``, per-device local cols + halo
cols). The dense entry point :func:`make_partition_plan` is a thin wrapper
that also materializes the dense ``adj_ext`` blocks (small graphs, and the
oracle form the dense Pallas kernel consumes);
:func:`make_partition_plan_dense_reference` keeps the original triple-loop
builder as the parity oracle for tests and the perf baseline for
``benchmarks/bench_partition_plan.py``.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels.gnn_aggregate.ops import (padded_neighbors_from_coo,
                                             rank_within_sorted_groups,
                                             sort_neighbor_slots)


@dataclass
class PartitionPlan:
    num_devices: int
    block: int                 # L — owned vertices per device (padded)
    halo: int                  # B — max boundary rows any device publishes
    n: int                     # global vertex-slot count (gather/forward size)
    perm: np.ndarray           # [P*L] global vertex id per slot (−1 = pad)
    send_idx: np.ndarray       # [P, B] local slot of each published row
    send_mask: np.ndarray      # [P, B] 1 where send_idx is real
    nbr_idx: np.ndarray        # [P, L, K] extended-col id per neighbor slot
    nbr_val: np.ndarray        # [P, L, K] edge weight (0 = pad slot)
    mask: np.ndarray           # [P, L] active-vertex mask per slot
    adj_ext: np.ndarray | None = None   # dense [P, L, L+P*B] blocks (lazy)

    # Two exchange layouts share this dataclass (DESIGN.md §8):
    #  * "gather" — send_idx/send_mask are [P, B]: every device publishes
    #    the union of its boundary rows, all-gathered to every peer.
    #  * "pair" — send_idx/send_mask are [P, P, B]: entry [q, p] lists the
    #    rows device q sends to device p, exchanged with one all_to_all
    #    over exactly the cut edges — no row travels to a device that
    #    doesn't read it. ``halo`` is then the max *per-pair* send count.

    @property
    def exchange(self) -> str:
        return "pair" if self.send_idx.ndim == 3 else "gather"

    @property
    def padded_n(self) -> int:
        return self.num_devices * self.block

    @property
    def ext_cols(self) -> int:
        return self.block + self.num_devices * self.halo

    @property
    def max_degree(self) -> int:
        """K — padded neighbor slots per row."""
        return self.nbr_idx.shape[2]

    @property
    def num_edges(self) -> int:
        """Directed (both-ways) edge count stored in the plan."""
        return int(np.count_nonzero(self.nbr_val))

    @property
    def density(self) -> float:
        """Global edge density nnz/N² of the planned layout."""
        return self.num_edges / max(self.n * self.n, 1)

    def bytes_per_aggregate(self, feature_dim: int,
                            dtype_bytes: int = 4) -> int:
        """Cross-device traffic per layer. "gather" layout: every device
        receives the other devices' [B, F] halo buffers (ring all-gather
        model). "pair" layout: the all_to_all moves one [B, F] chunk per
        *ordered pair* of distinct devices — same formula, but B is the
        per-pair send bound, which only counts rows the receiver reads."""
        p, b = self.num_devices, self.halo
        return p * (p - 1) * b * feature_dim * dtype_bytes

    def replicate_bytes_per_aggregate(self, feature_dim: int,
                                      dtype_bytes: int = 4) -> int:
        """Traffic of the replicate-everything baseline: every device ships
        its whole [L, F] block to every peer each layer — what serving
        would pay without the halo layout (the multihost bench's
        denominator)."""
        p = self.num_devices
        return p * (p - 1) * self.block * feature_dim * dtype_bytes

    def dense_adj_ext(self) -> np.ndarray:
        """Materialize (and memoize) the dense [P, L, L+P*B] blocks from the
        blocked-sparse form. Only for small layouts / the dense kernel."""
        if self.adj_ext is None:
            out = np.zeros((self.num_devices, self.block, self.ext_cols),
                           np.float32)
            pp = np.arange(self.num_devices)[:, None, None]
            ll = np.arange(self.block)[None, :, None]
            np.add.at(out, (np.broadcast_to(pp, self.nbr_idx.shape),
                            np.broadcast_to(ll, self.nbr_idx.shape),
                            self.nbr_idx), self.nbr_val)
            self.adj_ext = out
        return self.adj_ext

    def scatter(self, x: np.ndarray, fill: float = 0.0) -> np.ndarray:
        """[N, ...] global array → [P, L, ...] per-device blocks."""
        out = np.full((self.padded_n,) + x.shape[1:], fill, x.dtype)
        valid = self.perm >= 0
        out[valid] = x[self.perm[valid]]
        return out.reshape((self.num_devices, self.block) + x.shape[1:])

    def gather(self, blocks: np.ndarray) -> np.ndarray:
        """[P, L, ...] → [N, ...] (inverse of scatter)."""
        flat = np.asarray(blocks).reshape((self.padded_n,) + blocks.shape[2:])
        out = np.zeros((self.n,) + flat.shape[1:], flat.dtype)
        valid = self.perm >= 0
        out[self.perm[valid]] = flat[valid]
        return out

    def scatter_batch(self, xs, pad_to: int | None = None) -> np.ndarray:
        """Stack B global [N, F] arrays into the batched-forward layout
        [P, B', L, F] (device-major, so the mesh sharding spec is the same
        as the single-request path). ``pad_to`` zero-pads the batch axis to
        a fixed bucket size so batch shapes — and therefore jit compiles —
        stay bounded."""
        b = len(xs) if pad_to is None else int(pad_to)
        assert b >= len(xs), (b, len(xs))
        blocks = [self.scatter(np.asarray(x, np.float32)) for x in xs]
        out = np.zeros((self.num_devices, b) + blocks[0].shape[1:],
                       np.float32)
        for i, blk in enumerate(blocks):
            out[:, i] = blk
        return out

    def gather_batch(self, blocks: np.ndarray, count: int | None = None
                     ) -> list[np.ndarray]:
        """[P, B', L, ...] → ``count`` global [N, ...] arrays (padded batch
        slots beyond ``count`` are dropped)."""
        blocks = np.asarray(blocks)
        count = blocks.shape[1] if count is None else int(count)
        return [self.gather(blocks[:, i]) for i in range(count)]


def make_partition_plan_sparse(edges: np.ndarray, assign: np.ndarray,
                               num_devices: int, n: int | None = None,
                               weights: np.ndarray | None = None,
                               exchange: str = "gather") -> PartitionPlan:
    """Build the halo-exchange plan from a COO edge list — O(E), no N×N.

    ``edges`` is [E, 2] *unique undirected* pairs (i ≠ j, any order); an
    optional ``weights`` [E] carries per-edge values (default 1.0).
    With ``exchange="gather"`` semantics match
    :func:`make_partition_plan_dense_reference` exactly: same perm (owned
    vertices ascending per device), same boundary order, same
    extended-column layout. ``exchange="pair"`` builds the halo-only
    layout instead: per-(sender, receiver) send lists and extended columns
    addressing the all_to_all receive buffer, so cross-device traffic is
    exactly the cut rows (see :class:`PartitionPlan`)."""
    if exchange not in ("gather", "pair"):
        raise ValueError(f"unknown exchange {exchange!r}")
    assign = np.asarray(assign, np.int64)
    n = len(assign) if n is None else int(n)
    assert len(assign) == n, (len(assign), n)
    edges = np.asarray(edges, np.int64).reshape(-1, 2)
    w = (np.ones(len(edges), np.float32) if weights is None
         else np.asarray(weights, np.float32))
    active = assign >= 0

    # perm / local slots: actives grouped by device, ascending global id
    act_ids = np.nonzero(active)[0]
    order = np.argsort(assign[act_ids], kind="stable")
    owned = act_ids[order]                       # sorted by (device, id)
    dev = assign[owned]
    rank, counts = rank_within_sorted_groups(dev, num_devices)
    block = max(1, int(counts.max(initial=0)))
    perm = -np.ones(num_devices * block, np.int64)
    perm[dev * block + rank] = owned
    local_slot = -np.ones(n, np.int64)
    local_slot[owned] = rank
    mask = (np.arange(block)[None, :] < counts[:, None]).astype(np.float32)

    # symmetrize to directed edges between active endpoints
    i, j = edges.T if len(edges) else (np.zeros(0, np.int64),) * 2
    keep = active[i] & active[j] & (i != j) if len(edges) else \
        np.zeros(0, bool)
    src = np.concatenate([i[keep], j[keep]])
    dst = np.concatenate([j[keep], i[keep]])
    w2 = np.concatenate([w[keep], w[keep]])
    cross = assign[src] != assign[dst]

    if exchange == "pair":
        # per-ordered-pair send lists: device q sends row u to device p iff
        # some row p owns has u as a cross neighbor. One sorted unique pass
        # over (q, p, u) keys yields each list in ascending-global-id order.
        cq = assign[dst[cross]]                  # sender (owns the row)
        cp = assign[src[cross]]                  # receiver (reads the row)
        key = (cq * num_devices + cp) * n + dst[cross]
        uniq = np.unique(key)
        uq, rem = np.divmod(uniq, num_devices * n)
        up, uu = np.divmod(rem, n)
        p_rank, p_counts = rank_within_sorted_groups(
            uq * num_devices + up, num_devices * num_devices)
        halo = max(1, int(p_counts.max(initial=0)))
        send_idx = np.zeros((num_devices, num_devices, halo), np.int64)
        send_mask = np.zeros((num_devices, num_devices, halo), np.float32)
        send_idx[uq, up, p_rank] = local_slot[uu]
        send_mask[uq, up, p_rank] = 1.0
        # receive-buffer position of each cross edge's source row: the
        # receiver's all_to_all output stacks sender chunks [q, s, F], so
        # the extended column is block + q·halo + rank-in-(q→p)-list
        halo_col = cq * halo + p_rank[np.searchsorted(uniq, key)]
        col = local_slot[dst].copy()
        col[cross] = block + halo_col
    else:
        # boundary rows: owned vertices with ≥1 cross-device edge publish
        # once, to everyone (union of destinations)
        is_boundary = np.zeros(n, bool)
        is_boundary[src[cross]] = True
        b_ids = np.nonzero(is_boundary)[0]       # ascending global id
        b_order = np.argsort(assign[b_ids], kind="stable")
        b_sorted = b_ids[b_order]
        b_dev = assign[b_sorted]
        b_rank, b_counts = rank_within_sorted_groups(b_dev, num_devices)
        halo = max(1, int(b_counts.max(initial=0)))
        send_idx = np.zeros((num_devices, halo), np.int64)
        send_mask = np.zeros((num_devices, halo), np.float32)
        send_idx[b_dev, b_rank] = local_slot[b_sorted]
        send_mask[b_dev, b_rank] = 1.0
        halo_of = -np.ones(n, np.int64)          # flat halo-buffer position
        halo_of[b_sorted] = b_dev * halo + b_rank
        col = np.where(cross, block + halo_of[dst], local_slot[dst])

    flat_row = assign[src] * block + local_slot[src]
    nbr_idx, nbr_val = padded_neighbors_from_coo(flat_row, col, w2,
                                                 num_devices * block)
    k = nbr_idx.shape[1]
    return PartitionPlan(num_devices, block, halo, n, perm, send_idx,
                         send_mask, nbr_idx.reshape(num_devices, block, k),
                         nbr_val.reshape(num_devices, block, k), mask)


def make_partition_plan(adj: np.ndarray, assign: np.ndarray,
                        num_devices: int) -> PartitionPlan:
    """Dense entry point: N×N (symmetric, no self-loop) adjacency → plan.

    Thin wrapper over :func:`make_partition_plan_sparse` (the adjacency is
    converted to its upper-triangular edge list); the dense ``adj_ext``
    blocks are materialized eagerly so dense-input callers keep the
    blocked-matmul serving path."""
    adj = np.asarray(adj)
    i, j = np.nonzero(np.triu(adj, k=1))
    plan = make_partition_plan_sparse(np.stack([i, j], 1), assign,
                                      num_devices, n=adj.shape[0],
                                      weights=adj[i, j].astype(np.float32))
    plan.dense_adj_ext()
    return plan


def make_partition_plan_dense_reference(adj: np.ndarray, assign: np.ndarray,
                                        num_devices: int) -> PartitionPlan:
    """The original O(N²) triple-loop builder — parity oracle + perf
    baseline for the sparse path (tests/test_partition_sparse.py,
    benchmarks/bench_partition_plan.py)."""
    n = adj.shape[0]
    assign = np.asarray(assign)
    active = assign >= 0
    owned = [np.nonzero(assign == p)[0] for p in range(num_devices)]
    block = max(1, max(len(o) for o in owned))
    perm = -np.ones(num_devices * block, np.int64)
    local_slot = -np.ones(n, np.int64)
    for p, o in enumerate(owned):
        perm[p * block:p * block + len(o)] = o
        local_slot[o] = np.arange(len(o))

    cross = adj * (assign[:, None] != assign[None, :]) * \
        active[:, None] * active[None, :]
    boundary = [np.nonzero((cross[o] > 0).any(1))[0] if len(o) else
                np.zeros(0, np.int64) for o in owned]     # local indices
    halo = max(1, max(len(b) for b in boundary))
    send_idx = np.zeros((num_devices, halo), np.int64)
    send_mask = np.zeros((num_devices, halo), np.float32)
    for p, b in enumerate(boundary):
        send_idx[p, :len(b)] = b
        send_mask[p, :len(b)] = 1.0

    # global position of each published row in the flattened halo buffer
    halo_of: dict[int, int] = {}
    for p, b in enumerate(boundary):
        for slot, li in enumerate(b):
            halo_of[int(owned[p][li])] = p * halo + slot

    ext_cols = block + num_devices * halo
    adj_ext = np.zeros((num_devices, block, ext_cols), np.float32)
    for p, o in enumerate(owned):
        for li, g in enumerate(o):
            for gj in np.nonzero(adj[g])[0]:
                if not active[gj]:
                    continue
                if assign[gj] == p:
                    adj_ext[p, li, local_slot[gj]] = adj[g, gj]
                else:
                    adj_ext[p, li, block + halo_of[int(gj)]] = adj[g, gj]

    mask = np.zeros((num_devices, block), np.float32)
    for p, o in enumerate(owned):
        mask[p, :len(o)] = 1.0
    # padded neighbor form of the same blocks (row-major nonzero order)
    pidx, li, ci = np.nonzero(adj_ext)
    nbr_idx, nbr_val = padded_neighbors_from_coo(
        pidx * block + li, ci, adj_ext[pidx, li, ci], num_devices * block)
    k = nbr_idx.shape[1]
    return PartitionPlan(num_devices, block, halo, n, perm, send_idx,
                         send_mask, nbr_idx.reshape(num_devices, block, k),
                         nbr_val.reshape(num_devices, block, k), mask,
                         adj_ext)


# ---------------------------------------------------------------------------
# cross-topology shape buckets (DESIGN.md §7 "Cross-topology batching")
# ---------------------------------------------------------------------------

# Plans pad their (block, halo, max_degree) slot shapes up to multiples of
# this quantum before joining a cross-topology batch, so dynamically
# perturbed topologies whose plans differ by a few vertices/edges land in
# the SAME shape bucket (one compiled executable, one dispatch) instead of
# one bucket each. Larger quanta share more but pad more.
PLAN_BUCKET_QUANTUM = 8


def _ceil_to(v: int, q: int) -> int:
    return max(q, -(-int(v) // q) * q)


def plan_bucket(plan: PartitionPlan,
                quantum: int = PLAN_BUCKET_QUANTUM) -> tuple:
    """Shape bucket of a plan: ``(P, n, block', halo', k')`` with the slot
    dims rounded up to ``quantum``. Two plans in the same bucket can be
    padded (:func:`pad_plan`) to identical array shapes and served by one
    dispatch of :func:`_forward_blocks_multi` — the bucket tuple *is* the
    cross-topology batch key (the jit cache then keys on these shapes)."""
    base = (plan.num_devices, plan.n, _ceil_to(plan.block, quantum),
            _ceil_to(plan.halo, quantum), _ceil_to(plan.max_degree, quantum))
    # the two exchange layouts are never batch-compatible: same dims mean
    # different extended-column semantics, so pair plans get their own key
    return base + (("pair",) if plan.exchange == "pair" else ())


def pad_plan(plan: PartitionPlan, block: int, halo: int,
             k: int) -> PartitionPlan:
    """Pad a plan to ``(block, halo, k)`` slot shapes, exactly preserving
    its forward semantics.

    Padding appends inert slots only: pad rows carry ``mask = 0`` and zero
    neighbor values, pad halo slots carry ``send_mask = 0`` (they publish
    zero rows), pad neighbor slots carry value 0. Extended-column ids are
    remapped to the widened ``[block' | P × halo']`` layout — a cross-edge
    at old position ``q·halo + s`` of the flattened halo buffer moves to
    ``q·halo' + s``, so every gathered value is unchanged and the padded
    forward is numerically identical to the original (the scan-based
    aggregates are *bitwise* identical: pads only ever add exact zeros)."""
    p = plan.num_devices
    assert block >= plan.block and halo >= plan.halo \
        and k >= plan.max_degree, ((block, halo, k),
                                   (plan.block, plan.halo, plan.max_degree))
    perm = -np.ones((p, block), np.int64)
    perm[:, :plan.block] = plan.perm.reshape(p, plan.block)
    # send maps pad on the slot axis only — [P, H] (gather) and [P, P, H]
    # (pair) both keep their leading layout axes
    send_idx = np.zeros(plan.send_idx.shape[:-1] + (halo,), np.int64)
    send_idx[..., :plan.halo] = plan.send_idx
    send_mask = np.zeros(plan.send_mask.shape[:-1] + (halo,), np.float32)
    send_mask[..., :plan.halo] = plan.send_mask
    mask = np.zeros((p, block), np.float32)
    mask[:, :plan.block] = plan.mask
    # neighbor slots: remap extended cols into the widened layout, then pad
    old_idx, old_val = plan.nbr_idx, plan.nbr_val
    flat_halo = old_idx - plan.block          # q·halo + s for cross edges
    remapped = np.where(
        old_idx >= plan.block,
        block + (flat_halo // plan.halo) * halo + flat_halo % plan.halo,
        old_idx)
    remapped = np.where(old_val != 0, remapped, 0)   # pad slots → col 0
    nbr_idx = np.zeros((p, block, k), np.int64)
    nbr_val = np.zeros((p, block, k), np.float32)
    nbr_idx[:, :plan.block, :plan.max_degree] = remapped
    nbr_val[:, :plan.block, :plan.max_degree] = old_val
    return PartitionPlan(p, block, halo, plan.n, perm.reshape(-1), send_idx,
                         send_mask, nbr_idx, nbr_val, mask)


def pad_plan_to_bucket(plan: PartitionPlan, bucket: tuple) -> PartitionPlan:
    """Pad a plan to its (or a compatible) :func:`plan_bucket` shape."""
    p, n, block, halo, k = bucket[:5]
    exch = bucket[5] if len(bucket) > 5 else "gather"
    assert (p, n, plan.exchange) == (plan.num_devices, plan.n, exch), \
        (bucket, plan.num_devices, plan.n, plan.exchange)
    return pad_plan(plan, block, halo, k)


def scatter_multi(plans: Sequence[PartitionPlan], xs,
                  pad_to: int | None = None) -> np.ndarray:
    """Per-member scatter into one [P, B', L, F] cross-topology batch:
    member i's features are laid out by *its own* plan's perm (the plans
    must share a shape bucket). ``pad_to`` zero-fills the batch axis."""
    b = len(xs) if pad_to is None else int(pad_to)
    assert b >= len(xs) and len(plans) >= len(xs), (b, len(xs), len(plans))
    blocks = [plan.scatter(np.asarray(x, np.float32))
              for plan, x in zip(plans, xs)]
    out = np.zeros((blocks[0].shape[0], b) + blocks[0].shape[1:], np.float32)
    for i, blk in enumerate(blocks):
        out[:, i] = blk
    return out


def gather_multi(plans: Sequence[PartitionPlan], blocks: np.ndarray,
                 count: int | None = None) -> list[np.ndarray]:
    """Inverse of :func:`scatter_multi`: member i's output is gathered by
    its own plan's perm (padded batch slots beyond ``count`` dropped)."""
    blocks = np.asarray(blocks)
    count = blocks.shape[1] if count is None else int(count)
    return [plans[i].gather(blocks[:, i]) for i in range(count)]


def _halo_exchange(x_blk, send_idx, send_mask, axis: str):
    """Exchange boundary rows: [L, F] → extended rows [L + P·B, F].

    Dispatches on the send map's rank (static at trace time, so every
    jitted forward gains both paths without signature changes):

    * gather layout (``send_idx`` [B]): publish the boundary-row union
      once and ``all_gather`` every device's buffer — each device receives
      P·B rows whether it reads them or not.
    * pair layout (``send_idx`` [P, B]): build one [B, F] chunk per
      destination and ``all_to_all`` them — device p's chunk q holds
      exactly the rows q sends to p, so the wire carries only cut rows
      and the receive buffer is already in extended-column order."""
    if send_idx.ndim == 2:
        published = x_blk[send_idx] * send_mask[..., None]   # [P, B, F]
        halo = jax.lax.all_to_all(published, axis, 0, 0)     # [P, B, F]
    else:
        published = x_blk[send_idx] * send_mask[:, None]
        halo = jax.lax.all_gather(published, axis)           # [P, B, F]
    return jnp.concatenate([x_blk, halo.reshape(-1, halo.shape[-1])], 0)


def _halo_aggregate(x_blk, adj_ext_blk, send_idx, send_mask,
                    rs, cs_ext, axis: str):
    """One distributed normalized aggregation step (runs per device).

    x_blk [L, F]; returns rs·A_ext·cs @ [x_own ; halo]."""
    x_ext = _halo_exchange(x_blk, send_idx, send_mask, axis)
    a = adj_ext_blk * rs[:, None] * cs_ext[None, :]
    return a @ x_ext


def _halo_aggregate_sparse(x_blk, nbr_idx_blk, nbr_val_blk, send_idx,
                           send_mask, rs, cs_ext, axis: str):
    """Sparse variant: gather/scan over the padded neighbor slots instead
    of the [L, L + P·B] dense contraction — O(L·K·F)."""
    x_ext = _halo_exchange(x_blk, send_idx, send_mask, axis)
    xc = x_ext * cs_ext[:, None]

    def step(acc, slot):
        idx_k, val_k = slot
        return acc + val_k[:, None] * xc[idx_k], None

    acc, _ = jax.lax.scan(
        step, jnp.zeros_like(x_blk),
        (nbr_idx_blk.T.astype(jnp.int32), nbr_val_blk.T))
    return acc * rs[:, None]


# Per-layer aggregation step, one per `aggregate` mode. Uniform signature
# (h, w, a_args, sidx, smask, rs, cs_e, axis) → aggregated [L, F_out]: each
# mode places the layer matmul itself, because the fused mode reorders it —
# aggregate the *pre-matmul* activations at F_in width, then project, the
# formulation the fused Pallas kernel (kernels.gnn_aggregate.fused)
# executes on TPU as one gather→MXU pass. Linearity makes all three equal.
# Note the fused halo exchange consequently carries F_in-wide rows where
# dense/sparse exchange F_out-wide ones.

def _agg_step_dense(h, w, a_args, sidx, smask, rs, cs_e, axis: str):
    return _halo_aggregate(h @ w, a_args[0], sidx, smask, rs, cs_e, axis)


def _agg_step_sparse(h, w, a_args, sidx, smask, rs, cs_e, axis: str):
    return _halo_aggregate_sparse(h @ w, a_args[0], a_args[1], sidx, smask,
                                  rs, cs_e, axis)


def _agg_step_fused(h, w, a_args, sidx, smask, rs, cs_e, axis: str):
    agg = _halo_aggregate_sparse(h, a_args[0], a_args[1], sidx, smask, rs,
                                 cs_e, axis)
    return agg @ w


_AGG_STEPS = {"dense": _agg_step_dense, "sparse": _agg_step_sparse,
              "fused": _agg_step_fused}


# Per-slot cost ratio of the gather path vs one dense MAC column: a padded
# neighbor slot costs a random-access row load + FMA where the dense matmul
# streams MXU-aligned tiles. Calibrated on the BENCH_kernels /
# BENCH_partition shapes: dense wins at n=1000 (ext_cols=1004, K=34–35,
# 1004 < 32·35) and loses from n=2000 up (ext_cols≥4154, K≈36–39) — the
# crossover sits well between those, so the exact ratio has margin on
# both sides.
DENSE_AUTO_SLOT_RATIO = 32


def resolve_aggregate(plan: PartitionPlan, aggregate: str = "auto") -> str:
    """Select the per-device contraction: "dense", "sparse" or "fused".

    "auto" compares per-row *work*, not density: the dense path does
    ``ext_cols`` streaming MACs per row, the gather path ``max_degree + 1``
    random-access slot gathers (self-loop included), each worth roughly
    ``DENSE_AUTO_SLOT_RATIO`` dense MACs. Small extended blocks → "dense",
    else "fused" (the gather+normalize+matmul kernel,
    ``kernels.gnn_aggregate.fused``). Density alone mispredicts compact
    layouts — the BENCH_partition n=1000 plan has density 0.02 (well under
    ``SPARSE_DENSITY_THRESHOLD``) yet its 1004-wide extended block keeps
    the dense matmul faster than any gather (agg_speedup 0.85× under the
    old rule). ``bytes_per_aggregate`` (the collective volume) does not
    discriminate: it is layout-independent at equal feature width — only
    the per-device contraction differs between the paths."""
    if aggregate == "auto":
        dense_cols = DENSE_AUTO_SLOT_RATIO * (plan.max_degree + 1)
        return "dense" if plan.ext_cols < dense_cols else "fused"
    if aggregate not in ("dense", "sparse", "fused"):
        raise ValueError(f"unknown aggregate {aggregate!r}")
    return aggregate


def _plan_consts(plan: PartitionPlan, aggregate: str):
    """One-time numpy prep of everything the forward needs from a plan:
    (dinv, cs_ext, agg_args) — the fused-normalization scales and the
    extended adjacency in the selected layout (all jnp, ready to ship)."""
    p_dev, block, halo = plan.num_devices, plan.block, plan.halo
    # global GCN normalization (Â = A+I, D̃^-1/2) computed from the plan mask
    deg_blocks = plan.nbr_val.sum(2) + plan.mask       # self-loop
    dinv = np.where(deg_blocks > 0, 1.0 / np.sqrt(np.maximum(deg_blocks,
                                                             1e-9)), 0.0)
    dinv = dinv.astype(np.float32)
    # extended column scales: own block + halo rows (their global dinv).
    dinv_flat = dinv.reshape(-1)                       # per (p, local)
    if plan.exchange == "pair":
        # per-destination halo segments: device p's slot (q, s) holds the
        # row q sends *to p* (send_idx[q, p, s]) — each device has its own
        # receive buffer, unlike the broadcast gather layout below
        src_slots = np.arange(p_dev)[:, None, None] * block + plan.send_idx
        vals = dinv_flat[src_slots] * plan.send_mask   # [q, p, s]
        cs_halo = vals.transpose(1, 0, 2).reshape(p_dev, p_dev * halo)
    else:
        # the halo segment is the same on every device: slot (q, s) of the
        # flat buffer holds the row published from device q's send_idx[q,s]
        src_slots = np.arange(p_dev)[:, None] * block + plan.send_idx
        flat = (dinv_flat[src_slots] * plan.send_mask).reshape(-1)
        cs_halo = np.broadcast_to(flat, (p_dev, p_dev * halo))
    cs_ext = np.concatenate([dinv, cs_halo], axis=1).astype(np.float32)

    if aggregate == "dense":
        # add self-loops to the extended adjacency (own-block diagonal)
        adj_ext = plan.dense_adj_ext().copy()
        idx = np.arange(block)
        adj_ext[:, idx, idx] += plan.mask
        agg_args = (jnp.asarray(adj_ext),)
    else:
        # self-loops as one extra neighbor slot: col = own slot, val = mask
        self_idx = np.broadcast_to(np.arange(block, dtype=np.int32),
                                   (p_dev, block))[..., None]
        nbr_idx = np.concatenate([plan.nbr_idx.astype(np.int32), self_idx],
                                 axis=2)
        nbr_val = np.concatenate([plan.nbr_val, plan.mask[..., None]],
                                 axis=2)
        if aggregate == "fused":
            # the blocked kernel's sort-by-slot prefetch pass (host-side)
            nbr_idx, nbr_val = sort_neighbor_slots(nbr_idx, nbr_val)
        agg_args = (jnp.asarray(nbr_idx), jnp.asarray(nbr_val))
    return jnp.asarray(dinv), jnp.asarray(cs_ext), agg_args


def _device_layers(x_blk, sidx, smask, rs, cs_e, mask_blk, a_args, ws_,
                   agg_fn, axis: str):
    """The per-device multi-layer GCN body shared by the single-request and
    batched forwards: x_blk [L, F_in] → masked [L, F_out]."""
    h = x_blk
    for i, w in enumerate(ws_):
        h = agg_fn(h, w, a_args, sidx, smask, rs, cs_e, axis)
        if i < len(ws_) - 1:
            h = jax.nn.relu(h)
    return h * mask_blk[:, None]


@partial(jax.jit, static_argnames=("mesh", "axis", "aggregate"))
def _forward_blocks(mesh: Mesh, axis: str, aggregate: str, x_blocks,
                    send_idx, send_mask, dinv, cs_ext, mask, agg_args, ws):
    """Jitted multi-layer forward over the plan's block layout. Returns the
    [P, L, F_out] output blocks as a device array (no host sync). The jit
    cache is keyed on (mesh, axis, aggregate) + array shapes, so repeated
    serving steps — and different plans with equal block/halo/K shapes —
    reuse one compiled executable."""
    agg_fn = _AGG_STEPS[aggregate]

    def device_fn(x_blk, sidx, smask, rs, cs_e, mask_blk, a_args, ws_):
        # strip the sharded leading axis (block size 1 per device)
        x_blk, sidx, smask = x_blk[0], sidx[0], smask[0]
        rs, cs_e, mask_blk = rs[0], cs_e[0], mask_blk[0]
        a_args = tuple(a[0] for a in a_args)
        return _device_layers(x_blk, sidx, smask, rs, cs_e, mask_blk,
                              a_args, ws_, agg_fn, axis)[None]

    specs_in = (P(axis),) * 7 + (P(),)       # agg_args sharded, ws replicated
    fn = jax.shard_map(device_fn, mesh=mesh, in_specs=specs_in,
                       out_specs=P(axis), check_vma=False)
    return fn(x_blocks, send_idx, send_mask, dinv, cs_ext, mask, agg_args,
              ws)


@partial(jax.jit, static_argnames=("mesh", "axis", "aggregate"))
def _forward_blocks_batched(mesh: Mesh, axis: str, aggregate: str, x_blocks,
                            send_idx, send_mask, dinv, cs_ext, mask,
                            agg_args, ws):
    """Batched twin of :func:`_forward_blocks`: ``x_blocks`` is
    [P, B, L, F] (device-major so the sharding spec is unchanged) and every
    batch element runs the same plan's forward — the halo all-gather and
    the per-device aggregation are vmapped over B *inside* the shard_map
    body, so B concurrent requests on one cached plan cost a single XLA
    dispatch and one collective stream instead of B. The jit cache is
    keyed on shapes, so each batch-size bucket compiles once."""
    agg_fn = _AGG_STEPS[aggregate]

    def device_fn(x_bb, sidx, smask, rs, cs_e, mask_blk, a_args, ws_):
        x_bb, sidx, smask = x_bb[0], sidx[0], smask[0]     # [B, L, F]
        rs, cs_e, mask_blk = rs[0], cs_e[0], mask_blk[0]
        a_args = tuple(a[0] for a in a_args)

        def one(x_blk):
            return _device_layers(x_blk, sidx, smask, rs, cs_e, mask_blk,
                                  a_args, ws_, agg_fn, axis)
        return jax.vmap(one)(x_bb)[None]

    specs_in = (P(axis),) * 7 + (P(),)
    fn = jax.shard_map(device_fn, mesh=mesh, in_specs=specs_in,
                       out_specs=P(axis), check_vma=False)
    return fn(x_blocks, send_idx, send_mask, dinv, cs_ext, mask, agg_args,
              ws)


class PlanConsts(NamedTuple):
    """Everything the forward needs from one plan, prepped as jnp arrays
    (:func:`prepare_plan_consts`). Cross-topology batches stack B of these
    — one per member plan, all padded to a shared :func:`plan_bucket` —
    along a batch axis and vmap the device body over them."""
    send_idx: jnp.ndarray     # [P, H]
    send_mask: jnp.ndarray    # [P, H]
    dinv: jnp.ndarray         # [P, L]
    cs_ext: jnp.ndarray       # [P, L + P·H]
    mask: jnp.ndarray         # [P, L]
    agg_args: tuple           # aggregate-layout arrays, each [P, ...]


def prepare_plan_consts(plan: PartitionPlan, aggregate: str) -> PlanConsts:
    """One-time per-plan prep (:func:`_plan_consts` + send maps) in the
    stackable :class:`PlanConsts` form. ``aggregate`` must be resolved."""
    dinv, cs_ext, agg_args = _plan_consts(plan, aggregate)
    return PlanConsts(jnp.asarray(plan.send_idx),
                      jnp.asarray(plan.send_mask), dinv, cs_ext,
                      jnp.asarray(plan.mask), agg_args)


@partial(jax.jit, static_argnames=("mesh", "axis", "aggregate"))
def _forward_blocks_multi(mesh: Mesh, axis: str, aggregate: str, x_blocks,
                          consts: PlanConsts, ws):
    """Cross-topology twin of :func:`_forward_blocks_batched`: ``x_blocks``
    is [P, B, L, F] and every per-plan constant in ``consts`` carries the
    same batch axis ([P, B, ...]) — batch member i is served against *its
    own* plan's send maps, normalization scales and extended adjacency,
    so one dispatch serves B requests resolved against B **different**
    cached plans (padded to one shape bucket). The per-member math is the
    single-plan :func:`_device_layers` body vmapped over (x, consts)
    inside the shard_map, so the collective stream stays single. The jit
    cache keys on shapes = the bucket, so each bucket compiles once per
    batch-size bucket."""
    agg_fn = _AGG_STEPS[aggregate]

    def device_fn(x_bb, sidx, smask, rs, cs_e, mask_blk, a_args, ws_):
        x_bb, sidx, smask = x_bb[0], sidx[0], smask[0]     # [B, ...]
        rs, cs_e, mask_blk = rs[0], cs_e[0], mask_blk[0]
        a_args = tuple(a[0] for a in a_args)

        def one(x_blk, sidx_b, smask_b, rs_b, cs_b, mask_b, args_b):
            return _device_layers(x_blk, sidx_b, smask_b, rs_b, cs_b,
                                  mask_b, args_b, ws_, agg_fn, axis)
        return jax.vmap(one)(x_bb, sidx, smask, rs, cs_e, mask_blk,
                             a_args)[None]

    specs_in = (P(axis),) * 7 + (P(),)
    fn = jax.shard_map(device_fn, mesh=mesh, in_specs=specs_in,
                       out_specs=P(axis), check_vma=False)
    return fn(x_blocks, consts.send_idx, consts.send_mask, consts.dinv,
              consts.cs_ext, consts.mask, consts.agg_args, ws)


def make_multi_forward_fn(mesh: Mesh, axis: str, aggregate: str,
                          consts: Sequence[PlanConsts]):
    """B per-plan :class:`PlanConsts` (same bucket shapes) → one reusable
    non-blocking cross-topology forward.

    Stacks the members' constants along the batch axis once and returns
    ``forward(x_blocks, params)`` over [P, B, L, F] blocks
    (:func:`scatter_multi`) dispatching :func:`_forward_blocks_multi` —
    the cross-topology continuous-batching hot path of
    :class:`repro.serve.frontend.StreamingFrontend`. ``aggregate`` must be
    pre-resolved (resolve on any padded member: bucket mates agree)."""
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs, axis=1),
                                     *consts)

    def forward(x_blocks, params):
        ws = tuple(jnp.asarray(layer["w"]) for layer in params)
        return _forward_blocks_multi(mesh, axis, aggregate,
                                     jnp.asarray(x_blocks), stacked, ws)
    return forward


def make_forward_fn(mesh: Mesh, axis: str, plan: PartitionPlan,
                    aggregate: str = "auto"):
    """Plan → reusable non-blocking forward.

    Does the per-plan numpy prep (normalization scales, extended adjacency,
    send maps) exactly once and returns ``forward(x_blocks, params)`` which
    dispatches the jitted computation and immediately returns the [P, L, F]
    output blocks as a device array — callers overlap host work with the
    in-flight computation and block only when they fetch
    (``plan.gather(np.asarray(out))``). This is the serving engine's hot
    path (``repro.serve.engine``)."""
    aggregate = resolve_aggregate(plan, aggregate)
    dinv, cs_ext, agg_args = _plan_consts(plan, aggregate)
    send_idx = jnp.asarray(plan.send_idx)
    send_mask = jnp.asarray(plan.send_mask)
    mask = jnp.asarray(plan.mask)

    def forward(x_blocks, params):
        ws = tuple(jnp.asarray(layer["w"]) for layer in params)
        return _forward_blocks(mesh, axis, aggregate, jnp.asarray(x_blocks),
                               send_idx, send_mask, dinv, cs_ext, mask,
                               agg_args, ws)
    return forward


def make_batched_forward_fn(mesh: Mesh, axis: str, plan: PartitionPlan,
                            aggregate: str = "auto"):
    """Plan → reusable non-blocking *batched* forward.

    Same one-time prep as :func:`make_forward_fn`, but the returned
    ``forward(x_blocks, params)`` takes [P, B, L, F] blocks
    (``plan.scatter_batch``) and serves all B requests as one dispatch of
    :func:`_forward_blocks_batched` — the continuous-batching hot path of
    :class:`repro.serve.frontend.StreamingFrontend`. Each distinct B
    compiles once; callers bound compile count by padding B to buckets."""
    aggregate = resolve_aggregate(plan, aggregate)
    dinv, cs_ext, agg_args = _plan_consts(plan, aggregate)
    send_idx = jnp.asarray(plan.send_idx)
    send_mask = jnp.asarray(plan.send_mask)
    mask = jnp.asarray(plan.mask)

    def forward(x_blocks, params):
        ws = tuple(jnp.asarray(layer["w"]) for layer in params)
        return _forward_blocks_batched(mesh, axis, aggregate,
                                       jnp.asarray(x_blocks), send_idx,
                                       send_mask, dinv, cs_ext, mask,
                                       agg_args, ws)
    return forward


def distributed_gcn_forward(mesh: Mesh, axis: str, plan: PartitionPlan,
                            params, x: np.ndarray,
                            aggregate: str = "auto") -> np.ndarray:
    """Two-(or more-)layer GCN inference, vertex-partitioned over ``axis``.

    Matches ``repro.gnn.layers.gcn_apply`` exactly (tested); collective
    traffic = plan.bytes_per_aggregate per layer. ``aggregate`` selects the
    per-device contraction: "dense" (blocked matmul over adj_ext), "sparse"
    (gather/scan over the plan's padded neighbor lists), "fused" (the
    gather+normalize+matmul formulation of
    ``kernels.gnn_aggregate.fused``, slot-sorted layout), or "auto"
    (:func:`resolve_aggregate`). One-shot blocking wrapper over
    :func:`make_forward_fn` — pipelined callers build the forward once and
    dispatch asynchronously."""
    forward = make_forward_fn(mesh, axis, plan, aggregate)
    out = forward(plan.scatter(np.asarray(x, np.float32)), params)
    return plan.gather(np.asarray(out))
