"""Public ops: normalized_aggregate (dense), gather_aggregate (sparse) and
fused_gather_aggregate (sparse aggregation + layer matmul in one kernel).

``impl`` on all three:
  * "xla"      — plain jnp (runs everywhere; what the dry-run lowers)
  * "pallas"   — the TPU kernel (real hardware)
  * "interpret"— the Pallas kernel in interpret mode (CPU validation)

The sparse op consumes the *padded neighbor-list* layout ([N, K] ``nbr_idx``
int32 + ``nbr_val`` float32, 0-padded): a fixed-shape padded CSR whose pad
slots carry val = 0, so they are numerically inert no matter which (valid)
index they point at. :func:`padded_neighbors_from_coo` /
:func:`dense_to_padded_neighbors` build that layout in O(E) vectorized
numpy; the partition-plan builder (repro.gnn.distributed) and the layer
auto-dispatch (repro.gnn.layers) share them.

``SPARSE_DENSITY_THRESHOLD`` is the density below which callers holding a
dense adjacency should prefer the gather path (see DESIGN.md §4): at
nnz/N² ≈ 0.05 the K·F gather work is ~20× smaller than the N·F dense
contraction, which covers conversion overhead and the gather's worse
MXU utilization with margin.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.kernels.gnn_aggregate.autotune import (DEFAULT_VMEM_BUDGET,
                                                  get_config, smem_row_cap,
                                                  vmem_bytes)
from repro.kernels.gnn_aggregate.fused import gnn_fused_aggregate_pallas
from repro.kernels.gnn_aggregate.gnn_aggregate import (
    gnn_aggregate_pallas, gnn_gather_aggregate_pallas)
from repro.kernels.gnn_aggregate.ref import (gather_aggregate_ref,
                                             normalized_aggregate_ref)

SPARSE_DENSITY_THRESHOLD = 0.05


def _pad_to(x: jnp.ndarray, mult: int, axes: tuple[int, ...]) -> jnp.ndarray:
    pads = [(0, 0)] * x.ndim
    for ax in axes:
        rem = (-x.shape[ax]) % mult
        pads[ax] = (0, rem)
    return jnp.pad(x, pads) if any(p != (0, 0) for p in pads) else x


def normalized_aggregate(adj: jnp.ndarray, x: jnp.ndarray,
                         row_scale, col_scale, impl: str = "xla",
                         block: int = 128) -> jnp.ndarray:
    if impl == "xla":
        return normalized_aggregate_ref(adj, x, row_scale, col_scale)
    if impl not in ("pallas", "interpret"):
        raise ValueError(f"unknown impl {impl!r}")
    n, f = adj.shape[0], x.shape[1]
    rs = jnp.broadcast_to(jnp.asarray(row_scale, jnp.float32), (n,))
    cs = jnp.broadcast_to(jnp.asarray(col_scale, jnp.float32), (n,))
    adj_p = _pad_to(adj, block, (0, 1))
    x_p = _pad_to(x, block, (0, 1))
    rs_p = _pad_to(rs, block, (0,))
    cs_p = _pad_to(cs, block, (0,))
    y = gnn_aggregate_pallas(adj_p, x_p, rs_p, cs_p,
                             bm=block, bk=block, bf=block,
                             interpret=(impl == "interpret"))
    return y[:n, :f]


# ---------------------------------------------------------------------------
# sparse path: padded neighbor-list layout + gather op
# ---------------------------------------------------------------------------

def rank_within_sorted_groups(groups: np.ndarray, num_groups: int
                              ) -> tuple[np.ndarray, np.ndarray]:
    """For a sorted group-id array, return (rank within group, group sizes).

    The O(E) bucketing primitive behind every padded/blocked-sparse layout
    here (neighbor slots, per-device vertex slots, halo slots)."""
    counts = np.bincount(groups, minlength=num_groups)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    return np.arange(len(groups)) - starts[groups], counts


def padded_neighbors_from_coo(src: np.ndarray, dst: np.ndarray,
                              val: np.ndarray, n_rows: int,
                              min_k: int = 1
                              ) -> tuple[np.ndarray, np.ndarray]:
    """COO triples → padded per-row neighbor lists, O(E) vectorized.

    Returns ``(nbr_idx [n_rows, K] int32, nbr_val [n_rows, K] float32)``
    with K = max(row degree, ``min_k``); pad slots are (0, 0.0). Duplicate
    (src, dst) entries are kept as separate slots (they sum, like COO)."""
    src = np.asarray(src, np.int64).ravel()
    dst = np.asarray(dst, np.int64).ravel()
    val = np.broadcast_to(np.asarray(val, np.float32), src.shape)
    order = np.argsort(src, kind="stable")
    src_s, dst_s, val_s = src[order], dst[order], val[order]
    pos, deg = rank_within_sorted_groups(src_s, n_rows)
    k = max(min_k, int(deg.max(initial=0)))
    nbr_idx = np.zeros((n_rows, k), np.int32)
    nbr_val = np.zeros((n_rows, k), np.float32)
    nbr_idx[src_s, pos] = dst_s
    nbr_val[src_s, pos] = val_s
    return nbr_idx, nbr_val


def dense_to_padded_neighbors(adj: np.ndarray
                              ) -> tuple[np.ndarray, np.ndarray]:
    """Dense [N, M] adjacency → padded neighbor lists (rows gather cols)."""
    adj = np.asarray(adj)
    src, dst = np.nonzero(adj)
    return padded_neighbors_from_coo(src, dst, adj[src, dst].astype(
        np.float32), adj.shape[0])


def sort_neighbor_slots(nbr_idx, nbr_val) -> tuple[np.ndarray, np.ndarray]:
    """Sort every row's neighbor slots by destination index, pads last.

    The host-side "sort-by-slot prefetch" pass of the blocked fused layout
    (kernels.gnn_aggregate.fused): within a row tile the gathers then walk
    the resident XC slab quasi-monotonically instead of in insertion
    order. Pure slot permutation per row — the aggregate is unchanged up
    to float addition order. Works on [..., K] stacks (numpy, host-side)."""
    idx = np.asarray(nbr_idx)
    val = np.asarray(nbr_val)
    key = np.where(val != 0, idx.astype(np.int64), np.iinfo(np.int64).max)
    order = np.argsort(key, axis=-1, kind="stable")
    return (np.take_along_axis(idx, order, -1),
            np.take_along_axis(val, order, -1))


def gather_block_columns(n_cols: int, k: int, block: int = 128,
                         vmem_budget: int = DEFAULT_VMEM_BUDGET) -> int:
    """The feature-block width ``bf`` for ``gnn_gather_aggregate_pallas``.

    Enforces the kernel docstring's precondition — the resident
    [n_cols, bf] XC slab (plus the [block, K] index/value blocks and the
    output tile) must fit the VMEM budget — by halving ``bf`` from
    ``block`` until it fits, and raising a clear error when even the
    minimum width cannot."""
    def resident(bf: int) -> int:
        return 4 * (n_cols * bf + 2 * block * k + block + block * bf)

    bf = block
    while resident(bf) > vmem_budget and bf > 8:
        bf //= 2
    if resident(bf) > vmem_budget:
        raise ValueError(
            f"gather kernel: the [{n_cols}, {bf}] XC slab plus the "
            f"[{block}, {k}] index/value blocks need {resident(bf)} B, "
            f"over the {vmem_budget} B VMEM budget even at the minimum "
            f"feature block — shard the columns or raise the budget")
    return bf


def gather_aggregate(nbr_idx: jnp.ndarray, nbr_val: jnp.ndarray,
                     x: jnp.ndarray, row_scale, col_scale,
                     impl: str = "xla", block: int = 128,
                     vmem_budget: int | None = None) -> jnp.ndarray:
    """Sparse Y = (diag(rs)·A·diag(cs)) @ X over padded neighbor lists."""
    if impl == "xla":
        return gather_aggregate_ref(nbr_idx, nbr_val, x, row_scale,
                                    col_scale)
    if impl not in ("pallas", "interpret"):
        raise ValueError(f"unknown impl {impl!r}")
    n, k = nbr_idx.shape
    f = x.shape[1]
    budget = DEFAULT_VMEM_BUDGET if vmem_budget is None else int(vmem_budget)
    bf = gather_block_columns(x.shape[0], k, block, budget)
    bm = min(block, smem_row_cap(k))
    cs = jnp.broadcast_to(jnp.asarray(col_scale, jnp.float32),
                          (x.shape[0],))
    xc = x.astype(jnp.float32) * cs[:, None]
    rs = jnp.broadcast_to(jnp.asarray(row_scale, jnp.float32), (n,))
    # pad rows of the neighbor lists and features of xc; pad rows of xc are
    # never indexed (indices stay < x.shape[0]) so only F needs padding there
    idx_p = _pad_to(jnp.asarray(nbr_idx), bm, (0,))
    val_p = _pad_to(jnp.asarray(nbr_val), bm, (0,))
    rs_p = _pad_to(rs, bm, (0,))
    xc_p = _pad_to(xc, bf, (1,))
    y = gnn_gather_aggregate_pallas(idx_p, val_p, xc_p, rs_p,
                                    bm=bm, bf=bf,
                                    interpret=(impl == "interpret"))
    return y[:n, :f].astype(x.dtype)


def fused_gather_aggregate(nbr_idx: jnp.ndarray, nbr_val: jnp.ndarray,
                           x: jnp.ndarray, row_scale, col_scale,
                           w: jnp.ndarray, impl: str = "xla",
                           config=None,
                           vmem_budget: int | None = None) -> jnp.ndarray:
    """Fused layer hot path Y = (diag(rs)·A·diag(cs)·X) @ W, one kernel.

    The gather+normalize aggregation and the layer weight matmul run in a
    single blocked pass (kernels.gnn_aggregate.fused) — the gathered
    neighborhood feeds the matmul tile-locally, never materializing the
    aggregated [N, F_in] slab. ``config`` (an ``autotune.KernelConfig``)
    overrides the tuned blocking; by default ``autotune.get_config``
    resolves it from the persisted tuning table or the closed-form
    heuristic. Callers should pre-sort slots with
    :func:`sort_neighbor_slots` for the prefetch-friendly layout."""
    if impl == "xla":
        y = gather_aggregate_ref(nbr_idx, nbr_val, x.astype(jnp.float32),
                                 row_scale, col_scale)
        return (y @ jnp.asarray(w, jnp.float32)).astype(x.dtype)
    if impl not in ("pallas", "interpret"):
        raise ValueError(f"unknown impl {impl!r}")
    n, k = nbr_idx.shape
    n_cols, f_in = x.shape
    f_out = w.shape[1]
    budget = DEFAULT_VMEM_BUDGET if vmem_budget is None else int(vmem_budget)
    if config is None:
        config = get_config(n, n_cols, f_in, f_out, k, vmem_budget=budget)
    if vmem_bytes(config, n_cols, k) > budget:
        raise ValueError(
            f"fused kernel config {tuple(config)} needs "
            f"{vmem_bytes(config, n_cols, k)} B resident for n_cols="
            f"{n_cols}, K={k}, over the {budget} B VMEM budget")
    bm, bf, kc = config
    bm = min(bm, smem_row_cap(k))
    cs = jnp.broadcast_to(jnp.asarray(col_scale, jnp.float32), (n_cols,))
    xc = x.astype(jnp.float32) * cs[:, None]
    rs = jnp.broadcast_to(jnp.asarray(row_scale, jnp.float32), (n,))
    idx_p = _pad_to(_pad_to(jnp.asarray(nbr_idx), bm, (0,)), kc, (1,))
    val_p = _pad_to(_pad_to(jnp.asarray(nbr_val), bm, (0,)), kc, (1,))
    rs_p = _pad_to(rs, bm, (0,))
    xc_p = _pad_to(xc, bf, (1,))
    w_p = _pad_to(jnp.asarray(w, jnp.float32), bf, (0, 1))
    y = gnn_fused_aggregate_pallas(idx_p, val_p, xc_p, rs_p, w_p,
                                   bm=bm, bf=bf, kc=kc,
                                   interpret=(impl == "interpret"))
    return y[:n, :f_out].astype(x.dtype)
