"""Pallas TPU kernel: blocked normalized graph aggregation (masked SpMM).

TPU adaptation of the paper's GNN aggregation hot spot (Eq. 1 / Eq. 10): on
GPU this is gather/scatter message passing; on TPU we reformulate it as a
*blocked dense matmul with fused degree normalization*,

    Y[i, f] = Σ_k  rs[i] · A[i, k] · cs[k] · X[k, f],

tiled to MXU-aligned (128, 128) VMEM blocks. The normalization scales are
fused into the A-tile load, so the normalized adjacency is never
materialized in HBM (saves one full N×N HBM round-trip vs the naive
`(rs*A*cs) @ X` formulation).

Grid = (N/bm, F/bf, N/bk); the k axis is the reduction — o_ref accumulates
across the innermost grid dimension (standard Pallas matmul pattern).

A second, *gather-based* kernel serves the sparse regime (HiCut layouts,
PubMed-scale edge lists): rows carry a padded neighbor list
``nbr_idx``/``nbr_val`` ([N, K], 0-padded, read as scalars from SMEM) and
the kernel walks each row's K slots, loading the matching (column-scaled) X
row with a dynamic row slice — O(N·K·F) instead of O(N²·F). The row/column
normalization stays fused: cs is folded into X by the op wrapper, rs is
applied on the accumulator before the store, so the normalized adjacency
is again never materialized.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _agg_kernel(a_ref, x_ref, rs_ref, cs_ref, o_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a = a_ref[...].astype(jnp.float32)
    a = a * rs_ref[...] * cs_ref[...]             # [bm, 1] and [1, bk] scales
    x = x_ref[...].astype(jnp.float32)
    o_ref[...] += jnp.dot(a, x, preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bf", "interpret"))
def gnn_aggregate_pallas(adj: jnp.ndarray, x: jnp.ndarray,
                         row_scale: jnp.ndarray, col_scale: jnp.ndarray,
                         bm: int = 128, bk: int = 128, bf: int = 128,
                         interpret: bool = False) -> jnp.ndarray:
    """Y = (diag(rs)·A·diag(cs)) @ X with (bm, bk, bf) VMEM tiles.

    Shapes must be multiples of the block sizes (ops.py pads)."""
    n, _ = adj.shape
    f = x.shape[1]
    assert n % bm == 0 and n % bk == 0 and f % bf == 0, (n, f, bm, bk, bf)
    grid = (n // bm, f // bf, n // bk)
    out = pl.pallas_call(
        functools.partial(_agg_kernel, n_k=n // bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bf), lambda i, j, k: (k, j)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, bk), lambda i, j, k: (0, k)),
        ],
        out_specs=pl.BlockSpec((bm, bf), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, f), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(adj, x, jnp.broadcast_to(row_scale, (n,)).astype(jnp.float32)[:, None],
      jnp.broadcast_to(col_scale, (n,)).astype(jnp.float32)[None, :])
    return out.astype(x.dtype)


def _gather_kernel(idx_ref, val_ref, xc_ref, rs_ref, o_ref, *, n_k: int):
    """One (bm, bf) output tile: for each row, walk its K neighbor slots
    (index and value read as scalars from SMEM) and accumulate the matching
    row of the column-scaled X slab, loaded with a dynamic row slice."""
    def row(r, carry):
        def slot(k, acc):
            j = idx_ref[r, k]
            return acc + val_ref[r, k] * xc_ref[pl.ds(j, 1), :]

        acc = jax.lax.fori_loop(0, n_k, slot,
                                jnp.zeros((1, o_ref.shape[1]), jnp.float32))
        o_ref[pl.ds(r, 1), :] = acc * rs_ref[pl.ds(r, 1), :]
        return carry

    jax.lax.fori_loop(0, o_ref.shape[0], row, 0)


@functools.partial(jax.jit, static_argnames=("bm", "bf", "interpret"))
def gnn_gather_aggregate_pallas(nbr_idx: jnp.ndarray, nbr_val: jnp.ndarray,
                                xc: jnp.ndarray, row_scale: jnp.ndarray,
                                bm: int = 128, bf: int = 128,
                                interpret: bool = False) -> jnp.ndarray:
    """Y[i] = rs[i] · Σ_k val[i,k] · XC[idx[i,k]] over padded neighbor rows.

    ``xc`` is X with the column scale already folded in (ops.py does the
    fold + padding). The whole [n_cols, bf] feature slab is resident per
    tile, so n_cols·bf·4 B must fit VMEM (the [bm, K] index/value blocks
    sit in SMEM) — fine for per-device extended blocks (L + P·B rows); at
    very large n_cols shrink ``bf``. Each slot is one scalar-indexed row
    load, which Mosaic compiles for the TPU (tests/test_tpu_compile.py)."""
    n, k = nbr_idx.shape
    n_cols, f = xc.shape
    assert n % bm == 0 and f % bf == 0, (n, f, bm, bf)
    grid = (n // bm, f // bf)
    out = pl.pallas_call(
        functools.partial(_gather_kernel, n_k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, k), lambda i, j: (i, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((bm, k), lambda i, j: (i, 0),
                         memory_space=pltpu.SMEM),
            # one buffer: two of a PubMed-size slab overflow scoped VMEM
            pl.BlockSpec((n_cols, bf), lambda i, j: (0, j),
                         pipeline_mode=pl.Buffered(1)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bf), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, f), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(nbr_idx.astype(jnp.int32), nbr_val.astype(jnp.float32), xc,
      jnp.broadcast_to(row_scale, (n,)).astype(jnp.float32)[:, None])
    return out
