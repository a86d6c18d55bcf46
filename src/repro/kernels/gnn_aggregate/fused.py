"""Pallas TPU kernel: fused gather–normalize–matmul aggregation.

One kernel computes a whole GCN layer's hot path over the padded
neighbor-list layout (see ops.py),

    Y = rs · (Σ_k val[:, k] · XC[idx[:, k]]) @ W,

so the gathered neighborhood feeds the MXU directly instead of being
materialized as an [N, F_in] slab in HBM between a gather kernel and a
matmul (the unfused path does exactly that round-trip). The row scale is
applied on the accumulator before the matmul — linearity lets every
normalization commute through the contraction.

Layout: a *blocked two-pass* schedule:

* pass 1 (host, ops.py): neighbor slots are sorted by destination index
  with pads last (:func:`~repro.kernels.gnn_aggregate.ops.sort_neighbor_slots`),
  so each row's loads walk the resident XC slab in ascending order;
* pass 2 (kernel): each ``(bm, bf)`` tile walks every row's slots ``kc``
  at a time — scalar index and value from SMEM, one dynamic row load of
  the slab each — into a VMEM accumulator, which then feeds the MXU.

Grid = (N/bm, F_out/bf, F_in/bf); the F_in axis is the matmul reduction —
o_ref accumulates across the innermost grid dimension (standard Pallas
matmul pattern), with the ``[n_cols, bf]`` XC slab slice and the
``[bf, bf]`` weight block swapped per step. Block sizes come from
``autotune.get_config`` (persisted tuning table + closed-form heuristic);
``autotune.vmem_bytes`` is the resident-footprint model the configs are
validated against.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _fused_kernel(idx_ref, val_ref, xc_ref, rs_ref, w_ref, o_ref, acc_ref,
                  *, n_k: int, kc: int):
    """One (bm, bf) output tile for one F_in chunk: per row, walk the
    neighbor slots ``kc`` at a time (scalar index/value from SMEM, one
    dynamic row load each) into the tile-local accumulator, apply the row
    scale, then accumulate the weight-block matmul into the output tile."""
    l = pl.program_id(2)

    @pl.when(l == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    def row(r, carry):
        def chunk(c, acc):
            for t in range(kc):                     # static: n_k % kc == 0
                s = c * kc + t
                acc = acc + val_ref[r, s] * xc_ref[pl.ds(idx_ref[r, s], 1), :]
            return acc

        acc = jax.lax.fori_loop(0, n_k // kc, chunk,
                                jnp.zeros((1, acc_ref.shape[1]), jnp.float32))
        acc_ref[pl.ds(r, 1), :] = acc * rs_ref[pl.ds(r, 1), :]
        return carry

    jax.lax.fori_loop(0, acc_ref.shape[0], row, 0)
    o_ref[...] += jnp.dot(acc_ref[...], w_ref[...].astype(jnp.float32),
                          preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bm", "bf", "kc", "interpret"))
def gnn_fused_aggregate_pallas(nbr_idx: jnp.ndarray, nbr_val: jnp.ndarray,
                               xc: jnp.ndarray, row_scale: jnp.ndarray,
                               w: jnp.ndarray, bm: int = 256, bf: int = 128,
                               kc: int = 8,
                               interpret: bool = False) -> jnp.ndarray:
    """Y = (rs · Σ_k val·XC[idx]) @ W over padded neighbor rows, fused.

    ``xc`` is X with the column scale folded in (ops.py does the fold +
    padding); ``w`` is the layer weight [F_in, F_out]. Row count must be a
    multiple of ``bm``, the slot count of ``kc``, both feature widths of
    ``bf`` (ops.py pads). The [n_cols, bf] slab slice stays VMEM-resident
    per tile — configs are budget-checked via ``autotune.vmem_bytes``."""
    n, k = nbr_idx.shape
    n_cols, f_in = xc.shape
    f_out = w.shape[1]
    assert n % bm == 0 and k % kc == 0, (n, k, bm, kc)
    assert f_in % bf == 0 and f_out % bf == 0, (f_in, f_out, bf)
    assert w.shape[0] == f_in, (w.shape, f_in)
    grid = (n // bm, f_out // bf, f_in // bf)
    out = pl.pallas_call(
        functools.partial(_fused_kernel, n_k=k, kc=kc),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, k), lambda i, j, l: (i, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((bm, k), lambda i, j, l: (i, 0),
                         memory_space=pltpu.SMEM),
            # one buffer: two of a PubMed-size slab overflow scoped VMEM
            pl.BlockSpec((n_cols, bf), lambda i, j, l: (0, l),
                         pipeline_mode=pl.Buffered(1)),
            pl.BlockSpec((bm, 1), lambda i, j, l: (i, 0)),
            pl.BlockSpec((bf, bf), lambda i, j, l: (l, j)),
        ],
        out_specs=pl.BlockSpec((bm, bf), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, f_out), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bf), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(nbr_idx.astype(jnp.int32), nbr_val.astype(jnp.float32), xc,
      jnp.broadcast_to(row_scale, (n,)).astype(jnp.float32)[:, None],
      w.astype(jnp.float32))
    return out
