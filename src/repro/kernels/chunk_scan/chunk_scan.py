"""Pallas TPU kernel: fused Mamba2 SSD chunk scan.

One grid step processes one (batch, head, chunk) tile: the intra-chunk
quadratic part runs as dense [L,L] matmuls on the MXU, and the inter-chunk
[P,N] state lives in VMEM scratch and is carried across the (innermost,
``arbitrary``) chunk axis — the HBM round-trip for the state that a
chunk-by-chunk XLA scan would pay is eliminated, which is the point of
fusing (state is P·N floats per (b,h), re-read every chunk otherwise).

Inputs are pre-scaled x (Δ·x), shared B/C (single SSD group), and per-step
log-decay (≤ 0, so every exp here is ≤ 1 — numerically safe).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, b_ref, c_ref, la_ref, y_ref, state_ref, *, l: int):
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, :, 0, :].astype(jnp.float32)        # [L, P]
    bm = b_ref[0].astype(jnp.float32)                # [L, N]
    cm = c_ref[0].astype(jnp.float32)                # [L, N]
    la = la_ref[0, :, 0].astype(jnp.float32)         # [L]
    ca = jnp.cumsum(la)                              # [L]

    # intra-chunk: y_i = Σ_{j≤i} exp(ca_i − ca_j)·(C_i·B_j)·x_j
    g = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [L, L]
    dec = jnp.exp(ca[:, None] - ca[None, :])
    ii = jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (l, l), 1)
    w = jnp.where(ii >= jj, g * dec, 0.0)
    y = jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [L, P]

    # inter-chunk: y_i += exp(ca_i) · C_i · Sᵀ  (S = state at chunk start)
    state = state_ref[...]                           # [P, N]
    y = y + jnp.exp(ca)[:, None] * jax.lax.dot_general(
        cm, state, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    # state update: S ← exp(ca_L)·S + Σ_j exp(ca_L − ca_j)·x_j ⊗ B_j
    dec_end = jnp.exp(ca[-1] - ca)                   # [L]
    inc = jax.lax.dot_general(x, bm * dec_end[:, None],
                              (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # [P, N]
    state_ref[...] = state * jnp.exp(ca[-1]) + inc
    y_ref[0, :, 0, :] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_chunk_scan_pallas(x, bmat, cmat, loga, *, chunk: int = 128,
                          interpret: bool = False):
    """x [B,S,H,P], b/c [B,S,N], loga [B,S,H] ≤ 0 → y [B,S,H,P]."""
    b, s, h, p = x.shape
    n = bmat.shape[-1]
    l = min(chunk, s)
    while s % l:
        l //= 2
    nc = s // l
    grid = (b, h, nc)
    return pl.pallas_call(
        functools.partial(_ssd_kernel, l=l),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, l, 1, p), lambda b_, h_, c_: (b_, c_, h_, 0)),
            pl.BlockSpec((1, l, n), lambda b_, h_, c_: (b_, c_, 0)),
            pl.BlockSpec((1, l, n), lambda b_, h_, c_: (b_, c_, 0)),
            pl.BlockSpec((1, l, 1), lambda b_, h_, c_: (b_, c_, h_)),
        ],
        out_specs=pl.BlockSpec((1, l, 1, p),
                               lambda b_, h_, c_: (b_, c_, h_, 0)),
        out_shape=jax.ShapeDtypeStruct((b, s, h, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, bmat, cmat, loga)
