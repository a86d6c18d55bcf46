"""Pallas TPU flash attention (causal, sliding-window, logit softcap, GQA).

Online-softmax over KV blocks: grid = (B, H, S/bq, S/bk) with the KV-block
axis innermost and ``arbitrary`` semantics; VMEM scratch carries the running
(max m, denominator l, accumulator acc) per query block across KV steps.
Block shapes default to (128, 128) — MXU-aligned, and the (bq·dh + bk·dh +
bq·bk) working set stays far under the ~16 MB v5e VMEM budget for dh ≤ 256.

Sliding-window and causal predicates are applied per-element inside the
block; fully-masked KV blocks are skipped with ``pl.when`` (no FLOPs, no
VMEM traffic beyond the prefetch).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  bq: int, bk: int, nk: int, scale: float, causal: bool,
                  window: int | None, softcap: float | None):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * bq
    k_start = ik * bk
    # skip KV blocks entirely above the causal diagonal / outside the window
    relevant = True
    if causal:
        relevant = k_start <= q_start + bq - 1
    if window is not None:
        relevant = jnp.logical_and(
            relevant, q_start - (k_start + bk - 1) < window)

    @pl.when(relevant)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # [bq, dh]
        k = k_ref[0, 0].astype(jnp.float32)          # [bk, dh]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), bool)
        if causal:
            mask &= qpos >= kpos
        if window is not None:
            mask &= qpos - kpos < window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "bq", "bk", "interpret"))
def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           window: int | None = None,
                           softcap: float | None = None,
                           bq: int = 128, bk: int = 128,
                           interpret: bool = False):
    """q [B,H,S,dh], k/v [B,KV,S,dh] → [B,H,S,dh]. S divisible by bq/bk."""
    b, h, s, dh = q.shape
    kvh = k.shape[1]
    g = h // kvh
    assert s % bq == 0 and s % bk == 0, (s, bq, bk)
    nq, nk = s // bq, s // bk
    grid = (b, h, nq, nk)
    kernel = functools.partial(
        _flash_kernel, bq=bq, bk=bk, nk=nk, scale=dh ** -0.5, causal=causal,
        window=window, softcap=softcap)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, dh), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, bk, dh),
                         lambda b_, h_, iq, ik: (b_, h_ // g, ik, 0)),
            pl.BlockSpec((1, 1, bk, dh),
                         lambda b_, h_, iq, ik: (b_, h_ // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dh),
                               lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
