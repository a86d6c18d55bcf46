"""Pallas kernels vs pure-jnp oracles (interpret mode, shape/dtype sweeps
+ hypothesis property sweeps)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.chunk_scan.ops import ssd_chunk_scan
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.gnn_aggregate.ops import normalized_aggregate

RNG = np.random.default_rng(0)


# --- gnn_aggregate ----------------------------------------------------------

@pytest.mark.parametrize("n,f,dtype", [
    (64, 32, np.float32), (128, 128, np.float32), (200, 70, np.float32),
    (5, 3, np.float32), (130, 257, np.float32), (64, 32, jnp.bfloat16),
])
def test_gnn_aggregate_matches_ref(n, f, dtype):
    adj = (RNG.random((n, n)) < 0.15).astype(np.float32)
    x = jnp.asarray(RNG.normal(size=(n, f)).astype(np.float32)).astype(dtype)
    rs = RNG.random(n).astype(np.float32)
    cs = RNG.random(n).astype(np.float32)
    ref = normalized_aggregate(jnp.asarray(adj), x, rs, cs, impl="xla")
    ker = normalized_aggregate(jnp.asarray(adj), x, rs, cs,
                               impl="interpret")
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    assert float(jnp.max(jnp.abs(ref.astype(jnp.float32)
                                 - ker.astype(jnp.float32)))) < tol * max(
        1.0, float(jnp.max(jnp.abs(ref.astype(jnp.float32)))))


@settings(max_examples=10, deadline=None)
@given(st.integers(2, 96), st.integers(1, 48), st.integers(0, 9999))
def test_gnn_aggregate_property(n, f, seed):
    rng = np.random.default_rng(seed)
    adj = (rng.random((n, n)) < 0.2).astype(np.float32)
    x = jnp.asarray(rng.normal(size=(n, f)).astype(np.float32))
    rs = rng.random(n).astype(np.float32)
    cs = rng.random(n).astype(np.float32)
    ref = normalized_aggregate(jnp.asarray(adj), x, rs, cs, impl="xla")
    ker = normalized_aggregate(jnp.asarray(adj), x, rs, cs,
                               impl="interpret")
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


# --- flash attention --------------------------------------------------------

@pytest.mark.parametrize("b,h,kv,s,dh,causal,win,cap", [
    (2, 4, 2, 256, 64, True, None, None),
    (1, 4, 4, 128, 32, True, None, 50.0),
    (2, 8, 2, 256, 64, True, 128, None),
    (1, 2, 1, 512, 128, False, None, None),
    (1, 4, 2, 256, 64, True, 64, 30.0),
    (1, 2, 2, 384, 64, True, None, None),     # non-pow2 seq (block 128)
])
def test_flash_attention_matches_ref(b, h, kv, s, dh, causal, win, cap):
    q = jnp.asarray(RNG.normal(size=(b, h, s, dh)).astype(np.float32))
    k = jnp.asarray(RNG.normal(size=(b, kv, s, dh)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(b, kv, s, dh)).astype(np.float32))
    ref = flash_attention(q, k, v, causal=causal, window=win, softcap=cap,
                          impl="xla")
    ker = flash_attention(q, k, v, causal=causal, window=win, softcap=cap,
                          impl="interpret")
    assert float(jnp.max(jnp.abs(ref - ker))) < 2e-5


def test_flash_attention_bf16():
    q = jnp.asarray(RNG.normal(size=(1, 2, 128, 64))).astype(jnp.bfloat16)
    k = jnp.asarray(RNG.normal(size=(1, 2, 128, 64))).astype(jnp.bfloat16)
    v = jnp.asarray(RNG.normal(size=(1, 2, 128, 64))).astype(jnp.bfloat16)
    ref = flash_attention(q, k, v, impl="xla").astype(jnp.float32)
    ker = flash_attention(q, k, v, impl="interpret").astype(jnp.float32)
    assert float(jnp.max(jnp.abs(ref - ker))) < 3e-2


# --- ssd chunk scan ---------------------------------------------------------

@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (2, 64, 4, 32, 16, 16), (1, 128, 2, 64, 32, 32),
    (2, 96, 3, 16, 8, 32), (1, 256, 8, 64, 64, 128),
])
def test_ssd_chunk_scan_matches_sequential(b, s, h, p, n, chunk):
    x = jnp.asarray(RNG.normal(size=(b, s, h, p)).astype(np.float32)) * 0.5
    bm = jnp.asarray(RNG.normal(size=(b, s, n)).astype(np.float32)) * 0.5
    cm = jnp.asarray(RNG.normal(size=(b, s, n)).astype(np.float32)) * 0.5
    la = -jnp.asarray(RNG.random((b, s, h)).astype(np.float32))
    ref = ssd_chunk_scan(x, bm, cm, la, impl="xla")
    ker = ssd_chunk_scan(x, bm, cm, la, impl="interpret", chunk=chunk)
    rel = float(jnp.max(jnp.abs(ref - ker)) /
                (jnp.max(jnp.abs(ref)) + 1e-9))
    assert rel < 1e-4, rel


@settings(max_examples=8, deadline=None)
@given(st.integers(1, 3), st.sampled_from([32, 64, 96]),
       st.integers(1, 4), st.integers(0, 9999))
def test_ssd_chunk_scan_property(b, s, h, seed):
    rng = np.random.default_rng(seed)
    p = n = 16
    x = jnp.asarray(rng.normal(size=(b, s, h, p)).astype(np.float32)) * 0.5
    bm = jnp.asarray(rng.normal(size=(b, s, n)).astype(np.float32)) * 0.5
    cm = jnp.asarray(rng.normal(size=(b, s, n)).astype(np.float32)) * 0.5
    la = -jnp.asarray(rng.random((b, s, h)).astype(np.float32)) * 2.0
    ref = ssd_chunk_scan(x, bm, cm, la, impl="xla")
    ker = ssd_chunk_scan(x, bm, cm, la, impl="interpret", chunk=32)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_ssd_decay_extremes():
    """Zero decay (a→0) forgets history; unit decay accumulates it."""
    b, s, h, p, n = 1, 8, 1, 4, 4
    x = jnp.ones((b, s, h, p))
    bm = jnp.ones((b, s, n))
    cm = jnp.ones((b, s, n))
    la_zero = jnp.full((b, s, h), -50.0)       # decay ≈ 0
    y = ssd_chunk_scan(x, bm, cm, la_zero, impl="interpret", chunk=4)
    np.testing.assert_allclose(np.asarray(y), n, rtol=1e-4)
    la_one = jnp.zeros((b, s, h))              # decay = 1: running sum
    y = ssd_chunk_scan(x, bm, cm, la_one, impl="interpret", chunk=4)
    expect = n * np.arange(1, s + 1, dtype=np.float32)
    np.testing.assert_allclose(np.asarray(y)[0, :, 0, 0], expect, rtol=1e-4)
