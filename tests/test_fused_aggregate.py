"""Fused gather–normalize–matmul kernel: parity, autotuner, VMEM guards,
aggregate auto-selection and the forward's retrace cache.

All Pallas execution here is interpret mode — the CPU venue for the TPU
kernels (DESIGN.md §4). The oracle throughout is the jnp scan reference
``gather_aggregate_ref`` composed with the layer matmul in float32.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from hypothesis import given, settings, strategies as st
from repro.data.graphs import random_graph
from repro.gnn.distributed import (DENSE_AUTO_SLOT_RATIO, _forward_blocks,
                                   distributed_gcn_forward, make_forward_fn,
                                   make_partition_plan_sparse,
                                   resolve_aggregate)
from repro.gnn.layers import gcn_apply, gcn_init
from repro.kernels.gnn_aggregate.autotune import (DEFAULT_VMEM_BUDGET,
                                                  KernelConfig,
                                                  autotune_config,
                                                  candidate_configs,
                                                  get_config,
                                                  heuristic_config,
                                                  load_table, save_table,
                                                  shape_key, vmem_bytes)
from repro.kernels.gnn_aggregate.ops import (SPARSE_DENSITY_THRESHOLD,
                                             fused_gather_aggregate,
                                             gather_aggregate,
                                             gather_block_columns,
                                             sort_neighbor_slots)
from repro.kernels.gnn_aggregate.ref import gather_aggregate_ref


def _random_neighbors(rng, n_rows, n_cols, k, hub_frac=0.0):
    """Padded neighbor lists with random per-row degree in [0, k]; with
    ``hub_frac`` > 0 that fraction of slots collapses onto a few hub
    columns (degree-skewed gather traffic)."""
    deg = rng.integers(0, k + 1, size=n_rows)
    idx = np.zeros((n_rows, k), np.int32)
    val = np.zeros((n_rows, k), np.float32)
    for i, d in enumerate(deg):
        if d == 0:
            continue
        cols = rng.integers(0, n_cols, size=d)
        if hub_frac:
            hubs = rng.integers(0, max(1, n_cols // 8), size=d)
            cols = np.where(rng.random(d) < hub_frac, hubs, cols)
        idx[i, :d] = cols
        val[i, :d] = rng.normal(size=d).astype(np.float32)
    return idx, val


def _oracle(idx, val, x, rs, cs, w):
    y = gather_aggregate_ref(idx, val, jnp.asarray(x, jnp.float32), rs, cs)
    return np.asarray(y @ jnp.asarray(w, jnp.float32))


# -- kernel parity ----------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(st.integers(1, 48), st.integers(1, 10),
       st.sampled_from([3, 8, 17]), st.sampled_from([2, 5, 16]),
       st.sampled_from([(8, 8, 2), (16, 8, 4), (8, 16, 1), None]),
       st.integers(0, 1 << 20))
def test_fused_parity_random(n, k, f_in, f_out, cfg, seed):
    """Interpret-mode fused kernel matches the scan-reference + matmul
    oracle across random shapes, degrees and block configs — including
    rows/slots/features that don't divide the blocking (ops.py pads)."""
    rng = np.random.default_rng(seed)
    idx, val = _random_neighbors(rng, n, n, k)
    idx, val = sort_neighbor_slots(idx, val)
    x = rng.normal(size=(n, f_in)).astype(np.float32)
    w = rng.normal(size=(f_in, f_out)).astype(np.float32)
    rs = rng.random(n).astype(np.float32)
    cs = rng.random(n).astype(np.float32)
    got = fused_gather_aggregate(
        idx, val, jnp.asarray(x), rs, cs, w, impl="interpret",
        config=KernelConfig(*cfg) if cfg else None)
    np.testing.assert_allclose(np.asarray(got),
                               _oracle(idx, val, x, rs, cs, w),
                               rtol=1e-5, atol=1e-5)


def test_fused_parity_degree_skew(rng):
    """Hub-heavy slot traffic (most gathers hit a few columns) is just a
    worst case for the prefetch layout, never for correctness."""
    n, k = 64, 16
    idx, val = _random_neighbors(rng, n, n, k, hub_frac=0.9)
    val *= 10.0                                   # heavy hub magnitudes
    idx, val = sort_neighbor_slots(idx, val)
    x = rng.normal(size=(n, 24)).astype(np.float32)
    w = rng.normal(size=(24, 8)).astype(np.float32)
    rs = rng.random(n).astype(np.float32)
    got = fused_gather_aggregate(idx, val, jnp.asarray(x), rs, rs, w,
                                 impl="interpret")
    np.testing.assert_allclose(np.asarray(got),
                               _oracle(idx, val, x, rs, rs, w),
                               rtol=1e-5, atol=1e-5)


def test_fused_parity_nondivisible_shapes():
    """n=13, F_in=5, F_out=3, K=3 under an (8, 8, 2) blocking: every axis
    needs padding, and the pad rows/slots/columns must stay inert."""
    rng = np.random.default_rng(3)
    idx, val = _random_neighbors(rng, 13, 13, 3)
    idx, val = sort_neighbor_slots(idx, val)
    x = rng.normal(size=(13, 5)).astype(np.float32)
    w = rng.normal(size=(5, 3)).astype(np.float32)
    rs = rng.random(13).astype(np.float32)
    got = fused_gather_aggregate(idx, val, jnp.asarray(x), rs, rs, w,
                                 impl="interpret", config=KernelConfig(8, 8, 2))
    assert got.shape == (13, 3)
    np.testing.assert_allclose(np.asarray(got),
                               _oracle(idx, val, x, rs, rs, w),
                               rtol=1e-5, atol=1e-5)


def test_fused_pad_slots_inert(rng):
    """val=0 slots are numerically inert no matter which (valid) index
    they carry — the padded-CSR contract the kernel relies on."""
    n, k = 24, 6
    idx, val = _random_neighbors(rng, n, n, k)
    x = rng.normal(size=(n, 8)).astype(np.float32)
    w = rng.normal(size=(8, 8)).astype(np.float32)
    rs = np.ones(n, np.float32)
    scrambled = np.where(val == 0, rng.integers(0, n, size=idx.shape),
                         idx).astype(np.int32)
    a = fused_gather_aggregate(*sort_neighbor_slots(idx, val),
                               jnp.asarray(x), rs, rs, w, impl="interpret")
    b = fused_gather_aggregate(*sort_neighbor_slots(scrambled, val),
                               jnp.asarray(x), rs, rs, w, impl="interpret")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                               atol=1e-6)


def test_fused_inactive_rows_exact_zero(rng):
    """row_scale = 0 rows (inactive vertices) come out exactly zero — the
    scale is applied inside the kernel before the matmul."""
    n = 20
    idx, val = _random_neighbors(rng, n, n, 4)
    x = rng.normal(size=(n, 8)).astype(np.float32)
    w = rng.normal(size=(8, 8)).astype(np.float32)
    rs = (np.arange(n) % 2).astype(np.float32)    # half the rows inactive
    got = np.asarray(fused_gather_aggregate(
        *sort_neighbor_slots(idx, val), jnp.asarray(x), rs, np.ones(n,
        np.float32), w, impl="interpret"))
    assert np.all(got[rs == 0] == 0.0)
    assert np.any(got[rs == 1] != 0.0)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_fused_dtype_grid(rng, dtype, tol):
    """The kernel computes in f32 and rounds to the input dtype at the end
    — exactly like the impl="xla" twin, so the two agree to ~1 ulp of the
    storage dtype (bf16's is coarse; the oracle there is the twin, not
    the f32 reference)."""
    n = 32
    idx, val = _random_neighbors(rng, n, n, 6)
    idx, val = sort_neighbor_slots(idx, val)
    x = jnp.asarray(rng.normal(size=(n, 16)), dtype)
    w = jnp.asarray(rng.normal(size=(16, 8)), dtype)
    rs = rng.random(n).astype(np.float32)
    got = fused_gather_aggregate(idx, val, x, rs, rs, w, impl="interpret")
    want = fused_gather_aggregate(idx, val, x, rs, rs, w, impl="xla")
    assert got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_fused_matches_unfused_kernel_composition(rng):
    """Fused kernel == existing gather kernel followed by the matmul (both
    interpret mode) — the exact pair the fusion replaces."""
    n = 40
    idx, val = _random_neighbors(rng, n, n, 7)
    idx, val = sort_neighbor_slots(idx, val)
    x = jnp.asarray(rng.normal(size=(n, 12)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(12, 6)).astype(np.float32))
    rs = rng.random(n).astype(np.float32)
    fused = fused_gather_aggregate(idx, val, x, rs, rs, w, impl="interpret")
    unfused = gather_aggregate(idx, val, x, rs, rs, impl="interpret") @ w
    np.testing.assert_allclose(np.asarray(fused), np.asarray(unfused),
                               rtol=1e-5, atol=1e-5)


def test_fused_is_jit_compatible(rng):
    """The op resolves its config at trace time, so value-only re-calls
    hit the same executable (benches rely on this)."""
    n = 16
    idx, val = _random_neighbors(rng, n, n, 3)
    idx, val = sort_neighbor_slots(idx, val)
    w = rng.normal(size=(8, 8)).astype(np.float32)
    rs = np.ones(n, np.float32)
    fn = jax.jit(lambda xx: fused_gather_aggregate(
        idx, val, xx, rs, rs, w, impl="interpret"))
    for seed in (0, 1):
        x = np.random.default_rng(seed).normal(size=(n, 8)).astype(
            np.float32)
        np.testing.assert_allclose(np.asarray(fn(jnp.asarray(x))),
                                   _oracle(idx, val, x, rs, rs, w),
                                   rtol=1e-5, atol=1e-5)


def test_sort_neighbor_slots_permutation_only(rng):
    """Slot sorting is a pure per-row permutation (pads last, destinations
    ascending) — the aggregate is unchanged."""
    idx, val = _random_neighbors(rng, 10, 10, 5)
    sidx, sval = sort_neighbor_slots(idx, val)
    for i in range(10):
        d = int((val[i] != 0).sum())
        assert np.all(sval[i, d:] == 0)                     # pads last
        assert np.all(np.diff(sidx[i, :d]) >= 0)            # sorted dsts
        assert sorted(zip(idx[i][val[i] != 0], val[i][val[i] != 0])) == \
            sorted(zip(sidx[i, :d], sval[i, :d]))


# -- autotuner --------------------------------------------------------------

def test_heuristic_config_deterministic_and_budgeted():
    for shape in [(1000, 1000, 64, 64, 35), (5000, 5000, 64, 64, 40),
                  (100, 100, 3, 5, 2), (200_000, 200_000, 64, 64, 48)]:
        a = heuristic_config(*shape)
        assert a == heuristic_config(*shape)
        assert vmem_bytes(a, shape[1], shape[4]) <= DEFAULT_VMEM_BUDGET

def test_heuristic_bf_rounds_to_sublane_not_lane():
    """f=64 features keep a 64-wide tile — rounding to the 128 lane would
    double the gather traffic on every slot (the regression that capped
    the fused speedup at ~1x before the fix)."""
    assert heuristic_config(5000, 5000, 64, 64, 40).bf == 64
    assert heuristic_config(100, 100, 100, 100, 4).bf == 104
    assert heuristic_config(100, 100, 200, 200, 4).bf == 128


def test_candidate_configs_all_fit_budget():
    cands = candidate_configs(5000, 5000, 64, 64, 40)
    assert len(cands) >= 3
    assert len(set(cands)) == len(cands)
    for c in cands:
        assert vmem_bytes(c, 5000, 40) <= DEFAULT_VMEM_BUDGET


def test_get_config_table_hit_and_overbudget_fallback(tmp_path):
    tbl = tmp_path / "tuning.json"
    key = shape_key(64, 64, 8, 8, 4)
    good = KernelConfig(16, 8, 2)
    save_table({key: good}, tbl)
    assert get_config(64, 64, 8, 8, 4, table_path=tbl) == good
    # an entry that no longer fits the budget is ignored, not honored
    save_table({key: KernelConfig(1 << 16, 128, 64)}, tbl)
    assert get_config(64, 64, 8, 8, 4, table_path=tbl) == \
        heuristic_config(64, 64, 8, 8, 4)
    # missing shape key → heuristic
    assert get_config(32, 32, 8, 8, 4, table_path=tbl) == \
        heuristic_config(32, 32, 8, 8, 4)


def test_autotune_deterministic_and_persists(tmp_path):
    tbl = tmp_path / "tuning.json"
    measure = lambda cfg: 1000.0 / cfg.bm + cfg.kc    # pure fn of config
    best1, t1 = autotune_config(64, 64, 8, 8, 4, measure, persist=True,
                                table_path=tbl)
    best2, t2 = autotune_config(64, 64, 8, 8, 4, measure)
    assert best1 == best2 and t1 == t2                # deterministic
    assert load_table(tbl)[shape_key(64, 64, 8, 8, 4)] == best1
    # the persisted winner is what get_config now serves
    assert get_config(64, 64, 8, 8, 4, table_path=tbl) == best1
    # ties break toward candidate order (itself deterministic)
    flat, _ = autotune_config(64, 64, 8, 8, 4, lambda cfg: 7.0)
    assert flat == candidate_configs(64, 64, 8, 8, 4)[0]


def test_autotune_table_env_override(tmp_path, monkeypatch):
    tbl = tmp_path / "env_table.json"
    key = shape_key(48, 48, 8, 8, 3)
    save_table({key: KernelConfig(8, 8, 1)}, tbl)
    monkeypatch.setenv("REPRO_GNN_AGG_TUNING", str(tbl))
    assert get_config(48, 48, 8, 8, 3) == KernelConfig(8, 8, 1)


def test_checked_in_table_entries_fit_model():
    """The committed tuning table parses and every entry passes the VMEM
    model for its own shape key (nC/K parsed back from the key)."""
    import repro.kernels.gnn_aggregate.autotune as at
    table = load_table(at._DEFAULT_TABLE)
    assert table, "checked-in tuning table is empty"
    for key, cfg in table.items():
        n_cols = int(key.split("_")[1][1:])
        k = int(key.split("_k")[1])
        assert vmem_bytes(cfg, n_cols, k) <= DEFAULT_VMEM_BUDGET, key


# -- VMEM guards ------------------------------------------------------------

def test_gather_vmem_guard_shrinks_and_matches(rng):
    """An oversized [n_cols, bf] slab shrinks bf instead of (silently)
    blowing the budget — and the shrunken blocking still matches the
    reference."""
    n, k = 64, 4
    budget = 80_000
    assert gather_block_columns(n, k, vmem_budget=budget) < 128
    idx, val = _random_neighbors(rng, n, n, k)
    x = jnp.asarray(rng.normal(size=(n, 40)).astype(np.float32))
    rs = rng.random(n).astype(np.float32)
    got = gather_aggregate(idx, val, x, rs, rs, impl="interpret",
                           vmem_budget=budget)
    want = gather_aggregate_ref(idx, val, x, rs, rs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_gather_vmem_guard_raises_clearly():
    with pytest.raises(ValueError, match="VMEM budget"):
        gather_block_columns(1 << 20, 256, vmem_budget=100_000)


def test_fused_rejects_overbudget_config(rng):
    idx, val = _random_neighbors(rng, 16, 16, 3)
    x = jnp.asarray(rng.normal(size=(16, 8)).astype(np.float32))
    w = rng.normal(size=(8, 8)).astype(np.float32)
    rs = np.ones(16, np.float32)
    with pytest.raises(ValueError, match="VMEM budget"):
        fused_gather_aggregate(idx, val, x, rs, rs, w, impl="interpret",
                               config=KernelConfig(1 << 16, 128, 64),
                               vmem_budget=100_000)


# -- aggregate auto-selection (the n=1000 regression) -----------------------

def test_auto_selection_regression_bench_shapes():
    """The auto rule consults per-row *work* (ext_cols vs slot count), not
    density: the BENCH n=1000 plan is sparse by density (0.02 < threshold
    0.05) yet its compact 1000-wide extended block keeps dense faster —
    the old density-only rule picked the 0.85x gather path here."""
    g1 = random_graph(1000, 10_000, seed=1)
    plan1 = make_partition_plan_sparse(
        g1.edges, np.zeros(1000, np.int64), 1, n=1000)
    density = 2 * g1.num_edges / 1000**2
    assert density < SPARSE_DENSITY_THRESHOLD            # misprediction bait
    assert plan1.ext_cols < DENSE_AUTO_SLOT_RATIO * (plan1.max_degree + 1)
    assert resolve_aggregate(plan1) == "dense"

    g5 = random_graph(5000, 50_000, seed=1)
    plan5 = make_partition_plan_sparse(
        g5.edges, np.arange(5000) % 4, 4, n=5000)
    assert resolve_aggregate(plan5) == "fused"

    for explicit in ("dense", "sparse", "fused"):         # pass-through
        assert resolve_aggregate(plan1, explicit) == explicit
    with pytest.raises(ValueError, match="unknown aggregate"):
        resolve_aggregate(plan1, "csr")


# -- distributed forward: fused path parity + retrace cache -----------------

def _small_plan(rng, n=48, e=140, devices=1):
    from conftest import random_edges
    edges = random_edges(rng, n, e)
    assign = np.arange(n) % devices
    plan = make_partition_plan_sparse(edges, assign, devices, n=n)
    adj = np.zeros((n, n), np.float32)
    adj[edges[:, 0], edges[:, 1]] = 1.0
    adj[edges[:, 1], edges[:, 0]] = 1.0
    return plan, adj


@pytest.mark.parametrize("aggregate", ["dense", "sparse", "fused"])
def test_distributed_forward_backends_match_oracle(rng, aggregate):
    """Every per-device contraction — including the fused kernel path —
    reproduces the single-device gcn_apply oracle on one device."""
    plan, adj = _small_plan(rng)
    n = adj.shape[0]
    params = gcn_init(jax.random.PRNGKey(0), [8, 6, 4])
    x = rng.normal(size=(n, 8)).astype(np.float32)
    mesh = Mesh(np.array(jax.devices()[:1]), ("servers",))
    out = distributed_gcn_forward(mesh, "servers", plan, params, x,
                                  aggregate=aggregate)
    oracle = np.asarray(gcn_apply(params, jnp.asarray(x),
                                  jnp.asarray(adj), jnp.ones(n)))
    np.testing.assert_allclose(out, oracle, rtol=2e-4, atol=2e-4)


def test_forward_cache_retrace_once_per_shape(rng):
    """make_forward_fn's jitted core retraces exactly once per new shape
    and not at all on value-only changes (satellite: compile-cache)."""
    plan, _ = _small_plan(rng)
    n = plan.n
    mesh = Mesh(np.array(jax.devices()[:1]), ("servers",))
    fwd = make_forward_fn(mesh, "servers", plan, aggregate="fused")

    p6 = gcn_init(jax.random.PRNGKey(0), [6, 5, 4])
    x6 = plan.scatter(rng.normal(size=(n, 6)).astype(np.float32))
    c0 = _forward_blocks._cache_size()
    fwd(x6, p6)
    c1 = _forward_blocks._cache_size()
    assert c1 == c0 + 1                                   # first shape

    # value-only changes: new x values, new param values — no retrace
    p6b = gcn_init(jax.random.PRNGKey(7), [6, 5, 4])
    fwd(plan.scatter(rng.normal(size=(n, 6)).astype(np.float32)), p6b)
    fwd(x6, p6b)
    assert _forward_blocks._cache_size() == c1

    # a new feature width is a new shape: exactly one more trace
    p7 = gcn_init(jax.random.PRNGKey(1), [7, 5, 4])
    x7 = plan.scatter(rng.normal(size=(n, 7)).astype(np.float32))
    fwd(x7, p7)
    assert _forward_blocks._cache_size() == c1 + 1
    fwd(x7, p7)                                           # and it sticks
    assert _forward_blocks._cache_size() == c1 + 1
