"""Sparse edge-list fast path: plan-builder parity vs the dense oracle,
gather-aggregate parity vs the dense kernel, layer auto-dispatch, the
edge-list partition-cache key, and a 5k-vertex serve round-trip."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.api import topology_key
from repro.core.dynamic_graph import make_graph_state
from repro.gnn.distributed import (make_partition_plan,
                                   make_partition_plan_dense_reference,
                                   make_partition_plan_sparse)
from repro.kernels.gnn_aggregate.ops import (dense_to_padded_neighbors,
                                             gather_aggregate,
                                             normalized_aggregate,
                                             padded_neighbors_from_coo)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _random_layout(seed: int, n: int, p: int):
    """Random symmetric 0/1 adjacency + assignment with inactive slots."""
    rng = np.random.default_rng(seed)
    adj = (rng.random((n, n)) < rng.uniform(0.02, 0.3)).astype(np.float32)
    adj = np.maximum(adj, adj.T)
    np.fill_diagonal(adj, 0)
    assign = rng.integers(0, p, n).astype(np.int64)
    assign[rng.random(n) < 0.2] = -1
    adj *= (assign >= 0)[:, None] * (assign >= 0)[None, :]
    return adj, assign


# --- plan parity ------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(st.integers(4, 80), st.integers(2, 6), st.integers(0, 99999))
def test_sparse_plan_matches_dense_oracle(n, p, seed):
    """make_partition_plan_sparse == the original triple-loop builder on
    every field: perm, halo layout, send schedule, adjacency semantics."""
    adj, assign = _random_layout(seed, n, p)
    ref = make_partition_plan_dense_reference(adj, assign, p)
    i, j = np.nonzero(np.triu(adj, 1))
    sp = make_partition_plan_sparse(np.stack([i, j], 1), assign, p, n=n)
    wrapped = make_partition_plan(adj, assign, p)
    for plan in (sp, wrapped):
        assert (plan.block, plan.halo, plan.n) == (ref.block, ref.halo, n)
        np.testing.assert_array_equal(plan.perm, ref.perm)
        np.testing.assert_array_equal(plan.send_idx, ref.send_idx)
        np.testing.assert_array_equal(plan.send_mask, ref.send_mask)
        np.testing.assert_array_equal(plan.mask, ref.mask)
        np.testing.assert_allclose(plan.dense_adj_ext(), ref.adj_ext)


def test_sparse_plan_weighted_edges(rng):
    """Edge weights flow into adj_ext exactly as dense matrix entries do."""
    n, p = 30, 3
    adj = np.triu((rng.random((n, n)) < 0.2) * rng.integers(1, 9, (n, n)),
                  1).astype(np.float32)
    adj = adj + adj.T
    assign = rng.integers(0, p, n).astype(np.int64)
    ref = make_partition_plan_dense_reference(adj, assign, p)
    i, j = np.nonzero(np.triu(adj, 1))
    sp = make_partition_plan_sparse(np.stack([i, j], 1), assign, p, n=n,
                                    weights=adj[i, j])
    np.testing.assert_allclose(sp.dense_adj_ext(), ref.adj_ext)


def test_gather_handles_inactive_max_vertex(rng):
    """Satellite fix: scatter→gather round-trips to the stored n even when
    the highest-id vertices are inactive (perm.max()+1 would be wrong)."""
    n, p = 12, 2
    assign = np.array([0, 1, 0, 1, 0, 1, 0, 1, -1, -1, -1, -1], np.int64)
    edges = np.array([[0, 2], [1, 3], [4, 6], [0, 1]], np.int64)
    plan = make_partition_plan_sparse(edges, assign, p, n=n)
    assert plan.n == n
    x = rng.normal(size=(n, 5)).astype(np.float32)
    out = plan.gather(plan.scatter(x))
    assert out.shape == (n, 5)
    active = assign >= 0
    np.testing.assert_array_equal(out[active], x[active])
    assert np.all(out[~active] == 0)


# --- sparse aggregate parity ------------------------------------------------

@settings(max_examples=12, deadline=None)
@given(st.integers(2, 120), st.integers(1, 70), st.integers(0, 9999))
def test_gather_aggregate_matches_dense_oracle(n, f, seed):
    rng = np.random.default_rng(seed)
    adj = ((rng.random((n, n)) < 0.15) * rng.random((n, n))).astype(
        np.float32)
    x = jnp.asarray(rng.normal(size=(n, f)).astype(np.float32))
    rs = rng.random(n).astype(np.float32)
    cs = rng.random(n).astype(np.float32)
    ref = normalized_aggregate(jnp.asarray(adj), x, rs, cs, impl="xla")
    idx, val = dense_to_padded_neighbors(adj)
    for impl in ("xla", "interpret"):
        got = gather_aggregate(idx, val, x, rs, cs, impl=impl)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)


def test_padded_neighbors_roundtrip(rng):
    """COO → padded lists → dense reconstruction is exact (duplicates sum)."""
    n = 17
    src = rng.integers(0, n, 40)
    dst = rng.integers(0, n, 40)
    val = rng.random(40).astype(np.float32)
    idx, nv = padded_neighbors_from_coo(src, dst, val, n)
    dense = np.zeros((n, n), np.float32)
    np.add.at(dense, (src, dst), val)
    recon = np.zeros((n, n), np.float32)
    rows = np.repeat(np.arange(n), idx.shape[1])
    np.add.at(recon, (rows, idx.ravel()), nv.ravel())
    np.testing.assert_allclose(recon, dense, rtol=1e-6, atol=1e-6)


def test_layers_auto_sparse_matches_closed_form():
    """gcn_apply takes the gather path at ≥256 vertices / low density and
    still equals the closed-form dense propagation."""
    from repro.gnn.layers import (gcn_apply, gcn_init, gcn_norm,
                                  maybe_padded_neighbors)
    rng = np.random.default_rng(3)
    n = 300
    adj = (rng.random((n, n)) < 0.01).astype(np.float32)
    adj = np.maximum(adj, adj.T)
    np.fill_diagonal(adj, 0)
    x = jnp.asarray(rng.normal(size=(n, 16)).astype(np.float32))
    mask = jnp.ones(n)
    a_hat, dinv = gcn_norm(jnp.asarray(adj), mask)
    assert maybe_padded_neighbors(a_hat) is not None
    params = gcn_init(jax.random.PRNGKey(0), [16, 8, 4])
    out = gcn_apply(params, x, jnp.asarray(adj), mask)
    a_norm = dinv[:, None] * a_hat * dinv[None, :]
    expect = a_norm @ jax.nn.relu(a_norm @ x @ params[0]["w"]) @ \
        params[1]["w"]
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-4, atol=1e-5)


# --- control plane ----------------------------------------------------------

def test_topology_key_ignores_positions(rng):
    """The partition cache key hashes (capacity, mask, edge list): mobility
    leaves it unchanged, topology edits do not."""
    edges = [[0, 1], [1, 2], [2, 3]]
    pos = rng.random((5, 2)) * 100
    a = make_graph_state(8, pos, edges, np.ones(5))
    b = make_graph_state(8, rng.random((5, 2)) * 100, edges, np.ones(5))
    c = make_graph_state(8, pos, [[0, 1], [1, 2], [3, 4]], np.ones(5))
    assert topology_key(a) == topology_key(b)
    assert topology_key(a) != topology_key(c)


def test_decision_plan_is_sparse_built(rng):
    """Decision.to_partition_plan goes through the O(E) path (no dense
    blocks attached) and still serves the correct vertex set."""
    from repro.core import costs
    from repro.core.api import GraphEdgeController
    from repro.core.dynamic_graph import random_scenario
    state = random_scenario(rng, 24, 16, 40)
    net = costs.default_network(rng, 24, 4)
    dec = GraphEdgeController(net=net).step(state)
    plan = dec.to_partition_plan(4)
    assert plan.adj_ext is None          # sparse-first, densified on demand
    assert plan.n == state.capacity
    np.testing.assert_allclose(plan.dense_adj_ext().sum(),
                               np.asarray(state.adj).sum())


# --- end-to-end serve round-trip -------------------------------------------

@pytest.mark.slow
def test_sparse_serve_roundtrip_5k():
    """5000-vertex serve through the sparse plan + gather aggregation vs
    the closed-form dense GCN (independent of the kernels under test)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC
    code = textwrap.dedent("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh
        from repro.core.hicut import hicut_ref
        from repro.data.graphs import random_graph
        from repro.gnn.distributed import (distributed_gcn_forward,
                                           make_partition_plan_sparse)
        from repro.gnn.layers import gcn_init
        n = 5000
        g = random_graph(n, 50_000, seed=0, feature_dim=24)
        assign = hicut_ref(n, g.edges) % 4
        plan = make_partition_plan_sparse(g.edges, assign, 4, n=n)
        assert plan.adj_ext is None
        params = gcn_init(jax.random.PRNGKey(0), [24, 16, 5])
        x = g.features
        mesh = Mesh(np.array(jax.devices()), ("servers",))
        out = distributed_gcn_forward(mesh, "servers", plan, params, x)
        # closed-form dense oracle (no kernel reuse)
        a_hat = jnp.asarray(g.adjacency() + np.eye(n, dtype=np.float32))
        dinv = 1.0 / jnp.sqrt(a_hat.sum(1))
        a_norm = dinv[:, None] * a_hat * dinv[None, :]
        expect = a_norm @ jax.nn.relu(
            a_norm @ jnp.asarray(x) @ params[0]["w"]) @ params[1]["w"]
        print("ERR", float(np.abs(out - np.asarray(expect)).max()))
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=420, env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    assert float(out.stdout.split("ERR")[1]) < 1e-3
