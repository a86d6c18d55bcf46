"""HiCut (Algorithm 1): ref↔jax equivalence, partition invariants, and the
paper's Fig. 3 worked example."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from conftest import random_edges
from repro.core.hicut import cut_metrics, hicut_jax, hicut_ref


def _to_adj(n, edges):
    a = np.zeros((n, n), np.float32)
    for i, j in edges:
        a[i, j] = a[j, i] = 1.0
    return a


def test_fig3_style_example():
    """A chain of layers whose edge counts go 3 → 2 → 1 → 4: the cut must
    land where associations weaken before strengthening again (paper §4.2)."""
    # star root 0 with 3 children (d1=3), children chain to 2 nodes (d2=2),
    # then 1 edge (d3=1), then a dense blob (d4 >= 4)
    edges = np.array([
        (0, 1), (0, 2), (0, 3),        # layer 1: d=3 edges out of root
        (1, 4), (2, 4),                # layer 2
        (4, 5),                        # layer 3
        (5, 6), (5, 7), (6, 7), (6, 8), (7, 8),   # blob
    ])
    n = 9
    assigned = hicut_ref(n, edges)
    # every vertex assigned exactly once
    assert (assigned >= 0).all()
    # the blob must not share a subgraph with the root's star
    assert assigned[0] != assigned[8]


def test_all_vertices_assigned(rng):
    for _ in range(10):
        n = int(rng.integers(3, 60))
        edges = random_edges(rng, n, int(rng.integers(0, 3 * n)))
        assigned = hicut_ref(n, edges)
        assert (assigned >= 0).all()
        # ids are 0..K-1 compact
        ids = np.unique(assigned)
        assert ids.min() == 0 and (np.diff(ids) == 1).all()


def test_inactive_vertices_excluded(rng):
    n = 20
    edges = random_edges(rng, n, 30)
    active = rng.random(n) > 0.3
    assigned = hicut_ref(n, edges, active=active)
    assert (assigned[~active] == -1).all()
    assert (assigned[active] >= 0).all()


@settings(max_examples=30, deadline=None)
@given(st.integers(2, 24), st.integers(0, 60), st.integers(0, 10_000))
def test_jax_matches_ref(n, e, seed):
    rng = np.random.default_rng(seed)
    edges = random_edges(rng, n, e)
    ref = hicut_ref(n, edges)
    adj = _to_adj(n, edges)
    jx = np.asarray(hicut_jax(jnp.asarray(adj), jnp.ones(n, np.float32)))
    np.testing.assert_array_equal(ref, jx)


@settings(max_examples=15, deadline=None)
@given(st.integers(4, 20), st.integers(0, 40), st.integers(0, 10_000))
def test_jax_matches_ref_masked(n, e, seed):
    rng = np.random.default_rng(seed)
    edges = random_edges(rng, n, e)
    active = rng.random(n) > 0.3
    ref = hicut_ref(n, edges, active=active)
    adj = _to_adj(n, edges)
    jx = np.asarray(hicut_jax(jnp.asarray(adj),
                              jnp.asarray(active.astype(np.float32))))
    np.testing.assert_array_equal(ref, jx)


def test_cut_quality_on_community_graph(rng):
    """On a graph with planted communities HiCut must beat a random
    partition on cross-edges (the paper's P1 objective)."""
    k, size = 4, 12
    n = k * size
    edges = []
    for c in range(k):
        base = c * size
        for i in range(size):
            for j in range(i + 1, size):
                if rng.random() < 0.5:
                    edges.append((base + i, base + j))
    for _ in range(6):                         # sparse inter-community edges
        a, b = rng.integers(k, size=2)
        if a != b:
            edges.append((a * size + int(rng.integers(size)),
                          b * size + int(rng.integers(size))))
    edges = np.array(sorted(set(map(lambda t: (min(t), max(t)), edges))))
    assigned = hicut_ref(n, edges)
    m = cut_metrics(n, edges, assigned)
    rand = cut_metrics(n, edges, rng.integers(0, m["num_subgraphs"] + 1, n))
    assert m["cut_fraction"] <= rand["cut_fraction"]


def test_cut_metrics_consistency(rng):
    n = 30
    edges = random_edges(rng, n, 60)
    assigned = hicut_ref(n, edges)
    m = cut_metrics(n, edges, assigned)
    assert m["total_edges"] == len(edges)
    assert 0 <= m["cross_edges"] <= m["total_edges"]
