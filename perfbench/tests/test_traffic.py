"""A cell's structure comes from its files, not from ``--seed``."""
import json
import pathlib

import numpy as np
import pytest

from perfbench.harness import deployment, traffic

PERFBENCH = pathlib.Path(__file__).resolve().parents[1]
SEEDS = [0, 1, 2**31 + 12345, 2**40 + 3]


def _spec(name):
    return json.loads((PERFBENCH / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["steady-pubmed", "churn-pubmed"])
def test_request_count_and_layout_set_do_not_depend_on_seed(name):
    spec = _spec(name)
    streams = [traffic.make_stream(spec, s, 45.0) for s in SEEDS]
    counts = {len(st) for st in streams}
    assert counts == {int(round(spec["rate_rps"] * 45.0))}
    uses = {tuple(sorted(np.bincount(st.layout_of,
                                     minlength=spec["layouts"]["count"])))
            for st in streams}
    assert len(uses) == 1     # every layout as often (±1), every seed
    orders = {tuple(st.layout_of[:50]) for st in streams}
    assert len(orders) == len(SEEDS)   # in another order


def test_arrivals_lie_sorted_inside_the_window():
    st = traffic.make_stream(_spec("steady-pubmed"), 2**33, 10.0)
    assert np.all(np.diff(st.offsets) >= 0)
    assert 0.0 <= st.offsets[0] and st.offsets[-1] < 10.0


def test_cycle_visits_reuse_a_layout_only_a_pool_apart():
    spec = _spec("churn-pubmed")
    pool = spec["layouts"]["count"]
    st = traffic.make_stream(spec, 5, 45.0)
    for i in range(pool, len(st)):
        assert st.layout_of[i] == st.layout_of[i - pool]
    assert len(set(st.layout_of[:pool])) == pool


def test_same_seed_same_stream_and_features():
    spec = _spec("steady-pubmed")
    a, b = (traffic.make_stream(spec, 99, 5.0) for _ in range(2))
    assert np.array_equal(a.offsets, b.offsets)
    assert np.array_equal(a.feature_of, b.feature_of)
    fa = traffic.feature_pool({"features": {"pool": 2}}, 99, 10, 4)
    fb = traffic.feature_pool({"features": {"pool": 2}}, 99, 10, 4)
    assert np.array_equal(fa, fb) and fa.dtype == np.float32


def test_layout_pool_and_network_are_fixed_by_the_files():
    cfg = json.loads((PERFBENCH / "configs"
                      / "gcn-pubmed-u300.json").read_text())
    spec = _spec("steady-pubmed")
    a = deployment.layout_pool(cfg, spec["layouts"])
    b = deployment.layout_pool(cfg, spec["layouts"])
    assert all(np.array_equal(x.adj, y.adj) for x, y in zip(a, b))
    lay = a[0]
    assert lay.adj.shape == (cfg["capacity"], cfg["capacity"])
    assert np.array_equal(lay.adj, lay.adj.T)
    assert not np.any(np.diag(lay.adj))
    inactive = lay.mask == 0
    assert not lay.adj[inactive].any() and not lay.task_kb[inactive].any()
    net = deployment.config_network(cfg)
    assert net.capacity.shape == (cfg["servers"],)


def test_base_layout_has_the_configured_users_and_links():
    lay = deployment.random_layout(np.random.default_rng(0), 308, 300,
                                   4800, 2000.0, (500.0, 1500.0))
    assert int(lay.mask.sum()) == 300
    assert int(np.triu(lay.adj, 1).sum()) == 4800
