"""Cost model (Eqs. 3–14): hand-checked values + invariants + the
incremental env cost vs the batch model."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import costs
from repro.core.dynamic_graph import make_graph_state, random_scenario


def tiny_setup(n_users=4, m=2, seed=0):
    rng = np.random.default_rng(seed)
    state = random_scenario(rng, n_users, n_users, 3, plane=1000.0)
    net = costs.default_network(rng, n_users, m, plane=1000.0)
    return rng, state, net


def test_uplink_rate_formula():
    rng, state, net = tiny_setup()
    r = np.asarray(costs.uplink_rate(net, state))
    h = np.asarray(costs.channel_gain(net, state))
    # Eq. (3) recomputed by hand for one (i, m)
    i, m = 1, 0
    expect = float(net.B_im[i, m]) * np.log2(
        1 + float(net.P_i[i]) * h[i, m] / net.sigma2)
    assert np.isclose(r[i, m], expect, rtol=1e-5)
    assert (r > 0).all()


def test_upload_cost_scales_with_data():
    rng, state, net = tiny_setup()
    w = costs.assignment_onehot(jnp.zeros(4, jnp.int32), 2)
    t1, e1 = costs.upload_costs(net, state, w)
    state2 = state._replace(task_kb=state.task_kb * 2)
    t2, e2 = costs.upload_costs(net, state2, w)
    np.testing.assert_allclose(np.asarray(t2), 2 * np.asarray(t1), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(e2), 2 * np.asarray(e1), rtol=1e-5)


def test_cross_server_bits_zero_when_colocated():
    """Co-locating every user removes all cross-server traffic (Eq. 8→0)."""
    rng, state, net = tiny_setup()
    w = costs.assignment_onehot(jnp.zeros(4, jnp.int32), 2)
    x = costs.cross_server_bits(state, w)
    assert float(jnp.sum(x)) == 0.0


def test_cross_server_bits_hand_value():
    # two users, one edge, on different servers
    state = make_graph_state(2, [[0, 0], [10, 10]], [(0, 1)], [100.0, 200.0])
    rng = np.random.default_rng(0)
    net = costs.default_network(rng, 2, 2)
    w = costs.assignment_onehot(jnp.asarray([0, 1]), 2)
    x = np.asarray(costs.cross_server_bits(state, w))
    # x_{0→1} = X_0 (user0 on sv0 has neighbor on sv1), x_{1→0} = X_1
    assert np.isclose(x[0, 1], 100e3)
    assert np.isclose(x[1, 0], 200e3)


def test_system_cost_prefers_colocated_neighbors():
    state = make_graph_state(4, [[0, 0], [1, 1], [999, 999], [998, 998]],
                             [(0, 1), (2, 3)], [1000.0] * 4)
    rng = np.random.default_rng(1)
    net = costs.default_network(rng, 4, 2)
    together = costs.assignment_onehot(jnp.asarray([0, 0, 1, 1]), 2)
    split = costs.assignment_onehot(jnp.asarray([0, 1, 0, 1]), 2)
    c_tog = costs.system_cost(net, state, together)
    c_spl = costs.system_cost(net, state, split)
    assert float(c_tog.c) < float(c_spl.c)
    assert float(c_tog.cross_bits.sum()) == 0.0


def test_masked_users_cost_nothing():
    rng, state, net = tiny_setup()
    dead = state._replace(mask=jnp.zeros_like(state.mask))
    w = costs.assignment_onehot(jnp.zeros(4, jnp.int32), 2)
    sc = costs.system_cost(net, dead, w)
    assert float(sc.t_all) == 0.0
    assert float(sc.i_all) == 0.0


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 16), st.integers(0, 40), st.integers(0, 9999))
def test_costs_nonnegative_and_finite(n, e, seed):
    rng = np.random.default_rng(seed)
    state = random_scenario(rng, n, n, e)
    net = costs.default_network(rng, n, 4)
    assign = rng.integers(0, 4, n)
    sc = costs.system_cost(net, state,
                           costs.assignment_onehot(jnp.asarray(assign), 4))
    for v in (sc.c, sc.t_all, sc.i_all, sc.i_gnn):
        assert np.isfinite(float(v)) and float(v) >= 0.0


def test_env_marginal_cost_matches_batch_model():
    """Σ marginal costs over an episode == the Eqs. (12)–(13) batch totals
    for the assignment-dependent terms."""
    from repro.core.offload.env import OffloadEnv
    rng = np.random.default_rng(2)
    state = random_scenario(rng, 12, 10, 20)
    net = costs.default_network(rng, 12, 3)
    env = OffloadEnv(net, state, np.arange(12), use_subgraph_reward=False,
                     cost_scale=1.0)
    env.reset()
    total_marginal = 0.0
    while env.t < env.num_steps:
        i = env.current_user()
        k = int(rng.integers(3))
        total_marginal += env.marginal_cost(i, k)
        acts = np.zeros((3, 2), np.float32)
        acts[:, 1] = 1.0
        acts[k, 0] = 2.0
        env.step(acts)
    sc = env.final_cost()
    batch_total = float(jnp.sum(sc.t_up) + jnp.sum(sc.i_up)
                        + jnp.sum(sc.t_com) + sc.i_gnn
                        + jnp.sum(sc.i_com)
                        # marginal counts (X_i+X_j)/R per new cross pair once;
                        # batch T_tran counts x̃/R once per (k,l) — same total
                        + jnp.sum(sc.t_tran) / 2.0)
    assert np.isclose(total_marginal, batch_total, rtol=0.05), \
        (total_marginal, batch_total)
