"""The plain references agree with the program on small layouts (the
references import nothing of the program; these tests do, to compare)."""
import dataclasses

import numpy as np
import pytest

from perfbench.configs import gcn_reference
from perfbench.harness import deployment, reference

GNN = {"mu": 2e-11, "theta": 1e-10, "phi": 5e-11,
       "layer_sizes_kb": [1500.0, 64.0, 8.0], "update_norm_bits": 1000.0}


def _layout(seed, users=30, links=70, cap=36):
    rng = np.random.default_rng(seed)
    base = deployment.random_layout(rng, cap, users, links, 2000.0,
                                    (500.0, 1500.0))
    return deployment.perturb(rng, base, 0.2, 2000.0, (500.0, 1500.0))


def _program_net(net):
    import jax.numpy as jnp

    from repro.core import costs
    return costs.EdgeNetwork(
        server_pos=jnp.asarray(net.server_pos), f_k=jnp.asarray(net.f_k),
        capacity=jnp.asarray(net.capacity), B_im=jnp.asarray(net.B_im),
        B_kl=jnp.asarray(net.B_kl), P_i=jnp.asarray(net.P_i),
        P_k=jnp.asarray(net.P_k), eta_kl=jnp.asarray(net.eta_kl),
        sigma2=net.sigma2, rho0=net.rho0, h0=net.h0, zeta_im=net.zeta_im,
        zeta_kl=net.zeta_kl)


@pytest.mark.parametrize("seed", range(6))
def test_hicut_matches_program(seed):
    from repro.core.hicut import hicut_jax
    lay = _layout(seed)
    got = reference.hicut(lay)
    assert np.array_equal(got, np.asarray(hicut_jax(lay.adj, lay.mask)))


@pytest.mark.parametrize("seed", range(4))
def test_greedy_and_cost_match_program(seed):
    from repro.core import costs
    from repro.core.api import GraphEdgeController
    from repro.core.dynamic_graph import GraphState
    lay = _layout(seed)
    # capacities that fill: the all-full fallback is exercised too
    net = deployment.make_network(np.random.default_rng(seed),
                                  lay.capacity, 4, 2000.0)
    net = dataclasses.replace(net, capacity=np.array(
        [5.0, 6.5, 0.0, 7.0], np.float32))
    prog = GraphEdgeController(net=_program_net(net), policy="greedy_jit",
                               partitioner="hicut_jax",
                               gnn=costs.GNNCostParams())
    dec = prog.step(GraphState(lay.mask, lay.pos, lay.adj, lay.task_kb))
    sub = reference.hicut(lay)
    srv = reference.greedy(lay, sub, net)
    assert np.array_equal(np.asarray(dec.servers, np.int64), srv)
    want = reference.system_cost(lay, srv, net, GNN)
    assert float(dec.cost.c) == pytest.approx(want["total"], rel=1e-5)
    sc = dec.cost
    local = sum(float(np.sum(t)) for t in (sc.t_up, sc.t_com, sc.i_up,
                                           sc.i_gnn))
    assert local == pytest.approx(want["local"], rel=1e-5)
    transfer = float(np.sum(sc.t_tran) + np.sum(sc.i_com))
    assert transfer == pytest.approx(want["transfer"], rel=1e-5)


def test_gcn_reference_matches_program_forward():
    import jax

    from repro.gnn.layers import gcn_apply, gcn_init
    lay = _layout(3)
    params = gcn_init(jax.random.PRNGKey(0), [12, 8, 3])
    x = np.random.default_rng(1).normal(size=(lay.capacity, 12))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(gcn_apply(params, x.astype(np.float32), lay.adj,
                                    lay.mask))
    weights = [np.asarray(p["w"], np.float64) for p in params]
    got = gcn_reference.gcn_forward(weights, x, lay.adj, lay.mask)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_control_rounding_moves_the_outputs_by_percents():
    import ml_dtypes
    lay = _layout(4)
    rng = np.random.default_rng(2)
    weights = [rng.uniform(-0.3, 0.3, (32, 16)),
               rng.uniform(-0.5, 0.5, (16, 3))]
    x = rng.normal(size=(lay.capacity, 32))
    ref = gcn_reference.gcn_forward(weights, x, lay.adj, lay.mask)
    bf16 = gcn_reference.gcn_forward(weights, x, lay.adj, lay.mask,
                                     ml_dtypes.bfloat16)
    fp8 = gcn_reference.gcn_forward(weights, x, lay.adj, lay.mask,
                                    ml_dtypes.float8_e4m3fn)

    def rel(a):
        return np.sqrt(np.mean((a - ref) ** 2) / np.mean(ref ** 2))
    assert rel(bf16) < 0.01 < 0.03 < rel(fp8)
