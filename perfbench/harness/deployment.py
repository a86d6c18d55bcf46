"""Deployments as data: the edge network and the user layouts a cell serves.

A configuration file (``perfbench/configs/<name>.json``) fixes the edge
network and the base user graph from its own seeds; a traffic file fixes the
pool of perturbed layouts the requests carry. ``--seed`` changes neither, so
every run of a cell serves the same layouts with the same shapes.

The generators are copies, in vectorized numpy, of the program's
``costs.default_network``, ``dynamic_graph.random_scenario`` and
``dynamic_graph.perturb_scenario`` (paper §3.2, §6.1, Table 2): the
benchmark owns its yardstick, so a later change to the program's generators
does not change what is measured.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class Network:
    """The edge network ω in plain numpy (float32, as the program stores
    it): servers on a grid over the plane, Table 2's ranges."""
    server_pos: np.ndarray   # [M, 2] m
    f_k: np.ndarray          # [M] Hz
    capacity: np.ndarray     # [M] users a server may host
    B_im: np.ndarray         # [N, M] Hz
    B_kl: np.ndarray         # [M, M] Hz
    P_i: np.ndarray          # [N] W
    P_k: np.ndarray          # [M] W
    eta_kl: np.ndarray       # [M, M] {0, 1}
    sigma2: float
    rho0: float
    h0: float
    zeta_im: float
    zeta_kl: float


@dataclass(frozen=True)
class Layout:
    """One user layout G(t): mask, positions, dense 0/1 adjacency, task
    sizes, padded to the configuration's capacity."""
    mask: np.ndarray         # [N] f32 {0, 1}
    pos: np.ndarray          # [N, 2] f32
    adj: np.ndarray          # [N, N] f32 {0, 1}, symmetric, zero diagonal
    task_kb: np.ndarray      # [N] f32

    @property
    def capacity(self) -> int:
        return int(self.mask.shape[0])


def make_network(rng: np.random.Generator, capacity: int, m: int,
                 plane: float) -> Network:
    """Table 2's network: M servers on a √M grid, capacities drawn from
    {5/4, 1, 3/4} × capacity/M, f_k ∈ U(2, 10) GHz, B_im ∈ U(20, 50) MHz,
    B_kl = 100 MHz, P_i ∈ U(2, 5) mW, P_k ∈ U(10, 15) mW, σ² = −110 dBm."""
    side = int(np.ceil(np.sqrt(m)))
    cells = plane / side
    pos = np.array([[(i % side + 0.5) * cells, (i // side + 0.5) * cells]
                    for i in range(m)], np.float32)
    mean = capacity / m
    levels = np.array([1.25 * mean, mean, 0.75 * mean], np.float32)
    caps = levels[rng.integers(0, 3, m)]
    return Network(
        server_pos=pos,
        f_k=rng.uniform(2e9, 10e9, m).astype(np.float32),
        capacity=caps,
        B_im=rng.uniform(20e6, 50e6, (capacity, m)).astype(np.float32),
        B_kl=np.full((m, m), 100e6, np.float32),
        P_i=rng.uniform(2e-3, 5e-3, capacity).astype(np.float32),
        P_k=rng.uniform(10e-3, 15e-3, m).astype(np.float32),
        eta_kl=(np.ones((m, m)) - np.eye(m)).astype(np.float32),
        sigma2=10 ** (-110 / 10) * 1e-3, rho0=1e-3, h0=1e-7,
        zeta_im=3e-3 / 1e6, zeta_kl=5e-3 / 1e6)


def _masked(mask, pos, adj, kb) -> Layout:
    """Drop the edges and task data of inactive users (paper §3.2)."""
    adj = adj * mask[:, None] * mask[None, :]
    return Layout(mask.astype(np.float32), pos.astype(np.float32),
                  adj.astype(np.float32), (kb * mask).astype(np.float32))


def random_layout(rng: np.random.Generator, capacity: int, users: int,
                  links: int, plane: float, task_kb: tuple) -> Layout:
    """``users`` users placed uniformly on the plane with ``links`` distinct
    random associations and task sizes from ``task_kb`` (paper §6.1)."""
    pos = np.zeros((capacity, 2))
    pos[:users] = rng.uniform(0, plane, (users, 2))
    links = min(links, users * (users - 1) // 2)
    keys = np.zeros(0, np.int64)
    while len(keys) < links:
        i = rng.integers(users, size=2 * links)
        j = rng.integers(users, size=2 * links)
        cand = np.minimum(i, j) * users + np.maximum(i, j)
        cand = cand[i != j]
        keys = np.concatenate([keys, cand])
        _, first = np.unique(keys, return_index=True)
        keys = keys[np.sort(first)]
    keys = keys[:links]
    adj = np.zeros((capacity, capacity))
    a, b = np.divmod(keys, users)
    adj[a, b] = adj[b, a] = 1.0
    kb = np.zeros(capacity)
    kb[:users] = rng.uniform(*task_kb, users)
    mask = np.zeros(capacity)
    mask[:users] = 1.0
    return _masked(mask, pos, adj, kb)


def perturb(rng: np.random.Generator, base: Layout, change_rate: float,
            plane: float, task_kb: tuple, friends: int = 3) -> Layout:
    """One dynamic step of paper §6.4 at ``change_rate``: every active user
    drifts (σ = 5% of the plane), a ``change_rate / 2`` share of slots flip
    membership (leavers drop their links; joiners get ≤ ``friends`` random
    links), and a ``change_rate`` share of the links among active users is
    rewired to random pairs."""
    n = base.capacity
    mask = base.mask.copy()
    active = mask > 0
    pos = base.pos.astype(np.float64).copy()
    drift = np.clip(pos + rng.normal(0, 0.05 * plane, (n, 2)), 0, plane)
    pos[active] = drift[active]
    flips = rng.random(n) < change_rate * 0.5
    adj = base.adj.astype(np.float64).copy()
    kb = base.task_kb.astype(np.float64).copy()
    drop = flips & active
    mask[drop] = 0.0
    adj[drop, :] = 0.0
    adj[:, drop] = 0.0
    grow = flips & ~active
    if grow.any():
        new_pos = rng.uniform(0, plane, (n, 2))
        new_kb = rng.uniform(*task_kb, n)
        pos[grow] = new_pos[grow]
        kb[grow] = new_kb[grow]
        live = (mask > 0) | grow
        for i in np.nonzero(grow)[0]:
            cand = np.nonzero(live)[0]
            cand = cand[cand != i]
            pick = rng.choice(cand, size=min(friends, len(cand)),
                              replace=False)
            adj[i, pick] = adj[pick, i] = 1.0
        mask[grow] = 1.0
    act = np.nonzero(mask > 0)[0]
    if len(act) >= 2:
        i, j = np.nonzero(np.triu(adj, 1))
        sel = rng.random(len(i)) < change_rate
        adj[i[sel], j[sel]] = adj[j[sel], i[sel]] = 0.0
        a = rng.integers(len(act), size=int(sel.sum()))
        b = rng.integers(len(act) - 1, size=int(sel.sum()))
        b = b + (b >= a)
        adj[act[a], act[b]] = adj[act[b], act[a]] = 1.0
    np.fill_diagonal(adj, 0.0)
    return _masked(mask, pos, adj, kb)


def layout_pool(config: dict, spec: dict) -> list[Layout]:
    """The traffic's layouts: ``spec["count"]`` independent perturbations
    of the configuration's base layout, all from fixed seeds."""
    base = random_layout(np.random.default_rng(config["layout_seed"]),
                         config["capacity"], config["users"],
                         config["links"], config["plane_m"],
                         tuple(config["task_kb"]))
    rng = np.random.default_rng(spec["seed"])
    return [perturb(rng, base, spec["change_rate"], config["plane_m"],
                    tuple(config["task_kb"]))
            for _ in range(spec["count"])]


def config_network(config: dict) -> Network:
    return make_network(np.random.default_rng(config["network_seed"]),
                        config["capacity"], config["servers"],
                        config["plane_m"])
