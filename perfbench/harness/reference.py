"""Plain references for the control plane: HiCut (paper Algorithm 1), the
greedy offload GM (paper §6.1) and the system cost of Eqs. (3)–(14).

Straight loops in numpy float64 over the benchmark's own
:class:`~perfbench.harness.deployment.Layout` and
:class:`~perfbench.harness.deployment.Network`; nothing of the program is
imported, and no array the program made is read.
"""
from __future__ import annotations

import numpy as np

from perfbench.harness.deployment import Layout, Network

KB = 1e3   # bits per kilobit


def hicut(layout: Layout) -> np.ndarray:
    """Algorithm 1: [N] subgraph ids, −1 for inactive users.

    From every still-unassigned active user (in index order) a LayerCut
    walks breadth-first layers. ``d_n`` counts a layer's edges toward users
    not yet in any subgraph (an edge inside the layer counts from both
    ends). A layer whose ``d_n`` falls becomes the pending cut ``V_seg``;
    the cut is made where associations strengthen again (strictly), or the
    walk commits ``V_seg`` with the layer when ``d_n`` reaches 0. On a tie
    only the current layer commits; a ``V_seg`` still pending when the
    frontier dies stays unassigned and seeds a later LayerCut."""
    n = layout.capacity
    active = layout.mask > 0
    adj = (layout.adj > 0) & active[:, None] & active[None, :]
    assigned = np.full(n, -1, np.int64)
    sid = 0
    for v in range(n):
        if not active[v] or assigned[v] >= 0:
            continue
        assigned[v] = sid
        frontier = np.zeros(n, bool)
        frontier[v] = True
        visited = frontier.copy()
        vseg = np.zeros(n, bool)
        d_prev, first = 0, True
        while frontier.any():
            open_ = active & (assigned < 0)
            d_n = int(adj[frontier][:, open_].sum())
            nxt = adj[frontier].any(0) & open_ & ~visited
            visited |= nxt
            if d_n == 0:
                assigned[vseg | frontier] = sid
                break
            if first:
                d_prev, first = d_n, False
            elif d_prev <= d_n:
                if vseg.any() and d_prev < d_n:
                    assigned[vseg] = sid
                    break
                d_prev = d_n
                assigned[frontier] = sid
            else:
                assigned[vseg] = sid
                vseg = frontier.copy()
                d_prev = d_n
            frontier = nxt
        sid += 1
    return assigned


def distances(layout: Layout, net: Network) -> np.ndarray:
    """[N, M] user–server distances, float64."""
    pos = layout.pos.astype(np.float64)
    return np.linalg.norm(pos[:, None, :] - net.server_pos[None], axis=-1)


def greedy(layout: Layout, subgraph: np.ndarray, net: Network) -> np.ndarray:
    """GM: each active user, visited grouped by subgraph (ascending id,
    then user index), goes to the nearest server that is not full; a
    server is full once its load reaches its capacity (a server of
    capacity 0 from the start). When every server is full the user goes to
    the nearest server if that one is among the least-loaded servers that
    have capacity, else to the first of those."""
    caps = net.capacity.astype(np.float64)
    m = len(caps)
    active = np.nonzero(layout.mask > 0)[0]
    order = active[np.argsort(subgraph[active], kind="stable")]
    d = distances(layout, net)
    load = np.zeros(m)
    full = caps <= 0
    servers = np.full(layout.capacity, -1, np.int64)
    for i in order:
        open_d = np.where(full, np.inf, d[i])
        k = int(np.argmin(open_d if np.isfinite(open_d).any() else d[i]))
        if full.all():
            hosting = caps > 0
            pool = hosting if hosting.any() else np.ones(m, bool)
            low = pool & (load == load[pool].min())
            k = k if low[k] else int(np.argmax(low))
        servers[i] = k
        load[k] += 1.0
        full = load >= caps
    return servers


def system_cost(layout: Layout, servers: np.ndarray, net: Network,
                gnn: dict, transfer_operand=None) -> dict[str, float]:
    """Eqs. (3)–(14) for the assignment ``servers``: the terms each user
    pays alone (``local``: Eqs. 4, 5, 9 and the GNN energy of Eqs. 10–11),
    the server-to-server terms (``transfer``: Eqs. 6–8), and their sum
    ``total`` = C = T_all + I_all. ``transfer_operand`` rounds the task
    bits that enter the cross-server einsum (the control)."""
    m = len(net.f_k)
    mask = layout.mask.astype(np.float64)
    w = np.zeros((layout.capacity, m))
    placed = servers >= 0
    w[np.nonzero(placed)[0], servers[placed]] = 1.0
    w *= mask[:, None]
    bits = layout.task_kb.astype(np.float64) * KB * mask
    d = distances(layout, net)
    gain = net.rho0 / np.maximum(d, 1.0) ** 2                     # h_im
    rate_up = net.B_im * np.log2(1.0 + net.P_i[:, None] * gain
                                 / net.sigma2)                    # Eq. 3
    t_up = (bits[:, None] / np.maximum(rate_up, 1.0) * w).sum(1)  # Eq. 4
    i_up = (bits[:, None] * net.zeta_im * w).sum(1)               # Eq. 5
    x_bits = bits if transfer_operand is None else transfer_operand(bits)
    x = np.einsum("i,ik,ij,jl->kl", x_bits, w,
                  layout.adj.astype(np.float64), w)
    x *= 1.0 - np.eye(m)                                          # x_{k→l}
    rate_sv = net.B_kl * np.log2(1.0 + net.P_k[:, None] * net.h0
                                 / net.sigma2) * (1.0 - np.eye(m))  # Eq. 6
    t_tran = (x + x.T) / np.maximum(rate_sv, 1.0) * net.eta_kl    # Eq. 7
    i_com = net.zeta_kl * x * net.eta_kl                          # Eq. 8
    t_com = (bits[:, None] / net.f_k[None, :] * w).sum(1)         # Eq. 9
    deg = (layout.adj.astype(np.float64) @ mask) * mask
    sizes = [s * KB for s in gnn["layer_sizes_kb"]]
    i_gnn = 0.0
    for k in range(1, len(sizes)):                                # Eqs. 10-11
        i_gnn += gnn["mu"] * deg.sum() * sizes[k - 1]
        i_gnn += (gnn["theta"] * sizes[k - 1] * sizes[k]
                  / gnn["update_norm_bits"] + gnn["phi"] * sizes[k]) \
            * mask.sum()
    local = t_up.sum() + t_com.sum() + i_up.sum() + i_gnn
    transfer = t_tran.sum() + i_com.sum()
    return {"local": float(local), "transfer": float(transfer),
            "total": float(local + transfer)}                     # Eq. 14
