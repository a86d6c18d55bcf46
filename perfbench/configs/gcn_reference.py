"""Plain reference of the GCN both configurations serve (Kipf & Welling
2017, Eqs. 1–2 of the GraphEdge paper): numpy, no plan, no batching, no
kernels.

    H⁽ᵏ⁺¹⁾ = σ(D̃^{-1/2} Ã D̃^{-1/2} H⁽ᵏ⁾ W⁽ᵏ⁾),   Ã = A + I over active users

with ReLU between layers and none after the last; rows of inactive users
are zero. The reference computes in float64, a finer precision than the
"highest" float32 the TPU reaches. ``operand_dtype`` rounds every matmul
operand to a narrower type first (float32 accumulate): with
``float8_e4m3fn`` it is the control, one step below the bfloat16 operands
the configuration is served with.
"""
from __future__ import annotations

import numpy as np


def normalized_adjacency(adj: np.ndarray, mask: np.ndarray) -> np.ndarray:
    """D̃^{-1/2} (A + I) D̃^{-1/2} over the active users, float64."""
    m = np.asarray(mask, np.float64)
    a = np.asarray(adj, np.float64) * m[:, None] * m[None, :] + np.diag(m)
    deg = a.sum(1)
    dinv = np.where(deg > 0, 1.0 / np.sqrt(np.maximum(deg, 1e-300)), 0.0)
    return a * dinv[:, None] * dinv[None, :]


def _round(x: np.ndarray, dtype) -> np.ndarray:
    if dtype is None:
        return np.asarray(x, np.float64)
    return np.asarray(x, np.float32).astype(dtype).astype(np.float32)


def project(w: np.ndarray, x: np.ndarray, operand_dtype=None) -> np.ndarray:
    """The first layer's ``X·W`` (independent of the layout, so a batch of
    layouts over one feature pool shares it)."""
    return _round(_round(x, operand_dtype) @ _round(w, operand_dtype),
                  operand_dtype)


def gcn_from_projection(weights: list, xw: np.ndarray, adj: np.ndarray,
                        mask: np.ndarray, operand_dtype=None) -> np.ndarray:
    """Outputs [..., N, C] given the first projection ``xw`` [..., N, H]
    (leading axes are independent requests on the same layout)."""
    a = _round(normalized_adjacency(adj, mask), operand_dtype)
    h = a @ xw
    for w in weights[1:]:
        h = _round(np.maximum(h, 0.0), operand_dtype)
        h = a @ _round(h @ _round(w, operand_dtype), operand_dtype)
    return np.asarray(h, np.float64) * np.asarray(mask, np.float64)[:, None]


def gcn_forward(weights: list, x: np.ndarray, adj: np.ndarray,
                mask: np.ndarray, operand_dtype=None) -> np.ndarray:
    """Outputs [..., N, C] for features ``x`` [..., N, F]."""
    return gcn_from_projection(weights, project(weights[0], x, operand_dtype),
                               adj, mask, operand_dtype)
