"""Operations and bytes of the served GCN, computed from shapes.

Two counts, each named for what it is:

* :func:`forward_call` — what one dispatch of the served forward
  (``_forward_blocks*``) must at least compute and move: per layer the
  dense projection ``H·W`` and the aggregation over the plan's extended
  block (a dense ``[L, L + P·H]`` matmul, or ``K + 1`` gathered slots per
  row for the gather layouts). Bytes are the inputs read once and the
  output written once; intermediates are assumed to stay on chip, so the
  count is a floor and the roofline share it gives cannot pass 100%.
* :func:`model_request` — the GCN's own work for one request on its layout:
  ``2·N·F_in·F_out`` for each projection and ``2·nnz(Ã)·F_out`` for each
  aggregation over the active users. This is what ``serve_mfu`` counts:
  padding and the dense form of the aggregate are the system's choice, not
  the model's.
"""
from __future__ import annotations

F32 = 4
I32 = 4


def forward_call(mode: str, batch: int, devices: int, block: int,
                 ext_cols: int, slots: int, widths: list[int],
                 per_member_consts: bool) -> tuple[float, float]:
    """(flops, bytes) of one forward dispatch over ``batch`` requests.

    ``mode`` is the resolved aggregate ("dense", "sparse" or "fused"),
    ``slots`` the padded neighbour slots K (self loop excluded),
    ``widths`` the layer widths [F_in, ..., F_out]. The fused mode
    aggregates at the input width and projects after; the others project
    first. ``per_member_consts`` marks the cross-topology forward, whose
    adjacency and scales are read once per batch member."""
    flops = 0.0
    rows = devices * batch * block
    for f_in, f_out in zip(widths[:-1], widths[1:]):
        flops += 2.0 * rows * f_in * f_out
        agg_width = f_in if mode == "fused" else f_out
        cols = ext_cols if mode == "dense" else slots + 1
        flops += 2.0 * rows * cols * agg_width
    copies = batch if per_member_consts else 1
    if mode == "dense":
        adjacency = devices * block * ext_cols * F32
    else:
        adjacency = devices * block * (slots + 1) * (I32 + F32)
    scales = devices * (2 * block + ext_cols) * F32
    weights = sum(a * b for a, b in zip(widths[:-1], widths[1:])) * F32
    io = rows * (widths[0] + widths[-1]) * F32
    return flops, float(io + weights + copies * (adjacency + scales))


def model_request(active: int, nnz_with_loops: int,
                  widths: list[int]) -> float:
    """The GCN's own FLOPs for one request (see module docstring)."""
    return float(sum(2.0 * active * f_in * f_out
                     + 2.0 * nnz_with_loops * f_out
                     for f_in, f_out in zip(widths[:-1], widths[1:])))


def roofline_seconds(flops: float, nbytes: float, peak: dict
                     ) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_compute = flops / peak["bf16_flop_per_s"]
    t_memory = nbytes / peak["hbm_byte_per_s"]
    return (t_compute, "compute") if t_compute >= t_memory \
        else (t_memory, "memory")
