"""Blocking autotuner for the fused gather–normalize–matmul kernel.

The fused kernel (``fused.py``) is parameterized by a small config:

* ``bm``  — rows per output tile (the gather width),
* ``bf``  — feature columns per tile (both the XC slab slice and the
  matmul K-dim chunk share it, so one knob bounds the VMEM slab),
* ``kc``  — neighbor slots per inner step of each row's walk (the kernel
  unrolls that many scalar-indexed row loads per loop iteration).

Good choices depend on the *layout*, not the values: the padded slot
count K (``max_degree``), the row/column counts, the feature widths and
the VMEM budget. :func:`heuristic_config` derives a config from those in
closed form (deterministic — same shapes, same config);
:func:`autotune_config` measures a small candidate grid with an
injectable timer and persists the winner in a JSON **tuning table** keyed
by the shape signature, so subsequent runs (and other processes) skip
the search. Table lookup order: explicit ``table_path`` argument, the
``REPRO_GNN_AGG_TUNING`` environment variable, then the checked-in
``tuning_table.json`` next to this module (read-only defaults for the
benchmark shapes).
"""
from __future__ import annotations

import json
import os
import pathlib
from typing import Callable, NamedTuple

# Per-core VMEM on current TPUs is 16 MiB; leave headroom for the
# index/value blocks, the accumulator and double buffering.
DEFAULT_VMEM_BUDGET = 16 * 1024 * 1024
_LANE = 128          # TPU lane width: feature blocks are multiples of this
_SUBLANE = 8         # f32 sublane: row blocks are multiples of this
# Scalar memory is 1 MiB per core on a v5e. The gather and fused kernels
# keep their [bm, K] index and value blocks there, double-buffered, with K
# padded to whole lanes; half of it is theirs.
SMEM_BUDGET = 512 * 1024

_DEFAULT_TABLE = pathlib.Path(__file__).resolve().parent / \
    "tuning_table.json"
_ENV_TABLE = "REPRO_GNN_AGG_TUNING"


class KernelConfig(NamedTuple):
    """Blocking for one fused-aggregate call (see module docstring)."""
    bm: int              # rows per tile
    bf: int              # feature columns per tile
    kc: int              # neighbor slots per gather chunk


def shape_key(n_rows: int, n_cols: int, f_in: int, f_out: int,
              max_degree: int) -> str:
    """Tuning-table key: the layout signature the config depends on."""
    return f"n{n_rows}_c{n_cols}_fi{f_in}_fo{f_out}_k{max_degree}"


def _round_up(x: int, mult: int) -> int:
    return max(mult, ((x + mult - 1) // mult) * mult)


def _round_down_pow2(x: int) -> int:
    p = 1
    while p * 2 <= x:
        p *= 2
    return p


def vmem_bytes(config: KernelConfig, n_cols: int, max_degree: int) -> int:
    """Resident VMEM of one fused tile: the ``[n_cols, bf]`` XC slab, the
    ``[bm, K]`` index/value blocks, the ``[bf, bf]`` weight block, a
    ``[bm, kc, bf]`` allowance for in-flight row loads (an over-count: the
    kernel holds one ``[1, bf]`` row per load) and the ``[bm, bf]``
    accumulator/out."""
    bm, bf, kc = config
    k = _round_up(max_degree, kc)
    return 4 * (n_cols * bf           # XC slab slice
                + 2 * bm * k          # idx (i32) + val (f32)
                + bf * bf             # W block
                + bm * kc * bf        # gathered chunk
                + 2 * bm * bf)        # accumulator + out tile


def smem_row_cap(max_degree: int) -> int:
    """Most rows per tile whose index + value blocks (int32 + float32,
    double-buffered, K padded to lanes) fit :data:`SMEM_BUDGET`; a multiple
    of the sublane, at least one sublane."""
    per_row = 2 * 2 * 4 * _round_up(max_degree, _LANE)
    return max(_SUBLANE, SMEM_BUDGET // per_row // _SUBLANE * _SUBLANE)


def heuristic_config(n_rows: int, n_cols: int, f_in: int, f_out: int,
                     max_degree: int,
                     vmem_budget: int = DEFAULT_VMEM_BUDGET
                     ) -> KernelConfig:
    """Deterministic closed-form config from the layout shape.

    ``bf`` covers the feature width up to one lane tile (128), rounded to
    the f32 sublane — narrower features keep narrower tiles instead of
    paying pad-gather work on every slot; it is also the knob that
    shrinks first when the ``n_cols·bf`` slab would blow the budget.
    ``bm`` targets 256 rows (two gathers in flight per tile) and shrinks
    next; ``kc`` is the largest power of two ≤ ``max_degree`` capped at
    8 — deeper chunks enlarge the gather buffer faster than they amortize
    loop overhead (measured on the bench shapes; see BENCH_kernels)."""
    bf = min(_LANE, _round_up(max(f_in, f_out), _SUBLANE))
    bm = min(256, _round_up(n_rows, _SUBLANE))
    kc = min(8, _round_down_pow2(max(1, max_degree)))
    cfg = KernelConfig(bm, bf, kc)
    while vmem_bytes(cfg, n_cols, max_degree) > vmem_budget and \
            cfg.bf > _SUBLANE:
        cfg = cfg._replace(bf=cfg.bf // 2)
    while vmem_bytes(cfg, n_cols, max_degree) > vmem_budget and \
            cfg.bm > _SUBLANE:
        cfg = cfg._replace(bm=max(_SUBLANE, cfg.bm // 2))
    while vmem_bytes(cfg, n_cols, max_degree) > vmem_budget and cfg.kc > 1:
        cfg = cfg._replace(kc=cfg.kc // 2)
    return cfg


# ---------------------------------------------------------------------------
# persisted tuning table
# ---------------------------------------------------------------------------

def table_path(explicit: str | os.PathLike | None = None) -> pathlib.Path:
    if explicit is not None:
        return pathlib.Path(explicit)
    env = os.environ.get(_ENV_TABLE)
    return pathlib.Path(env) if env else _DEFAULT_TABLE


def load_table(path: str | os.PathLike | None = None) -> dict:
    p = table_path(path)
    try:
        raw = json.loads(p.read_text())
    except (OSError, json.JSONDecodeError):
        return {}
    return {k: KernelConfig(*v) for k, v in raw.items()
            if isinstance(v, (list, tuple)) and len(v) == 3}


def save_table(table: dict, path: str | os.PathLike | None = None) -> None:
    p = table_path(path)
    p.write_text(json.dumps({k: list(v) for k, v in sorted(table.items())},
                            indent=2) + "\n")


def get_config(n_rows: int, n_cols: int, f_in: int, f_out: int,
               max_degree: int, vmem_budget: int = DEFAULT_VMEM_BUDGET,
               table: dict | None = None,
               table_path: str | os.PathLike | None = None) -> KernelConfig:
    """Tuned config for a layout: the persisted table when it has the
    shape key (and the entry still fits the budget), else the heuristic.
    Deterministic: same arguments, same config."""
    table = load_table(table_path) if table is None else table
    hit = table.get(shape_key(n_rows, n_cols, f_in, f_out, max_degree))
    if hit is not None and vmem_bytes(hit, n_cols, max_degree) <= \
            vmem_budget:
        return hit
    return heuristic_config(n_rows, n_cols, f_in, f_out, max_degree,
                            vmem_budget)


def candidate_configs(n_rows: int, n_cols: int, f_in: int, f_out: int,
                      max_degree: int,
                      vmem_budget: int = DEFAULT_VMEM_BUDGET
                      ) -> list[KernelConfig]:
    """The small deterministic candidate grid the autotuner measures:
    the heuristic plus neighbors along each axis, budget-filtered."""
    base = heuristic_config(n_rows, n_cols, f_in, f_out, max_degree,
                            vmem_budget)
    seen, out = set(), []
    for bm in (base.bm // 2, base.bm, base.bm * 2):
        for kc in (max(1, base.kc // 2), base.kc, base.kc * 2):
            cfg = KernelConfig(max(_SUBLANE, bm), base.bf,
                               min(kc, max(1, max_degree)))
            if cfg in seen:
                continue
            seen.add(cfg)
            if vmem_bytes(cfg, n_cols, max_degree) <= vmem_budget:
                out.append(cfg)
    return out


def autotune_config(n_rows: int, n_cols: int, f_in: int, f_out: int,
                    max_degree: int,
                    measure: Callable[[KernelConfig], float],
                    vmem_budget: int = DEFAULT_VMEM_BUDGET,
                    persist: bool = False,
                    table_path: str | os.PathLike | None = None
                    ) -> tuple[KernelConfig, dict]:
    """Measure the candidate grid and return (best config, timings µs).

    ``measure(config) -> seconds_or_µs`` is injected so tests can drive
    the search with a deterministic fake timer. Ties break toward the
    candidate-grid order (itself deterministic), so the winner is a pure
    function of the measurements. ``persist=True`` writes the winner into
    the tuning table at ``table_path`` (merging with existing entries)."""
    cands = candidate_configs(n_rows, n_cols, f_in, f_out, max_degree,
                              vmem_budget)
    timings = {cfg: float(measure(cfg)) for cfg in cands}
    best = min(cands, key=lambda c: (timings[c], cands.index(c)))
    if persist:
        table = load_table(table_path)
        table[shape_key(n_rows, n_cols, f_in, f_out, max_degree)] = best
        save_table(table, table_path)
    return best, timings
