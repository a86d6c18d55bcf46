"""A small checkout for the benchmark's CPU tests: the real harness and
readers, a BENCHMARK.json whose cells point at a 40-user configuration and
two traffic mixes that a test run can hold."""
from __future__ import annotations

import json
import pathlib
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
for p in (str(REPO / "src"), str(REPO)):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = {"widths": [24, 8, 3], "users": 40, "links": 160, "capacity": 48}


def traffic_spec(visit: str, count: int, at_close: str, rate: float) -> dict:
    return {"rate_rps": rate,
            "layouts": {"count": count, "seed": 3, "change_rate": 0.2,
                        "visit": visit},
            "features": {"pool": 4},
            "frontend": {"max_batch": 8, "cross_topology": True,
                         "queue_depth": 100000},
            "at_close": at_close, "trace_seconds": 2.0}


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory) -> pathlib.Path:
    root = tmp_path_factory.mktemp("checkout")
    (root / "perfbench" / "configs").mkdir(parents=True)
    (root / "perfbench" / "traffic").mkdir()
    cfg = json.loads((REPO / "perfbench" / "configs"
                      / "gcn-pubmed-u300.json").read_text())
    cfg.update(TINY)
    (root / "perfbench" / "configs" / "tiny.json").write_text(
        json.dumps(cfg))
    for name, spec in {"steady": traffic_spec("balanced", 4, "drain", 40.0),
                       "churn": traffic_spec("cycle", 12, "shed", 60.0)
                       }.items():
        (root / "perfbench" / "traffic" / f"{name}.json").write_text(
            json.dumps(spec))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": "test",
                         "file": "perfbench/configs/tiny.json",
                         "reduced": [], "why": "test"}]
    bench["workloads"] = [
        {"name": "pubmed300-steady", "config": "tiny", "traffic": "steady",
         "chips": 1, "why": "test"},
        {"name": "pubmed300-churn", "config": "tiny", "traffic": "churn",
         "chips": 1, "why": "test"}]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture(scope="session")
def steady_run(tiny_root):
    """One warm run of the small steady cell on the CPU (the chip check
    skipped); tests open windows on it with :meth:`Run.retarget`."""
    from perfbench.harness.cell import Run
    run = Run("pubmed300-steady", 2**31 + 7, 1.5, False, tiny_root,
              require_tpu=False)
    yield run
    run.close()
