"""The request stream of one run, drawn from ``--seed``.

One general generator reads every traffic file (``perfbench/traffic/
<name>.json``). What decides how much work a window holds comes from the
file: the offered rate fixes the number of requests (``rate × seconds``,
rounded), the layout pool and its visiting rule fix which topologies are
served. ``--seed`` draws only the arrival times, which layout each request
carries and its features, so every seed gives the same set of sizes in
another order.

Arrivals are a Poisson process conditioned on its count: the sorted
offsets of ``count`` uniform draws over the window (the program's
``poisson_workload`` draws exponential gaps, whose count varies by seed).
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

# independent generators per quantity, so adding one never shifts another
_STREAMS = {"arrivals": 0, "layouts": 1, "features": 2, "weights": 3,
            "feature_values": 4}


def stream(seed: int, name: str) -> np.random.Generator:
    """The generator of one quantity of a run; any non-negative ``seed``
    (no 32-bit limit)."""
    return np.random.default_rng(
        np.random.SeedSequence([int(seed), _STREAMS[name]]))


@dataclass(frozen=True)
class Stream:
    """Everything the producer thread hands the front-end, precomputed."""
    offsets: np.ndarray      # [R] seconds after the window opens, sorted
    layout_of: np.ndarray    # [R] index into the layout pool
    feature_of: np.ndarray   # [R] index into the feature pool

    def __len__(self) -> int:
        return len(self.offsets)


def request_count(rate: float, seconds: float) -> int:
    return max(1, int(round(rate * seconds)))


def make_stream(spec: dict, seed: int, seconds: float) -> Stream:
    """Arrivals, layout choices and feature choices for one window."""
    count = request_count(spec["rate_rps"], seconds)
    offsets = np.sort(stream(seed, "arrivals").uniform(0.0, seconds, count))
    pool = spec["layouts"]["count"]
    rng = stream(seed, "layouts")
    if spec["layouts"]["visit"] == "balanced":
        # every layout equally often, in a seeded order: a hot working set
        layout_of = rng.permutation(np.resize(np.arange(pool), count))
    elif spec["layouts"]["visit"] == "cycle":
        # one seeded order repeated: every reuse is ``pool`` requests apart
        layout_of = np.resize(rng.permutation(pool), count)
    else:
        raise ValueError(f"unknown visit rule {spec['layouts']['visit']!r}")
    feature_of = stream(seed, "features").integers(
        spec["features"]["pool"], size=count)
    return Stream(offsets, layout_of, feature_of)


def feature_pool(spec: dict, seed: int, capacity: int,
                 width: int) -> np.ndarray:
    """[pool, capacity, width] float32 standard-normal vertex features."""
    return stream(seed, "feature_values").standard_normal(
        (spec["features"]["pool"], capacity, width), dtype=np.float32)
