"""SLO telemetry for the streaming serving front-end.

Every request that flows through :class:`repro.serve.frontend.
StreamingFrontend` is stamped on a **monotonic tick clock** at four points
— arrival (submit), admit (admission decision), dispatch (batched forward
launched) and done (output fetched) — giving the four per-request phase
latencies the SLO accounting is built on:

    queue_wait = admit − arrival      (time spent queued / deferred)
    decide     = dispatch − admit     (control step + scatter + dispatch)
    forward    = done − dispatch      (device compute + output fetch)
    total      = done − arrival       (the end-to-end request latency)

Each timing also records the ``cycle`` (the front-end's pump number) that
served it, so a slow request joins its cycle's ``ge.frontend.cycle`` span
in a profiler trace and its :class:`CycleTelemetry` record.

The tick clock is injectable: :class:`MonotonicClock` (the default) reads
``time.perf_counter`` so ticks are wall-clock seconds; :class:`ManualClock`
is a deterministic logical clock for tests and simulated workloads — the
front-end only ever calls ``now()`` and ``sleep()``, so the two are
interchangeable. All tick arithmetic is float seconds in either case.

:func:`summarize` aggregates a batch of timings into the
``BENCH_serving.json`` streaming-record shape: p50/p95/p99/mean/max per
phase plus **sustained requests/sec** (served count over the
first-arrival→last-done span — the open-loop throughput number, not the
inverse mean latency).
"""
from __future__ import annotations

import heapq
import time
from dataclasses import dataclass

import numpy as np

PERCENTILES = (50, 95, 99)
SLOWEST_KEPT = 8                 # slowest cycles CycleTelemetry keeps whole


class MonotonicClock:
    """Wall tick clock: ``now()`` is ``time.perf_counter`` seconds."""

    def now(self) -> float:
        return time.perf_counter()

    def sleep(self, dt: float) -> None:
        if dt > 0:
            time.sleep(dt)


class ManualClock:
    """Deterministic logical tick clock for tests/simulation: time moves
    only via ``sleep``/``advance`` (and an optional fixed per-``now`` tick
    so busy-loops cannot live-lock a simulated run)."""

    def __init__(self, start: float = 0.0, tick_per_now: float = 0.0):
        self._t = float(start)
        self.tick_per_now = float(tick_per_now)

    def now(self) -> float:
        self._t += self.tick_per_now
        return self._t

    def sleep(self, dt: float) -> None:
        self.advance(dt)

    def advance(self, dt: float) -> None:
        if dt > 0:
            self._t += float(dt)


@dataclass
class RequestTiming:
    """The four tick stamps of one served request (−1 = not reached) and
    the scheduling cycle that served it."""
    arrival: float
    admit: float = -1.0
    dispatch: float = -1.0
    done: float = -1.0
    cycle: int = -1

    @property
    def queue_wait(self) -> float:
        return self.admit - self.arrival

    @property
    def decide(self) -> float:
        return self.dispatch - self.admit

    @property
    def forward(self) -> float:
        return self.done - self.dispatch

    @property
    def total(self) -> float:
        return self.done - self.arrival

    def phases(self) -> dict[str, float]:
        return {"queue_wait": self.queue_wait, "decide": self.decide,
                "forward": self.forward, "total": self.total}


def percentiles(values, pcts=PERCENTILES) -> dict[str, float]:
    """{"p50": …, "p95": …, "p99": …, "mean": …, "max": …} of ``values``."""
    arr = np.asarray(list(values), np.float64)
    if arr.size == 0:
        return {f"p{p}": float("nan") for p in pcts} | \
            {"mean": float("nan"), "max": float("nan")}
    out = {f"p{p}": float(np.percentile(arr, p)) for p in pcts}
    out["mean"] = float(arr.mean())
    out["max"] = float(arr.max())
    return out


class CycleTelemetry:
    """Per-cycle telemetry, one sample per non-empty scheduling cycle: the
    batch size the cycle's single (vmapped) control dispatch covered, and
    the cycle's three stages on the tick clock:

        decide   = decided − admit     (control decision + plan lookups)
        dispatch = dispatched − decided (scatter + every group's forward
                                         dispatch)
        fetch    = done − dispatched   (blocking output fetches + gathers)

    The :data:`SLOWEST_KEPT` slowest cycles (by the sum of their stages)
    are kept whole with their cycle number, so a stall names its stage in
    any run, traced or not.

    ``as_dict`` emits the per-cycle batch-size histogram, p50/p95 of each
    stage and the slowest cycles — the numbers that show the batched
    controller amortizing (cycle batch sizes ≫ 1 while decide-per-request
    falls). Deterministic under :class:`ManualClock`."""

    def __init__(self):
        self.batch_sizes: list[int] = []
        self.decide_ticks: list[float] = []
        self.dispatch_ticks: list[float] = []
        self.fetch_ticks: list[float] = []
        self._slowest: list[tuple[float, int, dict]] = []   # min-heap

    def record(self, cycle: int, batch_size: int, decide: float,
               dispatch: float, fetch: float) -> None:
        self.batch_sizes.append(int(batch_size))
        self.decide_ticks.append(float(decide))
        self.dispatch_ticks.append(float(dispatch))
        self.fetch_ticks.append(float(fetch))
        total = float(decide) + float(dispatch) + float(fetch)
        rec = {"cycle": int(cycle), "batch": int(batch_size),
               "decide": float(decide), "dispatch": float(dispatch),
               "fetch": float(fetch), "total": total}
        item = (total, len(self.batch_sizes), rec)   # index breaks ties
        if len(self._slowest) < SLOWEST_KEPT:
            heapq.heappush(self._slowest, item)
        elif total > self._slowest[0][0]:
            heapq.heapreplace(self._slowest, item)

    def histogram(self) -> dict[int, int]:
        out: dict[int, int] = {}
        for b in self.batch_sizes:
            out[b] = out.get(b, 0) + 1
        return dict(sorted(out.items()))

    def slowest(self) -> list[dict]:
        """The slowest cycles' records, slowest first."""
        return [rec for _, _, rec in sorted(self._slowest, reverse=True)]

    def as_dict(self) -> dict:
        dec = percentiles(self.decide_ticks)
        dis = percentiles(self.dispatch_ticks)
        fet = percentiles(self.fetch_ticks)
        per_req = [t / b for t, b in
                   zip(self.decide_ticks, self.batch_sizes)]
        return {"cycles": len(self.batch_sizes),
                "batch_hist": {str(k): v
                               for k, v in self.histogram().items()},
                "batch_mean": (float(np.mean(self.batch_sizes))
                               if self.batch_sizes else 0.0),
                "decide": {"p50": dec["p50"], "p95": dec["p95"]},
                "decide_per_request": percentiles(per_req),
                "dispatch": {"p50": dis["p50"], "p95": dis["p95"]},
                "fetch": {"p50": fet["p50"], "p95": fet["p95"]},
                "slowest": self.slowest()}


def summarize(timings: list[RequestTiming]) -> dict:
    """Aggregate served-request timings into the streaming SLO record:
    per-phase percentile blocks + sustained requests/sec."""
    if not timings:
        return {"served": 0, "sustained_rps": 0.0}
    span = max(t.done for t in timings) - min(t.arrival for t in timings)
    out: dict = {"served": len(timings),
                 "sustained_rps": len(timings) / max(span, 1e-9)}
    for phase in ("queue_wait", "decide", "forward", "total"):
        out[phase] = percentiles(getattr(t, phase) for t in timings)
    return out
