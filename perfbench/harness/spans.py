"""The program's own profiler spans in a traced run.

The served path writes a ``jax.profiler.TraceAnnotation`` named ``ge.*`` at
each layer boundary (``repro.serve.frontend``, ``repro.serve.engine``,
``repro.core.api``), on the profiler's host clock, which the device planes
share. :func:`trace.load` keeps each host event's name and times, not its
stats, so the readers here take counts from elsewhere: a partition-cache
miss is a ``ge.control.partition`` span that encloses the benchmark's own
``bench.partition`` span (its partitioner wrapper, which only a miss
calls), and requests are counted from the served timings.

A metric counts the spans that start inside ``bench.window``; a span's
children are the spans inside its interval on the same host thread. On a
program without these spans every function here finds nothing, and the
readers return None.
"""
from __future__ import annotations

from typing import NamedTuple

from perfbench.harness import trace as tracing

PREFIX = "ge."
PARTITIONER = "bench.partition"


class Span(NamedTuple):
    start: float          # ns, profiler clock
    end: float
    name: str
    line: tuple[int, int]  # (plane, line): one host thread

    @property
    def seconds(self) -> float:
        return (self.end - self.start) * 1e-9


def host_spans(trace: dict) -> list[Span]:
    """Every ``ge.*`` host event, the window and the benchmark's
    partitioner span, in start order."""
    out = []
    for p, plane in enumerate(trace["planes"]):
        if not plane["name"].startswith("/host:"):
            continue
        for k, line in enumerate(plane["lines"]):
            for name, start, dur, *_ in line["events"]:
                if name.startswith(PREFIX) or name in (tracing.WINDOW,
                                                       PARTITIONER):
                    out.append(Span(start, start + dur, name, (p, k)))
    return sorted(out)


def window(spans: list[Span]) -> tuple[float, float] | None:
    for sp in spans:
        if sp.name == tracing.WINDOW:
            return sp.start, sp.end
    return None


def started_in_window(spans: list[Span], name: str) -> list[Span]:
    """The spans named ``name`` that start inside ``bench.window``."""
    win = window(spans)
    if win is None:
        return []
    lo, hi = win
    return [sp for sp in spans if sp.name == name and lo <= sp.start < hi]


def named(spans: list[Span], *names: str) -> list[Span]:
    return [sp for sp in spans if sp.name in names]


def inside(parent: Span, spans: list[Span]) -> list[Span]:
    """The spans inside ``parent``'s interval on its thread."""
    return [sp for sp in spans if sp.line == parent.line
            and parent.start <= sp.start and sp.end <= parent.end]


def device_gaps(trace: dict, lo: float, hi: float
                ) -> list[tuple[float, float]]:
    """The intervals of ``[lo, hi)`` in which no op ran on any device."""
    ops = []
    for plane in tracing.device_planes(trace):
        for line in plane["lines"]:
            if line["name"] == "XLA Ops":
                ops += [(s, s + d, n) for n, s, d, *_ in line["events"]]
    gaps, cursor = [], lo
    for s, e in tracing.union([(s, e) for s, e, _
                               in tracing.clip(ops, lo, hi)]):
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if cursor < hi:
        gaps.append((cursor, hi))
    return gaps


def program_idle_gaps(trace: dict, spans: list[Span]
                      ) -> list[tuple[str, float]] | None:
    """Device-idle seconds of the window under the innermost ``ge.*``
    span (:func:`trace.attribute`); what no such span covers goes to
    ``host.outside_spans``. None without a window, a device plane or a
    program span."""
    win = window(spans)
    program = [(sp.start, sp.end, sp.name) for sp in spans
               if sp.name.startswith(PREFIX)]
    if win is None or not program or not tracing.device_planes(trace):
        return None
    idle = tracing.attribute(device_gaps(trace, *win), program)
    return sorted(idle.items(), key=lambda kv: -kv[1])


def read(run) -> list[Span]:
    """The run's spans. The first call on a run whose program wrote spans
    also notes, for the window line, the program idle attribution and the
    front-end's slowest cycles (``CycleTelemetry``)."""
    raw = getattr(run, "raw_trace", None)
    spans = host_spans(raw) if raw else []
    if "slowest_cycles" not in run.notes and any(
            sp.name.startswith(PREFIX) for sp in spans):
        gaps = program_idle_gaps(raw, spans)
        if gaps is not None:
            run.notes["program_idle_gaps"] = gaps
        run.notes["slowest_cycles"] = run.frontend.cycles.as_dict()[
            "slowest"]
    return spans
