"""Reduction of a profiler trace to device busy time, per-op device time
and idle gaps attributed to the benchmark's host spans.

A trace is read once into plain data (:func:`load`): planes, their lines,
and events as ``[name, start_ns, duration_ns]``. On a TPU the device planes
are named ``/device:TPU:<i>`` and carry an ``XLA Modules`` line (one event
per jitted program run, named ``jit_<fn>(<fingerprint>)``) and an ``XLA
Ops`` line (one event per HLO op). The benchmark's spans are host events
whose names start with ``bench.``; ``bench.window`` marks the traced
window. :func:`reduce` works on that plain data only, so it is tested on a
small recorded trace kept with the benchmark.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

WINDOW = "bench.window"
OUTSIDE = "host.outside_spans"
_SUFFIX = re.compile(r"\.\d+$")
_FINGERPRINT = re.compile(r"\(\d+\)$")


def load(trace_dir: str) -> dict:
    """The newest ``*.xplane.pb`` under ``trace_dir`` as plain data."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name, "events": [
                [ev.name, float(ev.start_ns), float(ev.duration_ns)]
                for ev in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def op_name(raw: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` or ``fusion.12`` → ``fusion``."""
    head = raw.split(" = ", 1)[0].split(" ", 1)[0].lstrip("%")
    return _SUFFIX.sub("", head)


def module_name(raw: str) -> str:
    """``jit__forward_blocks(8280344382199864068)`` → ``jit__forward_blocks``."""
    return _FINGERPRINT.sub("", raw)


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Sorted, merged ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(events, lo: float, hi: float) -> list[tuple[float, float, str]]:
    """Events ``(start, end, name)`` cut to the window ``[lo, hi)``."""
    return [(max(s, lo), min(e, hi), n) for s, e, n in events
            if e > lo and s < hi]


def _line(plane: dict, name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def device_planes(trace: dict) -> list[dict]:
    return [p for p in trace["planes"]
            if p["name"].startswith("/device:TPU:")]


def host_spans(trace: dict) -> list[tuple[float, float, str]]:
    """Every ``bench.*`` host event as ``(start, end, name)``."""
    spans = []
    for plane in trace["planes"]:
        if not plane["name"].startswith("/host:"):
            continue
        for line in plane["lines"]:
            for name, start, dur, *_ in line["events"]:
                if name.startswith("bench."):
                    spans.append((start, start + dur, name))
    return spans


def attribute(gaps: list[tuple[float, float]],
              spans: list[tuple[float, float, str]]) -> dict[str, float]:
    """Seconds of the gaps under each span: at every instant the innermost
    (shortest) covering span takes the time; uncovered time goes to
    :data:`OUTSIDE`. One sweep over the sorted boundaries; a span of no
    length covers nothing (the device's buffer allocations are such)."""
    marks = []
    for k, (s, e, _) in enumerate(spans):
        if e > s:
            marks += [(s, 1, k), (e, -1, k)]
    for s, e in gaps:
        marks += [(s, 2, -1), (e, -2, -1)]
    marks.sort()
    active: dict[int, float] = {}
    depth, prev = 0, None
    out: dict[str, float] = {}
    for t, kind, k in marks:
        if depth > 0 and prev is not None and t > prev:
            if active:
                name = spans[min(active, key=active.get)][2]
            else:
                name = OUTSIDE
            out[name] = out.get(name, 0.0) + (t - prev) * 1e-9
        prev = t
        if kind == 1:
            active[k] = spans[k][1] - spans[k][0]
        elif kind == -1:
            active.pop(k, None)
        else:
            depth += 1 if kind == 2 else -1
    return out


def reduce(trace: dict, top: int = 10) -> dict | None:
    """Busy seconds (mean over device planes), the traced window's length,
    per-program calls and device seconds (each run counted whole if it
    starts in the window), per-op device self seconds (summing to busy),
    and the idle gaps attributed to host spans. None when the trace has no
    window or no device plane."""
    windows = [(s, e) for s, e, n in host_spans(trace) if n == WINDOW]
    devices = device_planes(trace)
    if not windows or not devices:
        return None
    lo, hi = windows[0]
    busy_per_device = []
    all_busy = []
    op_time: dict[str, float] = {}
    modules: dict[str, list] = {}
    for plane in devices:
        mods = sorted((s, s + d, module_name(n))
                      for n, s, d, *_ in _line(plane, "XLA Modules"))
        for s, e, n in mods:
            if lo <= s < hi:      # a program run counts whole, by its start
                entry = modules.setdefault(n, [0, 0.0])
                entry[0] += 1
                entry[1] += (e - s) * 1e-9
        ops = sorted(clip([(s, s + d, n) for n, s, d, *_
                           in _line(plane, "XLA Ops")], lo, hi))
        busy = union([(s, e) for s, e, _ in ops])
        busy_per_device.append(sum(e - s for s, e in busy) * 1e-9)
        all_busy.extend(busy)
        mod_starts = [m[0] for m in mods]
        named = []
        for s, e, n in ops:
            k = bisect.bisect_right(mod_starts, s) - 1
            owner = mods[k][2] if k >= 0 and mods[k][1] >= e else "?"
            named.append((s, e, f"{owner}:{op_name(n)}"))
        # self time: an op that encloses others (a while loop and its body)
        # keeps only the time no inner op covers
        for key, sec in attribute(busy, named).items():
            op_time[key] = op_time.get(key, 0.0) + sec
    gaps, cursor = [], lo
    for s, e in union(all_busy):
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if cursor < hi:
        gaps.append((cursor, hi))
    spans = [sp for sp in host_spans(trace) if sp[2] != WINDOW]
    idle = attribute(gaps, spans)
    return {
        "busy_s": sum(busy_per_device) / len(busy_per_device),
        "window_s": (hi - lo) * 1e-9,
        "modules": {k: {"calls": v[0], "seconds": v[1]}
                    for k, v in sorted(modules.items())},
        "device_ops": sorted(op_time.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(idle.items(), key=lambda kv: -kv[1])[:top],
    }
