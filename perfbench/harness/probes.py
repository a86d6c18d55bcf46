"""Spans and counters the benchmark wraps around the program's layer calls.

The program carries no spans of its own on the served path, so the
benchmark installs them from its own files: it wraps the bound methods of
the objects it built (front-end, engine, controller) and, for the
host↔device layout, the two methods of ``PartitionPlan`` that move
features between the global and the block layout. Each span adds its
duration to a per-name total while the window is open; with tracing on it
also writes a ``jax.profiler.TraceAnnotation`` of the same name, so the
device trace can attribute idle gaps to what the host was doing.
"""
from __future__ import annotations

import functools
import glob
import os
import resource
import threading
import time
from collections import defaultdict

class Probes:
    """Per-name span totals and counts, plus the per-cycle and per-call
    records the metric readers need. Recording happens only while
    :attr:`active` is set (the measured window)."""

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.active = False
        self._lock = threading.Lock()
        self.total = defaultdict(float)
        self.count = defaultdict(int)
        self.cycles: list[tuple[float, float, int]] = []   # start, s, served
        self.plan_misses: list[float] = []                  # seconds each
        self.forwards: list[tuple] = []                     # flops records
        if annotate:
            from jax.profiler import TraceAnnotation
            self._annotation = TraceAnnotation
        self._patched: list[tuple[object, str, object]] = []

    def add(self, name: str, seconds: float) -> None:
        with self._lock:
            self.total[name] += seconds
            self.count[name] += 1

    def timed(self, name: str, fn, on_result=None):
        """``fn`` wrapped in the span ``name``; ``on_result(result, start,
        seconds)`` runs after each recorded call."""
        probes = self

        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            if not probes.active:
                return fn(*args, **kwargs)
            start = time.perf_counter()
            if probes.annotate:
                with probes._annotation(name):
                    out = fn(*args, **kwargs)
            else:
                out = fn(*args, **kwargs)
            seconds = time.perf_counter() - start
            probes.add(name, seconds)
            if on_result is not None:
                on_result(out, start, seconds)
            return out
        return wrapped

    def patch(self, owner, attr: str, name: str, on_result=None) -> bool:
        """Replace ``owner.attr`` by its spanned twin; False (and nothing
        patched) when the program no longer has that attribute."""
        fn = getattr(owner, attr, None)
        if fn is None:
            return False
        self._patched.append((owner, attr, owner.__dict__.get(attr)
                              if isinstance(owner, type) else None))
        setattr(owner, attr, self.timed(name, fn, on_result))
        return True

    def restore(self) -> None:
        """Undo the class-level patches (instance patches die with their
        objects)."""
        for owner, attr, original in reversed(self._patched):
            if isinstance(owner, type):
                setattr(owner, attr, original)
        self._patched.clear()

    def reset(self) -> None:
        with self._lock:
            self.total.clear()
            self.count.clear()
            self.cycles.clear()
            self.plan_misses.clear()
            self.forwards.clear()


class GcClock:
    """Python garbage-collector pauses per generation, from
    ``gc.callbacks``, while :attr:`active` is set."""

    def __init__(self):
        self.active = False
        self.pause = defaultdict(float)
        self.collections = defaultdict(int)
        self.longest = 0.0
        self._start = None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._start = time.perf_counter()
        elif self._start is not None:
            seconds = time.perf_counter() - self._start
            self._start = None
            if self.active:
                gen = int(info.get("generation", -1))
                self.pause[gen] += seconds
                self.collections[gen] += 1
                self.longest = max(self.longest, seconds)

    def summary(self) -> dict:
        return {f"gen{g}": {"collections": self.collections[g],
                            "pause_ms": self.pause[g] * 1e3}
                for g in sorted(self.collections)} | \
            {"longest_ms": self.longest * 1e3,
             "total_ms": sum(self.pause.values()) * 1e3}


class CompileCounter:
    """JAX compile events while :attr:`active` is set, from
    ``jax.monitoring``: how many programs were compiled or loaded from the
    persistent cache, the seconds it took, and every compile-path event by
    name (tracing and lowering included)."""

    BACKEND = "/jax/core/compile/backend_compile_duration"
    PREFIXES = ("/jax/core/compile/", "/jax/compilation_cache/")

    def __init__(self):
        self.active = False
        self.compiles = 0
        self.seconds = 0.0
        self.events: dict[str, int] = {}

    def reset(self) -> None:
        self.compiles, self.seconds, self.events = 0, 0.0, {}

    def __call__(self, event: str, duration: float, **_) -> None:
        if not self.active or not event.startswith(self.PREFIXES):
            return
        self.events[event] = self.events.get(event, 0) + 1
        if event == self.BACKEND:
            self.compiles += 1
            self.seconds += duration


def host_counters() -> dict[str, float]:
    """Process and machine counters that name a host stall: CPU time of
    this process, time its threads waited on a run queue, page faults,
    context switches, and the machine's stolen CPU time (all cumulative)."""
    ru = resource.getrusage(resource.RUSAGE_SELF)
    wait_ns = 0
    for path in glob.glob("/proc/self/task/*/schedstat"):
        try:
            with open(path) as f:
                wait_ns += int(f.read().split()[1])
        except (OSError, IndexError, ValueError):
            pass              # a thread that ended while we read
    with open("/proc/stat") as f:
        cpu = f.readline().split()
    steal = int(cpu[8]) if len(cpu) > 8 else 0
    return {"cpu_s": ru.ru_utime + ru.ru_stime,
            "runqueue_wait_s": wait_ns * 1e-9,
            "minor_faults": ru.ru_minflt, "major_faults": ru.ru_majflt,
            "voluntary_switches": ru.ru_nvcsw,
            "involuntary_switches": ru.ru_nivcsw,
            "machine_steal_s": steal / os.sysconf("SC_CLK_TCK")}


def counter_delta(before: dict, after: dict) -> dict:
    return {k: after[k] - before[k] for k in before}
