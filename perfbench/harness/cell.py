"""One run of one cell: set-up, the measured window, the comparison that
decides ``correct``, and the metrics.

The served path is the program's own: ``StreamingFrontend.run_threaded``
over a ``ServingEngine`` whose controller partitions (HiCut, through its
topology LRU), offloads and prices every cycle in one vmapped call, builds
or reuses the halo plan, and dispatches the GCN forward. The benchmark
hands it prebuilt requests on an open-loop schedule and times each one
from when it was due.
"""
from __future__ import annotations

import gc
import json
import os
import pathlib
import shutil
import tempfile
import time

import numpy as np

from perfbench.harness import deployment, flops, reference, stats, traffic
from perfbench.harness import trace as tracing
from perfbench.harness.probes import (CompileCounter, GcClock, Probes,
                                      counter_delta, host_counters)

PERFBENCH = pathlib.Path(__file__).resolve().parents[1]


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def process_age() -> float:
    """Seconds since this process started (``/proc``, 10 ms ticks)."""
    with open("/proc/self/stat") as f:
        start_ticks = float(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def load_cell(root: pathlib.Path, workload: str) -> tuple[dict, dict, dict,
                                                          dict]:
    """(BENCHMARK.json, the workload entry, its configuration, its traffic)
    found by name."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    config_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((root / config_entry["file"]).read_text())
    spec = json.loads((root / "perfbench" / "traffic"
                       / f"{cell['traffic']}.json").read_text())
    return bench, cell, config, spec


def batch_pads(max_batch: int) -> list[int]:
    """The batch sizes the front-end pads a group of two or more to."""
    pads = set()
    for b in range(2, max_batch + 1):
        p = 1
        while p < b:
            p <<= 1
        pads.add(max(b, min(p, max_batch)))
    return sorted(pads)


class WindowAdmission:
    """Admits every request while the window is open and refuses what is
    still queued once :attr:`closed` is set, so a cell above its knee ends
    with its window instead of draining the backlog."""
    name = "window"

    def __init__(self, admit: str, reject: str):
        self._admit, self._reject = admit, reject
        self.closed = False

    def decide(self, entry, now, backlog, est_service) -> str:
        return self._reject if self.closed else self._admit

    def on_cycle(self, served, now) -> None:
        pass


class Run:
    """Set-up and state of one run: construction builds and warms up,
    :meth:`window` measures, :meth:`check` compares, :meth:`end_to_end`
    and :meth:`attribution` report."""

    def __init__(self, workload: str, seed: int, seconds: float, trace: bool,
                 root: pathlib.Path, require_tpu: bool = True):
        self.t_begin = time.perf_counter()
        self.age_begin = process_age()
        self.workload, self.seed, self.trace = workload, int(seed), trace
        self.root = pathlib.Path(root)
        self.bench, self.cell, self.config, self.spec = load_cell(
            self.root, workload)
        self.seconds = min(float(seconds), self.spec["trace_seconds"]) \
            if trace else float(seconds)
        self.shed = trace or self.spec["at_close"] == "shed"
        self.setup: dict[str, float] = {}
        self.notes: dict = {}
        self._tick = time.perf_counter()
        self._import_program(require_tpu)
        self._build_inputs()
        self._build_program()
        self._warm_up()

    # -- set-up ---------------------------------------------------------------
    def _lap(self, name: str) -> None:
        now = time.perf_counter()
        self.setup[name] = now - self._tick
        self._tick = now

    def _import_program(self, require_tpu: bool) -> None:
        from repro.launch import enable_compile_cache
        enable_compile_cache()
        import jax
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        self.jax = jax
        self._lap("imports_s")
        devices = jax.devices()
        chips = int(self.cell["chips"])
        if require_tpu and (devices[0].platform != "tpu"
                            or len(devices) < chips):
            raise NoChip(f"{self.workload} needs {chips} TPU chip(s); JAX "
                         f"found {len(devices)} {devices[0].platform} "
                         f"device(s)")
        self.devices = devices[:chips]
        self._lap("devices_s")

    def _build_inputs(self) -> None:
        cfg, spec = self.config, self.spec
        self.net = deployment.config_network(cfg)
        self.pool = deployment.layout_pool(cfg, spec["layouts"])
        self.stream = traffic.make_stream(spec, self.seed, self.seconds)
        self.features = traffic.feature_pool(spec, self.seed,
                                             cfg["capacity"],
                                             cfg["widths"][0])
        self._lap("inputs_s")
        jax = self.jax
        import jax.numpy as jnp
        widths = cfg["widths"]

        def init(key):
            keys = jax.random.split(key, len(widths) - 1)
            out = []
            for k, f_in, f_out in zip(keys, widths[:-1], widths[1:]):
                bound = float(np.sqrt(6.0 / (f_in + f_out)))   # Glorot
                out.append({"w": jax.random.uniform(
                    k, (f_in, f_out), jnp.float32, -bound, bound)})
            return out
        key_seed = int(traffic.stream(self.seed, "weights").integers(2**31))
        self.params = jax.jit(init)(jax.random.PRNGKey(key_seed))
        jax.block_until_ready(self.params)
        self._lap("weights_s")

    def _build_program(self) -> None:
        import jax.numpy as jnp
        from jax.sharding import Mesh

        from repro.core import costs
        from repro.core.api import GraphEdgeController
        from repro.core.dynamic_graph import GraphState
        from repro.serve import ServingEngine
        cfg = self.config
        n = self.net
        edge_net = costs.EdgeNetwork(
            server_pos=jnp.asarray(n.server_pos), f_k=jnp.asarray(n.f_k),
            capacity=jnp.asarray(n.capacity), B_im=jnp.asarray(n.B_im),
            B_kl=jnp.asarray(n.B_kl), P_i=jnp.asarray(n.P_i),
            P_k=jnp.asarray(n.P_k), eta_kl=jnp.asarray(n.eta_kl),
            sigma2=n.sigma2, rho0=n.rho0, h0=n.h0, zeta_im=n.zeta_im,
            zeta_kl=n.zeta_kl)
        g = cfg["cost_model"]
        gnn = costs.GNNCostParams(
            mu=g["mu"], theta=g["theta"], phi=g["phi"],
            layer_sizes_kb=tuple(g["layer_sizes_kb"]),
            update_norm_bits=g["update_norm_bits"])
        self.states = [GraphState(lay.mask, lay.pos, lay.adj, lay.task_kb)
                       for lay in self.pool]
        controller = GraphEdgeController(
            net=edge_net, policy=cfg["policy"],
            partitioner=cfg["partitioner"], gnn=gnn)
        mesh = Mesh(np.array(self.devices), ("servers",))
        self.engine = ServingEngine(controller=controller,
                                    params=self.params, mesh=mesh,
                                    axis="servers")
        self.probes = Probes(annotate=self.trace)
        self._instrument_engine()
        self.open_frontend()
        self._lap("program_s")

    def open_frontend(self) -> None:
        """A fresh front-end (empty queue, zeroed counters) over the warm
        engine, with the requests of :attr:`stream`."""
        from repro.serve import (MonotonicClock, StreamingFrontend,
                                 StreamRequest)
        from repro.serve.frontend import ADMIT, REJECT
        fe = self.spec["frontend"]
        self.admission = WindowAdmission(ADMIT, REJECT)
        self.frontend = StreamingFrontend(
            engine=self.engine, queue_depth=fe["queue_depth"],
            max_batch=fe["max_batch"], admission=self.admission,
            clock=MonotonicClock(), cross_topology=fe["cross_topology"])
        st = self.stream
        self.requests = [StreamRequest(self.states[st.layout_of[i]],
                                       self.features[st.feature_of[i]],
                                       rid=i) for i in range(len(st))]
        p = self.probes

        def on_pump(out, start, seconds):
            p.cycles.append((start, seconds, len(out)))
        p.patch(self.frontend, "submit", "bench.submit")
        p.patch(self.frontend, "pump", "bench.pump", on_pump)
        p.patch(self.frontend.clock, "sleep", "bench.idle")

    def retarget(self, rate: float) -> None:
        """Offer ``rate`` requests per second in the next window (the knee
        sweep): a new stream and a new front-end, the same warm engine."""
        self.spec = dict(self.spec, rate_rps=float(rate))
        self.stream = traffic.make_stream(self.spec, self.seed, self.seconds)
        self.open_frontend()

    def close(self) -> None:
        """Detach the compile listener from the process."""
        self.jax.monitoring.unregister_event_duration_listener(self.compiles)

    def _instrument_engine(self) -> None:
        """Spans around the engine's and the controller's calls, and a
        record of every forward dispatch's shapes for the roofline."""
        from repro.gnn.distributed import resolve_aggregate
        p = self.probes
        engine = self.engine
        widths = self.config["widths"]

        def on_plan(out, start, seconds):
            if not out[1]:
                p.plan_misses.append(seconds)

        def plan_for(decision):
            entry, hit = orig_plan_for(decision)
            if not getattr(entry, "_perfbench", False):
                entry.forward = spanned_forward(entry.forward, entry.plan,
                                                False)
                entry._perfbench = True
            return entry, hit

        def spanned_forward(forward, plan, per_member):
            mode = resolve_aggregate(plan, engine.aggregate)

            def call(x_blocks, params):
                if p.active:
                    batch = x_blocks.shape[1] if x_blocks.ndim == 4 else 1
                    p.forwards.append((time.perf_counter(),)
                                      + flops.forward_call(
                        mode, batch, plan.num_devices, plan.block,
                        plan.ext_cols, plan.max_degree, widths, per_member))
                return forward(x_blocks, params)
            return call

        def batched(entry):
            return spanned_forward(orig_batched(entry), entry.plan, False)

        def cross(entries):
            plans, forward = orig_cross(entries)
            return plans, spanned_forward(forward, plans[0], True)

        orig_batched = engine.batched_forward
        orig_cross = engine.cross_batched_forward
        orig_plan_for = engine._plan_for
        engine.batched_forward = batched
        engine.cross_batched_forward = cross
        engine._plan_for = plan_for
        p.patch(engine, "decide_entries", "bench.decide")
        p.patch(engine, "_plan_for", "bench.plan", on_plan)
        p.patch(engine, "batched_forward", "bench.batch_prep")
        p.patch(engine, "cross_batched_forward", "bench.batch_prep")
        engine.controller.partitioner = _SpannedPartitioner(
            engine.controller.partitioner, p)
        self.compiles = CompileCounter()
        self.jax.monitoring.register_event_duration_secs_listener(
            self.compiles)

    def _warm_up(self) -> None:
        """Compile (or load from the persistent cache) every program the
        window can run, once: the decide batch sizes, each layout's forward
        at its own shape, the batched forward where a layout can repeat
        within a batch, and the cross-topology forward of every shape
        bucket at every batch pad."""
        self.compiles.active = True
        engine, params = self.engine, self.params
        fe = self.spec["frontend"]
        max_batch = fe["max_batch"]
        order = list(dict.fromkeys(int(i) for i in self.stream.layout_of))
        order += [i for i in range(len(self.states)) if i not in order]
        states = [self.states[i] for i in order]
        widest = min(max_batch, len(states))
        for b in range(1, widest + 1):
            engine.decide_entries(states[:b])
        entries = {}
        for i in range(0, len(states), widest):
            for _, entry, _ in engine.decide_entries(states[i:i + widest]):
                entries[entry.key] = entry
        x0 = self.features[0]
        pads = batch_pads(max_batch)
        repeats = self.spec["layouts"]["visit"] == "balanced"
        outs = []
        for entry in entries.values():
            outs.append(entry.forward(entry.plan.scatter(x0), params))
            if repeats:
                for pad in pads:
                    fwd = engine.batched_forward(entry)
                    outs.append(fwd(entry.plan.scatter_batch([x0], pad),
                                    params))
        if fe["cross_topology"]:
            from repro.gnn.distributed import scatter_multi
            for entry in entries.values():
                engine.entry_bucket(entry)          # settle the quanta
            buckets: dict[tuple, list] = {}
            for entry in entries.values():
                buckets.setdefault(engine.entry_bucket(entry),
                                   []).append(entry)
            for members in buckets.values():
                if len(members) < 2:
                    continue
                for pad in pads:
                    padded = (members[:pad]
                              + [members[-1]] * max(0, pad - len(members)))
                    plans, fwd = engine.cross_batched_forward(padded)
                    outs.append(fwd(scatter_multi(plans, [x0] * pad, pad),
                                    params))
        for out in outs:
            np.asarray(out)
        self.compiles.active = False
        self.setup["warmup_programs"] = self.compiles.compiles
        self.setup["warmup_compile_s"] = self.compiles.seconds
        self.compiles.reset()
        self._lap("warmup_s")

    # -- the measured window --------------------------------------------------
    def window(self) -> None:
        """Offer the stream open-loop for ``seconds`` and collect every
        answer; with ``shed`` the backlog left at the close is refused."""
        jax = self.jax
        trace_dir = None
        if self.trace:
            trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        gc.collect()
        st, requests = self.stream, self.requests
        count = len(st)
        due = [0.0] * count
        late = [0.0] * count
        offsets = [float(v) for v in st.offsets]
        lead = 0.05
        t0 = time.perf_counter() + lead
        t_end = t0 + self.seconds
        self.setup_s = self.age_begin + (t0 - self.t_begin)
        annotation = jax.profiler.TraceAnnotation if self.trace else None
        admission = self.admission
        sleep, clock = time.sleep, time.perf_counter

        def produce():
            span = annotation("bench.window") if annotation else None
            rem = t0 - clock()
            if rem > 0:
                sleep(rem)
            if span:
                span.__enter__()
            for i in range(count):
                d = t0 + offsets[i]
                due[i] = d
                rem = d - clock()
                while rem > 0:
                    sleep(rem)
                    rem = d - clock()
                late[i] = -rem
                yield -1.0, requests[i]
            rem = t_end - clock()
            if rem > 0:
                sleep(rem)
            if span:
                span.__exit__(None, None, None)
            if self.shed:
                admission.closed = True

        from repro.gnn.distributed import PartitionPlan as plan_cls
        self.probes.reset()
        self.probes.patch(plan_cls, "scatter", "bench.scatter")
        self.probes.patch(plan_cls, "gather", "bench.gather")
        self.compiles.reset()
        self.gc_clock = GcClock()
        gc.callbacks.append(self.gc_clock)
        for probe in (self.probes, self.gc_clock, self.compiles):
            probe.active = True
        host_before = host_counters()
        results = self.frontend.run_threaded(produce())
        t_closed = time.perf_counter()
        self.host = counter_delta(host_before, host_counters())
        for probe in (self.probes, self.gc_clock, self.compiles):
            probe.active = False
        self.probes.restore()
        gc.callbacks.remove(self.gc_clock)
        self.results = results
        self.due, self.late = np.array(due), np.array(late)
        self.t0, self.t_end, self.t_closed = t0, t_end, t_closed
        self.memory_peak = max(int((d.memory_stats() or {}).get(
            "peak_bytes_in_use", 0)) for d in self.devices)
        self.reduced = None
        if trace_dir:
            jax.profiler.stop_trace()
            try:
                self.raw_trace = tracing.load(trace_dir)
                self.reduced = tracing.reduce(self.raw_trace)
            finally:
                shutil.rmtree(trace_dir, ignore_errors=True)

    # -- correctness ----------------------------------------------------------
    def check(self, control: bool = False) -> dict:
        """Every answer against the plain references: the partition against
        HiCut, the assignment against GM, its cost terms against Eqs.
        (3)–(14), the GCN outputs against the float64 forward. Each number
        beside its limit.

        With ``control`` the reference computed one precision step below
        the configuration's takes the program's place (:meth:`_control`),
        and has to come out as not correct."""
        from perfbench.configs import gcn_reference
        limits = self.config["limits"]
        weights = [np.asarray(layer["w"], np.float64)
                   for layer in self.params]
        st = self.stream
        xw = gcn_reference.project(weights[0], self.features)
        refs: dict[int, tuple] = {}
        controls: dict[int, tuple] = {}
        worst = dict.fromkeys(("out_rel_rms", "out_rel_max",
                               "cost_local_rel", "cost_transfer_rel"), 0.0)
        cut_bad = assign_bad = bad = 0
        for res in self.results:
            li = int(st.layout_of[res.rid])
            lay = self.pool[li]
            if li not in refs:
                sub = reference.hicut(lay)
                srv = reference.greedy(lay, sub, self.net)
                refs[li] = (sub, srv, reference.system_cost(
                    lay, srv, self.net, self.config["cost_model"]),
                    gcn_reference.gcn_from_projection(weights, xw, lay.adj,
                                                      lay.mask))
                if control:
                    controls[li] = self._control(lay, weights)
            sub, srv, cost, out = refs[li]
            fi = int(st.feature_of[res.rid])
            if control:
                got_sub, got_srv, got_cost, got_out = controls[li]
                got_out = got_out[fi]
            else:
                dec = res.decision
                got_sub = np.asarray(dec.partition.subgraph, np.int64)
                got_srv = np.asarray(dec.servers, np.int64)
                got_cost = _program_cost(dec.cost)
                got_out = res.output
            live = lay.mask > 0
            got = np.asarray(got_out, np.float64)[live]
            want = out[fi][live]
            gaps = {
                "out_rel_rms": float(np.sqrt(np.mean((got - want) ** 2))
                                     / np.sqrt(np.mean(want ** 2))),
                "out_rel_max": float(np.abs(got - want).max()
                                     / np.abs(want).max()),
                "cost_local_rel": abs(got_cost["local"] - cost["local"])
                / abs(cost["local"]),
                "cost_transfer_rel": abs(got_cost["transfer"]
                                         - cost["transfer"])
                / max(abs(cost["transfer"]), 1e-300)}
            wrong = [not np.array_equal(got_sub, sub),
                     not np.array_equal(got_srv, srv)]
            cut_bad += wrong[0]
            assign_bad += wrong[1]
            for k, v in gaps.items():
                worst[k] = max(worst[k], v)
                wrong.append(v > limits[k])
            bad += any(wrong)
        stats_ = self.frontend.stats
        shed = stats_.rejected.get("admission", 0)
        missing = len(self.stream) - len(self.results)
        if self.shed:
            missing -= shed
        self.failed = missing + bad
        values = {**worst, "cut_mismatch": cut_bad,
                  "assign_mismatch": assign_bad, "missing": missing,
                  "ledger_gap": 0 if stats_.conservation_ok else 1}
        return {k: {"value": v, "limit": limits[k]}
                for k, v in values.items()}

    def _control(self, lay, weights) -> tuple:
        """The reference one precision step below the configuration's:
        elementwise arithmetic on bfloat16 inputs (positions, task sizes,
        the network's rates and powers), matmul operands in float8_e4m3fn
        (the GCN's, and the task bits of the cross-server einsum, scaled to
        the type's range)."""
        import dataclasses

        import ml_dtypes

        from perfbench.configs import gcn_reference

        def bf16(x):
            return np.asarray(x, np.float32).astype(
                ml_dtypes.bfloat16).astype(np.float32)

        def fp8_scaled(v):
            scale = 448.0 / max(float(np.abs(v).max()), 1e-300)
            return np.asarray(v * scale, np.float32).astype(
                ml_dtypes.float8_e4m3fn).astype(np.float64) / scale
        net = dataclasses.replace(self.net, **{
            f.name: bf16(getattr(self.net, f.name))
            for f in dataclasses.fields(self.net)
            if isinstance(getattr(self.net, f.name), np.ndarray)})
        low = deployment.Layout(lay.mask, bf16(lay.pos), lay.adj,
                                bf16(lay.task_kb))
        sub = reference.hicut(low)
        srv = reference.greedy(low, sub, net)
        cost = reference.system_cost(low, srv, net,
                                     self.config["cost_model"],
                                     transfer_operand=fp8_scaled)
        out = gcn_reference.gcn_forward(weights, self.features, lay.adj,
                                        lay.mask, ml_dtypes.float8_e4m3fn)
        return sub, srv, cost, out

    # -- metrics --------------------------------------------------------------
    def peak(self) -> dict:
        """This chip's row of the peaks table; a chip not in it is an
        error."""
        table = json.loads((PERFBENCH / "peaks.json").read_text())
        kind = self.devices[0].device_kind
        if kind not in table:
            raise KeyError(f"device {kind!r} is not in perfbench/peaks.json")
        return table[kind]

    def latencies(self) -> np.ndarray:
        """Seconds from due to answered, for every answered request."""
        return np.array([r.timing.done - self.due[r.rid]
                         for r in self.results])

    def answered_in_window(self) -> int:
        return sum(r.timing.done <= self.t_end for r in self.results)

    def end_to_end(self) -> dict:
        lat = self.latencies()
        return {
            "latency_p50_ms": stats.percentile(lat, 50) * 1e3,
            "latency_p95_ms": stats.percentile(lat, 95) * 1e3,
            "throughput_rps": stats.rate(self.answered_in_window(),
                                         self.seconds),
            "setup_s": self.setup_s,
        }

    def attribution(self) -> dict:
        """The per-run line that names what the host did in the window."""
        cyc = [c[1] for c in self.probes.cycles]
        med = float(np.median(cyc)) if cyc else 0.0
        shed = self.frontend.stats.rejected.get("admission", 0)
        return {
            "workload": self.workload, "seed": self.seed,
            "window_s": self.seconds, "offered_rps": self.spec["rate_rps"],
            "due": len(self.stream), "answered": len(self.results),
            "answered_in_window": self.answered_in_window(),
            "left_queued_at_close": shed,
            "close_to_return_s": self.t_closed - self.t_end,
            "cycles": len(cyc), "cycle_median_ms": med * 1e3,
            "cycle_longest_ms": max(cyc, default=0.0) * 1e3,
            "cycles_over_2x_median": int(sum(c > 2 * med for c in cyc)),
            "generator_late_ms": {
                "p99": stats.percentile(self.late, 99) * 1e3,
                "max": float(self.late.max()) * 1e3},
            "gc": self.gc_clock.summary(),
            "host": self.host,
            "window_compiles": self.compiles.compiles,
            "window_jax_events": self.compiles.events,
            "setup": {"setup_s": self.setup_s, **self.setup},
        }


def _program_cost(sc) -> dict[str, float]:
    """The program's ``SystemCost`` split as :func:`reference.system_cost`
    splits it."""
    local = sum(float(np.sum(np.asarray(t, np.float64)))
                for t in (sc.t_up, sc.t_com, sc.i_up, sc.i_gnn))
    transfer = sum(float(np.sum(np.asarray(t, np.float64)))
                   for t in (sc.t_tran, sc.i_com))
    return {"local": local, "transfer": transfer,
            "total": local + transfer}


class _SpannedPartitioner:
    """The controller's partitioner with the ``bench.partition`` span
    around each call (the controller calls it on a topology-cache miss)."""

    def __init__(self, inner, probes: Probes):
        self._inner = inner
        self.name = inner.name
        self._call = probes.timed("bench.partition", inner.__call__)

    def __call__(self, state):
        return self._call(state)

    def __getattr__(self, attr):
        return getattr(self._inner, attr)
