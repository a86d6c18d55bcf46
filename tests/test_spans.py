"""The program's own profiler spans on the served path: two front-end
cycles traced on the CPU, read back with ``jax.profiler.ProfileData``.
Every cycle encloses one control decision; every decision looks its
layouts up in the partition cache and waits once for the device; the
``cycle`` stat joins a span to the requests it served; a repeated layout's
second lookup is a hit in both caches."""
import glob
import os

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.core import costs
from repro.core.api import GraphEdgeController
from repro.core.dynamic_graph import perturb_scenario, random_scenario
from repro.gnn.layers import gcn_init
from repro.serve import ServingEngine, StreamingFrontend, StreamRequest


def make_frontend(cross: bool):
    rng = np.random.default_rng(0)
    state = random_scenario(rng, 24, 18, 40)
    net = costs.default_network(rng, 24, 3)
    engine = ServingEngine(
        controller=GraphEdgeController(net=net, policy="greedy_jit"),
        params=gcn_init(jax.random.PRNGKey(0), [8, 6, 4]),
        mesh=Mesh(np.array(jax.devices()[:1]), ("servers",)))
    fe = StreamingFrontend(engine=engine, queue_depth=16, max_batch=4,
                           cross_topology=cross)
    return fe, state, perturb_scenario(rng, state, 0.3), rng


def ge_spans(trace_dir):
    """Every ``ge.*`` host event as (start, end, name, stats, line)."""
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    spans = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for k, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("ge."):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name, dict(ev.stats), (plane.name, k)))
    return sorted(spans)


def inside(outer, spans, name):
    s0, e0, _, _, line = outer
    return [sp for sp in spans if sp[2] == name and sp[4] == line
            and s0 <= sp[0] and sp[1] <= e0]


@pytest.mark.parametrize("cross", [False, True])
def test_served_path_spans_nest_and_join_their_requests(cross, tmp_path):
    fe, state, other, rng = make_frontend(cross)

    def req(st):
        x = rng.normal(size=(st.capacity, 8)).astype(np.float32)
        return StreamRequest(st, x)
    with jax.profiler.trace(str(tmp_path)):
        fe.submit(req(state))
        fe.submit(req(state))
        first = fe.pump()                 # one layout, twice: both miss
        fe.submit(req(state))
        fe.submit(req(other))
        second = fe.pump()                # the layout again: both hit
    spans = ge_spans(str(tmp_path))
    cycles = [sp for sp in spans if sp[2] == "ge.frontend.cycle"]
    assert len(cycles) == 2
    for cyc in cycles:
        decides = inside(cyc, spans, "ge.control.decide")
        assert len(decides) == 1
        parts = inside(decides[0], spans, "ge.control.partition")
        assert parts and all("hit" in sp[3] for sp in parts)
        assert inside(decides[0], spans, "ge.control.wait")
        assert inside(cyc, spans, "ge.forward.dispatch")
        assert inside(cyc, spans, "ge.transfer.fetch")
    # the cycle stat increases and is the cycle on each served timing
    numbers = [cyc[3]["cycle"] for cyc in cycles]
    assert numbers[0] < numbers[1]
    assert {r.timing.cycle for r in first} == {numbers[0]}
    assert {r.timing.cycle for r in second} == {numbers[1]}
    assert [cyc[3]["batch"] for cyc in cycles] == [2, len(second)]

    def hits(cyc, name):
        return [sp[3]["hit"] for sp in inside(cyc, spans, name)]
    assert hits(cycles[0], "ge.control.partition") == [0]
    assert hits(cycles[0], "ge.plan.lookup") == [0]
    assert 1 in hits(cycles[1], "ge.control.partition")
    assert 1 in hits(cycles[1], "ge.plan.lookup")
    assert {sp[2] for sp in spans} >= {
        "ge.frontend.admit", "ge.control.dispatch", "ge.control.unpack",
        "ge.plan.build", "ge.transfer.scatter", "ge.transfer.gather"}
