"""``correct`` comes out false when the timed path is broken underneath,
and under the control; it comes out true on the program as it is.

These drive the harness on the CPU (its look for a chip skipped) through
the program's real served path at a small size, with one fault planted
per window."""
import dataclasses
import json

import numpy as np
import pytest


def _window(run, patch=None):
    run.retarget(40.0)
    undo = patch(run) if patch else None
    try:
        run.window()
    finally:
        if undo:
            undo()
    checks = run.check()
    return checks, all(c["value"] <= c["limit"] for c in checks.values())


def _alter_answer(run):
    """One request's outputs scaled by 5% where the plan gathers them."""
    from repro.gnn.distributed import PartitionPlan
    original = PartitionPlan.gather
    calls = []

    def gather(self, blocks):
        out = original(self, blocks)
        calls.append(1)
        return out * 1.05 if len(calls) == 3 else out
    PartitionPlan.gather = gather
    return lambda: setattr(PartitionPlan, "gather", original)


def _decisions(run, change):
    ctl = run.engine.controller
    original = ctl.step_batch

    def step_batch(states):
        return [change(d) for d in original(states)]
    ctl.step_batch = step_batch
    return lambda: delattr(ctl, "step_batch")


def _swap_servers(run):
    """Two users of different servers swap places in every decision."""
    def change(d):
        srv = np.array(d.servers)
        on = np.nonzero(srv >= 0)[0]
        a = on[0]
        b = on[np.nonzero(srv[on] != srv[a])[0][0]]
        srv[a], srv[b] = srv[b], srv[a]
        return dataclasses.replace(d, assignment=dataclasses.replace(
            d.assignment, servers=srv))
    return _decisions(run, change)


def _scale_upload(run):
    """Every decision reports upload times (Eq. 4) 1% too high."""
    def change(d):
        return dataclasses.replace(d, cost=d.cost._replace(
            t_up=d.cost.t_up * 1.01))
    return _decisions(run, change)


def _drop_transfer_energy(run):
    """Every decision leaves out the server-to-server energy (Eq. 8)."""
    def change(d):
        return dataclasses.replace(d, cost=d.cost._replace(
            i_com=d.cost.i_com * 0.0))
    return _decisions(run, change)


def _merge_subgraphs(run):
    """The partitioner merges subgraph 1 into subgraph 0."""
    ctl = run.engine.controller
    inner = ctl.partitioner

    class Merged:
        name = inner.name

        def __call__(self, state):
            part = inner(state)
            sub = np.array(part.subgraph)
            sub[sub == 1] = 0
            return dataclasses.replace(part, subgraph=sub)
    ctl.invalidate_partitions()
    ctl.partitioner = Merged()

    def undo():
        ctl.partitioner = inner
        ctl.invalidate_partitions()
    return undo


def _lose_request(run):
    """The front-end accepts one request and never queues it."""
    fe = run.frontend
    original = fe.submit
    seen = []

    def submit(req):
        seen.append(1)
        return True if len(seen) == 5 else original(req)
    fe.submit = submit
    return lambda: None


def test_program_as_it_is_is_correct(steady_run):
    checks, ok = _window(steady_run)
    assert ok, json.dumps(checks)
    assert checks["out_rel_rms"]["value"] < 1e-5


def test_control_is_not_correct(steady_run):
    _window(steady_run)
    checks = steady_run.check(control=True)
    for number in ("out_rel_rms", "out_rel_max", "cost_local_rel",
                   "cost_transfer_rel"):
        assert checks[number]["value"] > checks[number]["limit"], number


@pytest.mark.parametrize("fault, number", [
    (_alter_answer, "out_rel_rms"),
    (_swap_servers, "assign_mismatch"),
    (_scale_upload, "cost_local_rel"),
    (_drop_transfer_energy, "cost_transfer_rel"),
    (_merge_subgraphs, "cut_mismatch"),
    (_lose_request, "missing"),
])
def test_fault_is_not_correct(steady_run, fault, number):
    checks, ok = _window(steady_run, fault)
    assert not ok
    assert checks[number]["value"] > checks[number]["limit"], \
        json.dumps(checks)
