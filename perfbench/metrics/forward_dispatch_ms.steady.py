"""Host↔device: host time in the forward's dispatch
(``ge.forward.dispatch``: the feature upload and the enqueue), per request
dispatched in the window (a request's ``RequestTiming.dispatch`` inside
it)."""
from perfbench.harness import spans as program


def read(run):
    calls = program.started_in_window(program.read(run),
                                      "ge.forward.dispatch")
    served = sum(run.t0 <= r.timing.dispatch < run.t_end
                 for r in run.results)
    if not calls or not served:
        return None
    return 1e3 * sum(c.seconds for c in calls) / served
