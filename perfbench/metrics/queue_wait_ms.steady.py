"""Front-end: mean time from a request's due time to its admission into a
batch (``RequestTiming.admit``), over the requests answered in the window."""


def read(run):
    waits = [r.timing.admit - run.due[r.rid] for r in run.results
             if r.timing.done <= run.t_end]
    return 1e3 * sum(waits) / len(waits) if waits else None
