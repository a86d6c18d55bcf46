"""Front-end: requests served per scheduling cycle (``pump``) in the
window."""


def read(run):
    served = [c[2] for c in run.probes.cycles]
    return sum(served) / len(served) if served else None
