"""Host↔device: host time laying features out into the plan's device blocks
and the outputs back (``PartitionPlan.scatter``/``gather``), per request
answered."""


def read(run):
    n = len(run.results)
    t = run.probes.total["bench.scatter"] + run.probes.total["bench.gather"]
    return 1e3 * t / n if n and t > 0 else None
