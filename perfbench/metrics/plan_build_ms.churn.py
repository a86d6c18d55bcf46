"""Plan build: host time of a plan-cache miss in ``ServingEngine._plan_for``
(halo plan from the decision and its forward's constants), per miss."""


def read(run):
    misses = run.probes.plan_misses
    return 1e3 * sum(misses) / len(misses) if misses else None
