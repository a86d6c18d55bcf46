"""Control plane: host time per cycle in ``ServingEngine.decide_entries``
(partition through the LRU, the vmapped offload and cost, plan lookup)."""


def read(run):
    n = run.probes.count.get("bench.decide", 0)
    return 1e3 * run.probes.total["bench.decide"] / n if n else None
