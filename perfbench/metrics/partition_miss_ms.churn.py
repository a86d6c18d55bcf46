"""Control plane: host time of a partition-cache miss in the program's
``ge.control.partition`` span (topology key, LRU lookup, HiCut), per miss;
a miss is a span that encloses the partitioner call (``bench.partition``)."""
from perfbench.harness import spans as program


def read(run):
    spans = program.read(run)
    cuts = program.named(spans, program.PARTITIONER)
    misses = [p for p in program.started_in_window(
        spans, "ge.control.partition") if program.inside(p, cuts)]
    if not misses:
        return None
    return 1e3 * sum(p.seconds for p in misses) / len(misses)
