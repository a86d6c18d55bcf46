"""Control plane: host time per cycle in the program's
``ge.control.decide`` span, less the time its ``ge.control.wait`` children
spend in the first read of the offload outputs (waiting for the device)."""
from perfbench.harness import spans as program


def read(run):
    spans = program.read(run)
    decides = program.started_in_window(spans, "ge.control.decide")
    if not decides:
        return None
    waits = program.named(spans, "ge.control.wait")
    host = [d.seconds - sum(w.seconds for w in program.inside(d, waits))
            for d in decides]
    return 1e3 * sum(host) / len(host)
