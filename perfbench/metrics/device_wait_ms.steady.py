"""Device: host time per front-end cycle (``ge.frontend.cycle``) spent
blocked on the device, in the first read of the offload outputs
(``ge.control.wait``) and the fetch of each group's forward output
(``ge.transfer.fetch``)."""
from perfbench.harness import spans as program


def read(run):
    spans = program.read(run)
    cycles = program.started_in_window(spans, "ge.frontend.cycle")
    if not cycles:
        return None
    waits = program.named(spans, "ge.control.wait", "ge.transfer.fetch")
    blocked = sum(w.seconds for c in cycles for w in program.inside(c, waits))
    return 1e3 * blocked / len(cycles)
