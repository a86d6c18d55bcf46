"""Forward kernel: the least time the chip could take for the forward
dispatches in the traced window (operations and bytes from their shapes,
``harness.flops.forward_call``, against the peaks table) over the device
time of the ``_forward_blocks*`` programs that started in it."""
from perfbench.harness import flops

PREFIX = "jit__forward_blocks"


def read(run):
    if not run.reduced:
        return None
    mods = [m for name, m in run.reduced["modules"].items()
            if name.startswith(PREFIX)]
    device_s = sum(m["seconds"] for m in mods)
    calls = [c for c in run.probes.forwards if run.t0 <= c[0] < run.t_end]
    if not calls or device_s <= 0:
        return None
    peak = run.peak()
    least = [flops.roofline_seconds(f, b, peak) for _, f, b in calls]
    run.notes["forward_roofline_bound"] = {
        kind: sum(t for t, k in least if k == kind)
        for kind in ("compute", "memory")}
    return 100.0 * sum(t for t, _ in least) / device_s
