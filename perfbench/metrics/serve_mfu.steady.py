"""Whole served step: the GCN's own FLOPs of the requests answered in the
window (``harness.flops.model_request``) per second, over the chip's bf16
peak."""
from perfbench.harness import flops


def read(run):
    widths = run.config["widths"]
    per_layout = {}
    total = 0.0
    for r in run.results:
        if r.timing.done <= run.t_end:
            li = int(run.stream.layout_of[r.rid])
            if li not in per_layout:
                lay = run.pool[li]
                active = int((lay.mask > 0).sum())
                per_layout[li] = flops.model_request(
                    active, int((lay.adj > 0).sum()) + active, widths)
            total += per_layout[li]
    if total <= 0:
        return None
    peak = run.peak()
    return 100.0 * total / run.seconds / (
        peak["bf16_flop_per_s"] * len(run.devices))
