"""Decision kernel: device time per run of the controller's jitted offload
and cost program (``_jit_offload_and_cost_batch``, and its one-layout twin),
from the device trace."""

PREFIX = "jit__jit_offload_and_cost"


def read(run):
    if not run.reduced:
        return None
    mods = [m for name, m in run.reduced["modules"].items()
            if name.startswith(PREFIX)]
    calls = sum(m["calls"] for m in mods)
    return 1e3 * sum(m["seconds"] for m in mods) / calls if calls else None
