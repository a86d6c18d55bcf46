"""Host runtime: Python garbage-collector pause time in the window, all
generations, from ``gc.callbacks``."""


def read(run):
    return run.gc_clock.summary()["total_ms"]
