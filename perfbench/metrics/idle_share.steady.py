"""Device: share of the traced window in which no operation ran on the
chip (1 − busy / window, busy the union of op intervals)."""


def read(run):
    r = run.reduced
    if not r or r["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - r["busy_s"] / r["window_s"])
