"""Device: share of the traced window in which no operation ran on the
chip (averaged over the chips used)."""
from readings import window_busy_ns

UNIT = "%"


def read(rec):
    if rec.trace is None:
        return None
    lo, hi = rec.trace_window
    return 100.0 * (1.0 - window_busy_ns(rec) / (hi - lo))
