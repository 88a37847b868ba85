"""Scheduler: 90th percentile of the wait from a request's due time to
the start of the first engine step that took it out of the queue."""
from window import percentile

UNIT = "ms"


def read(rec):
    waits = [t.admitted - t.due for t in rec.window_tracks()
             if t.admitted is not None]
    return percentile(waits, 90) * 1e3 if waits else None
