"""Step roofline: least HBM bytes of the decode-only steps (every weight
the step multiplies by, one embedding row per lane, and each distinct
resident token a live lane attends, shared documents once) over their
device time at the chip's peak bandwidth."""
from readings import traced_steps
from trace_reduce import busy_ns

UNIT = "%"


def read(rec):
    steps = traced_steps(rec, chunked=False)
    if not steps:
        return None
    least = sum(rec.shapes.decode_least_bytes(len(s.decode_rids),
                                              s.decode_kv_tokens)
                for s in steps)
    busy_s = sum(busy_ns(rec.trace, *s.span) for s in steps) * 1e-9
    if busy_s <= 0:
        return None
    return 100.0 * least / (busy_s * rec.peak["hbm_bytes_per_s"])
