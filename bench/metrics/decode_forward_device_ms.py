"""Model step: device time of one run of the compiled decode forward
(`jit_decode`, one token for every lane), averaged over its runs that
start in the traced window.  A step that completes no prompt returns
with its prefill forward still running, so device time per step mixes
neighbouring steps; one forward's run does not.  Its runs are the
engine's `engine/decode_forwards`, counted in the trace."""
from program_trace import run_ms

UNIT = "ms"


def read(rec):
    if rec.trace is None:
        return None
    return run_ms(rec.trace.modules, "decode", *rec.trace_window)
