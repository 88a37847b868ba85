"""Model step: device time of one run of the compiled chunked-prefill
forward (`jit_chunked_prefill`, max_batch x prefill_chunk prompt
positions), averaged over its runs that start in the traced window.
A step that completes no prompt returns with this forward still
running, so device time per step mixes neighbouring steps; one
forward's run does not.  Its runs are the engine's
`engine/prefill_forwards`, counted in the trace."""
from program_trace import run_ms

UNIT = "ms"


def read(rec):
    if rec.trace is None:
        return None
    return run_ms(rec.trace.modules, "prefill", *rec.trace_window)
