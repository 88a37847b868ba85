"""Sampler: device time of the compiled `sample_tokens` program in the
window, per engine step executed."""
from readings import program_per_step_ms

UNIT = "ms"


def read(rec):
    return program_per_step_ms(rec, "sample_tokens")
