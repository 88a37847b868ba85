"""Model step: device-busy time inside the harness's `engine.step` spans
of steps that carried no prompt chunk, per step."""
from readings import step_device_ms

UNIT = "ms"


def read(rec):
    return step_device_ms(rec, chunked=False)
