"""Scheduler: output tokens emitted in the window per engine step
executed in it (idle polls excluded)."""
UNIT = "lanes"


def read(rec):
    steps = rec.window_steps()
    if not steps:
        return None
    return sum(s.emitted for s in steps) / len(steps)
