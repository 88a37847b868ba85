"""Shared arithmetic of the per-layer readers in `metrics/`.

Each reader is a file `metrics/<name>.py` with `UNIT` and
`read(rec) -> float | None`; the harness finds it by the metric's name
in BENCHMARK.json.  A reader that finds nothing to read returns None,
and the harness leaves the metric out of the result line.
"""
from __future__ import annotations

from trace_reduce import busy_ns, program_ns


def traced_steps(rec, chunked: bool | None = None) -> list:
    """Executed window steps that have a trace span; with `chunked`,
    only those that carried (True) or did not carry (False) prompt
    chunks."""
    if rec.trace is None:
        return []
    out = [s for s in rec.window_steps() if s.span is not None]
    if chunked is not None:
        out = [s for s in out if s.has_chunks == chunked]
    return out


def step_device_ms(rec, chunked: bool) -> float | None:
    """Mean device-busy time (ms) inside the spans of such steps."""
    steps = traced_steps(rec, chunked)
    if not steps:
        return None
    return sum(busy_ns(rec.trace, *s.span) for s in steps) / len(steps) / 1e6


def window_busy_ns(rec) -> float:
    return busy_ns(rec.trace, *rec.trace_window)


def program_per_step_ms(rec, program: str) -> float | None:
    steps = traced_steps(rec)
    if not steps:
        return None
    return program_ns(rec.trace, program, *rec.trace_window) / len(steps) / 1e6
