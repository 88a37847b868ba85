"""Window arithmetic: what the run records, and the end-to-end metrics
computed from it.  Everything here is plain Python over host-clock
timestamps, so it is tested on synthetic records.

Definitions (all over every sample in the window, none a median of
chunks):

  ttft       first-token time minus due time (open loop) or submit
             time (closed loop), for every request due in the window;
             one that never got a token counts with its wait until the
             end of the grace period, a lower bound of its ttft
  itl        every gap between successive tokens of one request whose
             later token falls in the window; their mean and p95
  tok_per_s  output tokens emitted in the window / window seconds
"""
from __future__ import annotations

import dataclasses
import math


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) with linear interpolation between
    order statistics (numpy's default method)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


@dataclasses.dataclass
class Track:
    """One request as the harness saw it."""
    rid: int
    due: float                  # when it was due (open) or sent (closed)
    submitted: float            # when the harness handed it to the engine
    prompt_len: int
    max_new_tokens: int
    sampled: bool
    document: int = -1
    in_window: bool = False     # due inside [t_start, t_end)
    admitted: float | None = None   # start of the first step that took it
    tokens: list = dataclasses.field(default_factory=list)  # wall times
    done: float | None = None


@dataclasses.dataclass
class Step:
    """One executed engine step, as the harness timed it."""
    t0: float
    t1: float
    kind: str                   # the engine's event kind
    chunks: tuple = ()          # ((rid, n_tokens), ...) prompt chunks
    decode_rids: tuple = ()     # lanes that decoded one token
    emitted: int = 0            # tokens the harness saw appear
    chunk_starts: tuple = ()    # prompt position each chunk began at
    decode_ctx: tuple = ()      # context length of each decode lane
    decode_kv_tokens: int = 0   # distinct resident tokens decode reads
    span: tuple | None = None   # (start, end) ns of its trace span

    @property
    def has_chunks(self) -> bool:
        return bool(self.chunks)


@dataclasses.dataclass
class Record:
    """What one run recorded, for the end-to-end and per-layer readers."""
    t_start: float
    t_end: float
    t_grace_end: float
    tracks: list
    steps: list
    counters: dict = dataclasses.field(default_factory=dict)
    trace: object = None        # trace_reduce.Trace, traced runs only
    trace_window: tuple | None = None   # (start, end) ns of the window
    shapes: object = None       # flops.Shapes of the configuration
    peak: dict = dataclasses.field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return self.t_end - self.t_start

    def window_steps(self) -> list:
        """Executed (not idle) steps that ended inside the window."""
        return [s for s in self.steps if s.kind != "idle"
                and self.t_start <= s.t1 < self.t_end]

    def window_tracks(self) -> list:
        return [t for t in self.tracks if t.in_window]

    def finished_tracks(self) -> list:
        """Requests finished from the window's start on, in the window
        or its grace, whenever they were sent."""
        return [t for t in self.tracks
                if t.done is not None and t.done >= self.t_start]


def ttfts(rec: Record) -> list:
    out = []
    for t in rec.window_tracks():
        first = t.tokens[0] if t.tokens else rec.t_grace_end
        out.append(first - t.due)
    return out


def itls(rec: Record) -> list:
    out = []
    for t in rec.tracks:
        for a, b in zip(t.tokens, t.tokens[1:]):
            if rec.t_start <= b < rec.t_end:
                out.append(b - a)
    return out


def tokens_in_window(rec: Record) -> int:
    return sum(1 for t in rec.tracks for x in t.tokens
               if rec.t_start <= x < rec.t_end)


def end_to_end(rec: Record) -> dict:
    """Every end-to-end metric but set-up, by name, in its unit."""
    gaps = itls(rec)
    first = ttfts(rec)
    out = {"tok_per_s": tokens_in_window(rec) / rec.seconds}
    if first:
        out["ttft_p90_ms"] = percentile(first, 90) * 1e3
    if gaps:
        out["itl_mean_ms"] = sum(gaps) / len(gaps) * 1e3
        out["itl_p95_ms"] = percentile(gaps, 95) * 1e3
    return out
