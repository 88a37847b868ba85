"""Reduction of a profiler trace to device times.

The JAX profiler writes `<dir>/plugins/profile/<time>/*.xplane.pb`;
`jax.profiler.ProfileData` reads it.  On a TPU each chip is a plane
`/device:TPU:<n>` whose line "XLA Ops" holds one event per operation
run on the chip and whose line "XLA Modules" holds one per compiled
program run.  The harness's host spans (`jax.profiler.TraceAnnotation`)
are events on the host plane, on the same clock.

On the CPU (the recorded trace the tests read) there is no device
plane: the operations are host-thread events that carry an `hlo_op`
stat.  Only a caller that asks for it (`host_ops=True`, the tests)
has them stand in for device 0, so that the arithmetic can be checked
without a chip; a run on the chip whose trace has no device plane, or
no operation on it, is an error, never a reading of host events.

Definitions:

  busy       union of the operation intervals of one chip inside an
             interval, averaged over the chips used
  idle share 1 - busy / window
  span time  device busy time inside each harness span of one name
  program    summed duration of the "XLA Modules" events whose name
             holds a given program name (e.g. sample_tokens)
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import re
from pathlib import Path

COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|allreduce|allgather", re.I)
HOST_SPANS = ("submit", "engine.step", "poll", "wait_arrival")


@dataclasses.dataclass
class Trace:
    """Intervals in nanoseconds on the profiler's clock."""
    ops: list          # per chip: sorted [(start, end, name)]
    modules: list      # per chip: [(start, end, name)]
    spans: list        # host spans: [(start, end, name)]

    def __post_init__(self):
        self.busy = [Busy(o) for o in self.ops]

    def spans_named(self, name: str) -> list:
        return [s for s in self.spans if s[2] == name]

    def window(self) -> tuple:
        w = self.spans_named("window")
        if not w:
            raise ValueError("the trace holds no `window` span")
        return w[0][0], w[0][1]


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(str(Path(log_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


def load(path: str, host_ops: bool = False) -> Trace:
    """Read one .xplane.pb into chips' operations and host spans;
    `host_ops` reads a CPU trace's host-thread operations as device 0's."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: dict[int, tuple[list, list]] = {}
    spans: list = []
    cpu_ops: list = []
    cpu_modules: dict = {}
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            ops, mods = devices.setdefault(int(m.group(1)), ([], []))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((e.start_ns, e.start_ns + e.duration_ns,
                                e.name) for e in line.events)
                elif line.name == "XLA Modules":
                    mods.extend((e.start_ns, e.start_ns + e.duration_ns,
                                 e.name) for e in line.events)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                start, end = e.start_ns, e.start_ns + e.duration_ns
                if e.name == "window" or e.name in HOST_SPANS:
                    spans.append((start, end, e.name))
                    continue
                st = _stats(e)
                if "hlo_op" in st:
                    cpu_ops.append((start, end, e.name))
                    mod = st.get("hlo_module", "")
                    lo, hi = cpu_modules.get(
                        (mod, st.get("run_id")), (start, end))
                    cpu_modules[(mod, st.get("run_id"))] = (
                        min(lo, start), max(hi, end))
    if host_ops:
        ops = [sorted(cpu_ops)]
        mods = [sorted((lo, hi, mod)
                       for (mod, _), (lo, hi) in cpu_modules.items())]
    elif not devices:
        raise ValueError(f"{path}: no /device:TPU:<n> plane (planes: "
                         f"{sorted(p.name for p in pd.planes)})")
    else:
        ops = [sorted(devices[k][0]) for k in sorted(devices)]
        mods = [sorted(devices[k][1]) for k in sorted(devices)]
        if not all(ops):
            lines = sorted({ln.name for p in pd.planes
                            if p.name.startswith("/device:TPU:")
                            for ln in p.lines})
            raise ValueError(f"{path}: a TPU plane holds no \"XLA Ops\" "
                             f"event (lines: {lines})")
    return Trace(ops=ops, modules=mods, spans=sorted(spans))


def merge(intervals) -> list:
    """Sorted disjoint [start, end) intervals covering `intervals`."""
    out: list = []
    for s, e, *_ in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class Busy:
    """One chip's merged busy intervals, for fast overlap sums."""

    def __init__(self, intervals):
        self.iv = merge(intervals)
        self.starts = [s for s, _ in self.iv]
        self.cum = [0.0]
        for s, e in self.iv:
            self.cum.append(self.cum[-1] + (e - s))

    def within(self, lo: float, hi: float) -> float:
        """Busy time inside [lo, hi)."""
        if hi <= lo or not self.iv:
            return 0.0
        i = max(bisect.bisect_right(self.starts, lo) - 1, 0)
        j = bisect.bisect_left(self.starts, hi)
        total = self.cum[j] - self.cum[i]
        s, e = self.iv[i]
        total -= max(0.0, min(e, lo) - s)          # cut the head
        if j - 1 >= i:
            s, e = self.iv[j - 1]
            total -= max(0.0, e - max(s, hi))      # cut the tail
        return max(total, 0.0)


def union(intervals, lo: float, hi: float) -> float:
    """Length of the union of `intervals` clipped to [lo, hi)."""
    return Busy(intervals).within(lo, hi)


def busy_ns(trace: Trace, lo: float, hi: float) -> float:
    """Device busy time in [lo, hi), averaged over the chips."""
    return sum(b.within(lo, hi) for b in trace.busy) / len(trace.busy)


def span_busy_ns(trace: Trace, name: str) -> list:
    """Device busy time inside each host span called `name`, in order."""
    return [busy_ns(trace, s, e) for s, e, _ in trace.spans_named(name)]


def program_ns(trace: Trace, program: str, lo: float, hi: float) -> float:
    """Summed time of the runs of compiled programs whose name holds
    `program`, inside [lo, hi), averaged over the chips."""
    tot = 0.0
    for mods in trace.modules:
        tot += sum(min(e, hi) - max(s, lo) for s, e, n in mods
                   if program in n and min(e, hi) > max(s, lo))
    return tot / len(trace.modules)


def collective_ns(trace: Trace, lo: float, hi: float) -> float:
    """Union of the collective operations' intervals, averaged over chips."""
    return sum(union([o for o in ops if COLLECTIVE.search(o[2])], lo, hi)
               for ops in trace.ops) / len(trace.ops)


def _leaf_ops(ops: list) -> list:
    """Operations that enclose no other (drops a loop's own event where
    its body's operations are listed too)."""
    out = []
    for i, (s, e, n) in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is not None and nxt[0] >= s and nxt[1] <= e and nxt[0] < e:
            continue
        out.append((s, e, n))
    return out


def breakdown(trace: Trace, lo: float, hi: float, top: int = 10) -> dict:
    """The device operations that took most time on chip 0 (by name,
    seconds summed), and chip 0's idle time inside the window summed by
    the harness span that was open on the host ("other" where none)."""
    per: dict = {}
    for s, e, n in _leaf_ops(trace.ops[0]):
        d = min(e, hi) - max(s, lo)
        if d > 0:
            key = re.sub(r"\.\d+$", "", n)
            per[key] = per.get(key, 0.0) + d
    ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
    gaps: dict = {}
    cursor = lo
    host = [s for s in trace.spans if s[2] != "window"]
    for s, e, _ in sorted(trace.ops[0]) + [(hi, hi, "")]:
        s, e = max(s, lo), min(e, hi)
        if s > cursor:
            label = _open_span(host, (cursor + s) / 2)
            gaps[label] = gaps.get(label, 0.0) + (s - cursor)
        cursor = max(cursor, e)
    idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, v * 1e-9] for n, v in ops],
            "idle_gaps": [[n, v * 1e-9] for n, v in idle]}


def _open_span(spans: list, t: float) -> str:
    """Name of the host span open at `t` (spans sorted by start and not
    nested), or "other"."""
    i = bisect.bisect_right(spans, (t, float("inf"), "")) - 1
    if i >= 0 and spans[i][0] <= t < spans[i][1]:
        return spans[i][2]
    return "other"
