#!/usr/bin/env python3
"""Run one cell traced, as `bench/run.py --trace 1` does, with the
program's own spans on, and reduce them from the same trace.

    python bench/profile_cell.py --workload <cell> --seed <n> \\
        --seconds <s> [--spans 0|1] [--out DIR]

The run is `run.run_cell` itself.  Four seams are wrapped around it,
none of which changes what the engine does: the harness's `annotate`
flag, which the run sets for the traced window, also sets the engine
tracer's `profiling` (with `--spans 1`); the trace reduction also
reduces the program's spans and scopes (`program_trace`) from the same
read of the trace; the run's record is kept; and the profiler starts
without its event per Python call, which `run.py --trace 1` records
and which adds host work to every step.  Prints the result line and
writes `<out>/<cell>.<seed>.spans<0|1>.json`: the line, the engine's
forward and prefill counters over the window, `program_trace`'s
summary of it, the engine steps' wall time, each step's composition
with its wall and device time, the compositions the longest token
gaps fall on, and the operations that took most time.  Step
composition: `D` decode forward only, `P` prefill forward only, `DP`
both, `+c` where a chunk completed a prompt (a prefill sample in the
step).
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import program_trace  # noqa: E402
import run as bench_run  # noqa: E402
import window  # noqa: E402


COUNTERS = ("engine/decode_forwards", "engine/prefill_forwards",
            "backend/prefill_tokens", "backend/prefill_positions")


class _Follow:
    """`Harness.annotate` that also switches the engine's spans, and
    calls `on_edge(engine, on)` when the traced window opens and when
    it is over."""

    def __init__(self, spans: bool, on_edge):
        self.spans, self.on_edge = spans, on_edge

    def __get__(self, h, owner=None):
        return self if h is None else h.__dict__.get("_annotate", False)

    def __set__(self, h, on):
        was = h.__dict__.get("_annotate", False)
        h.__dict__["_annotate"] = on
        h.eng.obs.profiling = bool(on) and self.spans
        if bool(was) != bool(on):
            self.on_edge(h.eng, bool(on))


def composition(spans: list) -> str:
    names = {(s.name, s.phase) for s in spans}
    kind = ("D" if ("serve.dispatch", "decode") in names else "") + \
        ("P" if ("serve.dispatch", "prefill") in names else "")
    return kind + ("+c" if ("serve.sample", "prefill") in names else "")


def _runs(prog) -> tuple[list, list]:
    mods = prog.modules[0]
    return mods, [s for s, _, _ in mods]


def step_table(prog, rec) -> list:
    """One row per traced window step: composition, wall ms, device ms
    inside its span, and the part of it from program runs that began
    before the span (a previous step's leftover)."""
    by = program_trace.spans_by_step(prog)
    idx = dict(zip(prog.steps, program_trace.step_index(prog)))
    mods, starts = _runs(prog)
    ops = program_trace.leaf_ops(prog)
    op_starts = [o[0] for o in ops]
    rows = []
    for s in rec.window_steps():
        if s.span is None:
            continue
        lo, hi = s.span
        k = idx.get((lo, hi), -1)
        dev = left = 0.0
        for o in ops[bisect.bisect_left(op_starts, lo):
                     bisect.bisect_left(op_starts, hi)]:
            d = min(o[1], hi) - o[0]
            dev += d
            i = bisect.bisect_right(starts, o[0]) - 1
            if i >= 0 and mods[i][0] < lo:
                left += d
        rows.append({"t1": s.t1, "kind": composition(by.get(k, [])),
                     "chunks": s.has_chunks, "wall_ms": (s.t1 - s.t0) * 1e3,
                     "device_ms": dev / 1e6, "leftover_ms": left / 1e6})
    return rows


def by_kind(rows: list) -> dict:
    out: dict = {}
    for r in rows:
        out.setdefault(r["kind"], []).append(r)
    return {k: {"n": len(v),
                "wall_ms": sum(r["wall_ms"] for r in v) / len(v),
                "wall_p95_ms": window.percentile([r["wall_ms"] for r in v],
                                                 95),
                "device_ms": sum(r["device_ms"] for r in v) / len(v),
                "leftover_ms": sum(r["leftover_ms"] for r in v) / len(v)}
            for k, v in sorted(out.items())}


def tail_gaps(rec, rows: list) -> dict:
    """Compositions of the steps that end the token gaps at or above
    the window's p95 gap."""
    kind = {r["t1"]: r["kind"] for r in rows}
    gaps = [(b - a, b) for t in rec.tracks
            for a, b in zip(t.tokens, t.tokens[1:])
            if rec.t_start <= b < rec.t_end]
    if not gaps:
        return {}
    p95 = window.percentile([g for g, _ in gaps], 95)
    out: dict = {}
    for g, b in gaps:
        if g >= p95:
            k = kind.get(b, "?")
            out[k] = out.get(k, 0) + 1
    return {"p95_ms": p95 * 1e3, "steps_of_tail_gaps": out}


def top_ops(prog, n: int = 40) -> list:
    """Chip 0's operations that took most time in the window, by
    (program, name without its number, scope): seconds and count."""
    lo, hi = prog.window
    per: dict = {}
    for s, t, name, mod, path in program_trace.leaf_ops(prog):
        d = min(t, hi) - max(s, lo)
        if d > 0:
            key = (mod, name.rsplit(".", 1)[0] if name.rsplit(".", 1)[-1]
                   .isdigit() else name, program_trace.scope_of(path))
            tot, k = per.get(key, (0.0, 0))
            per[key] = (tot + d, k + 1)
    top = sorted(per.items(), key=lambda kv: -kv[1][0])[:n]
    return [[*key, tot * 1e-9, k] for key, (tot, k) in top]


def profile(root: Path, workload: str, seed: int, seconds: float,
            spans: bool, check_chips: bool = True,
            t_process: float = T_PROCESS) -> dict:
    """One traced run of the cell with the seams wrapped (restored on
    return); the report."""
    import jax
    kept: dict = {}
    start_trace = jax.profiler.start_trace

    def start_without_python(log_dir):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        start_trace(log_dir, profiler_options=opts)
    load = bench_run.trace_reduce.load

    def on_edge(eng, on):
        counts = {k: eng.obs.registry.count(k) for k in COUNTERS}
        if on:
            kept["counters"] = counts
            return
        kept["counters"] = {k: v - kept["counters"][k]
                            for k, v in counts.items()}
        kept["op_paths"] = program_trace.engine_op_paths(eng)

    def load_both(path, host_ops=False):
        trace = load(path, host_ops=host_ops)
        kept["program"] = program_trace.load(
            path, op_paths=kept.get("op_paths"), trace=trace)
        return trace

    class KeptRecord(window.Record):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            kept["record"] = self

    saved = (harness.Harness.__dict__.get("annotate"), bench_run.Record)
    harness.Harness.annotate = _Follow(spans, on_edge)
    bench_run.trace_reduce.load = load_both
    bench_run.Record = KeptRecord
    jax.profiler.start_trace = start_without_python
    try:
        out = bench_run.run_cell(root, workload, seed, seconds, True,
                                 check_chips=check_chips,
                                 t_process=t_process)
    finally:
        jax.profiler.start_trace = start_trace
        bench_run.trace_reduce.load = load
        bench_run.Record = saved[1]
        if saved[0] is None:
            del harness.Harness.annotate
        else:
            harness.Harness.annotate = saved[0]
    prog, rec = kept["program"], kept["record"]
    rows = step_table(prog, rec)
    walls = [r["wall_ms"] for r in rows]
    return {
        "line": out, "spans": int(spans),
        "program": program_trace.summary(prog),
        "counters": kept["counters"],
        "traced_end_to_end": window.end_to_end(rec),
        "step_wall_ms_mean": sum(walls) / max(len(walls), 1),
        "steps_by_kind": by_kind(rows),
        "tail": tail_gaps(rec, rows),
        "top_ops": top_ops(prog),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--spans", type=int, choices=(0, 1), default=1)
    ap.add_argument("--out", default="profile_out")
    args = ap.parse_args(argv)
    root = BENCH.parent
    sys.path.insert(0, str(root / "src"))
    bench_run.use_compile_cache(root)
    report = profile(root, args.workload, args.seed, args.seconds,
                     bool(args.spans))
    dest = Path(args.out)
    dest.mkdir(parents=True, exist_ok=True)
    path = dest / f"{args.workload}.{args.seed}.spans{args.spans}.json"
    path.write_text(json.dumps(report, indent=1))
    print(json.dumps(report["line"]), flush=True)
    print(f"wrote {path}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
