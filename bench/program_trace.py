"""The program's own instrumentation in a profiler trace.

The serve engine opens host spans `serve.*` on the profiler's clock
when its tracer's `profiling` is set, each with the index of the
engine step it belongs to (`step`) and, for a forward's spans, its
`phase` (decode or prefill); its jitted programs run their parts under
named scopes (`embed`, `kv_read`, `attention`, `mlp`, `kv_write`,
`lm_head`, and `sampler` in `sample_tokens`).  This module reads both
back from one `.xplane.pb`, on top of what `trace_reduce.load` reads
(the operations, the program runs, the harness's `window` and
`engine.step` spans), and reduces them (`bench/profile_cell.py`
records such a trace of a benchmark cell):

  per-program time  chip 0's busy time in the window by the compiled
                    program whose run holds the operation (decode,
                    chunked prefill, sampler, the rest), and the runs
                    of each program
  scope time        operation time by named scope; an operation whose
                    name path holds no scope reads ""
  idle in step      chip 0's idle time inside `engine.step` spans,
                    summed by the `serve.*` span open at each gap's
                    midpoint ("other" where none); the same time split
                    by each gap's overlap with the spans; and the gaps
                    of 1 ms or more, each one host sync's round trip
  step host time    the summed time of the host's own work in each
                    step: `serve.schedule`, `fund`, `pack`, `account`
                    and `apply` (and each span's time a step)

An operation's scope comes from its name path, which no event of the
trace carries (a v5e's names the HLO instruction and spells it out, a
CPU's names it): the compiled programs' HLO `op_name` metadata
(`hlo_op_paths`, `engine_op_paths`) is passed in as `op_paths`.  A
fusion carries the path of its root.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
import statistics

import trace_reduce
from trace_reduce import _leaf_ops, _stats, merge

SCOPES = ("embed", "kv_read", "attention", "mlp", "kv_write", "lm_head",
          "sampler")
HOST_WORK = ("serve.schedule", "serve.fund", "serve.pack", "serve.account",
             "serve.apply")
PROGRAMS = {"decode": "jit_decode", "prefill": "jit_chunked_prefill",
            "sampler": "jit_sample_tokens"}


@dataclasses.dataclass
class Span:
    start: float
    end: float
    name: str
    step: int
    phase: str = ""


@dataclasses.dataclass
class Program:
    """Intervals in nanoseconds on the profiler's clock."""
    window: tuple                  # (start, end) of the `window` span
    steps: list                    # `engine.step` spans [(start, end)]
    spans: list                    # `serve.*` spans [Span], by start
    ops: list                      # per chip [(start, end, name, module, path)]
    modules: list                  # per chip [(start, end, name)]


def hlo_op_paths(hlo_text: str) -> dict:
    """{instruction name: op_name} of an HLO module's text; a fusion's
    own metadata is its root's."""
    out = {}
    for m in re.finditer(r"^\s*(?:ROOT )?%?([\w.\-]+) = [^\n]*?"
                         r'op_name="([^"]*)"', hlo_text, re.M):
        out.setdefault(m.group(1), m.group(2))
    return out


def engine_op_paths(eng) -> dict:
    """{module: {instruction: op_name}} of the paged engine `eng`'s
    compiled decode, chunked-prefill and sampler programs, lowered for
    the shapes it runs (its compile cache serves them again)."""
    import jax
    import jax.numpy as jnp

    from repro.serve import sampler
    be, ec = eng.backend, eng.ecfg
    b, c, pmax = ec.max_batch, ec.prefill_chunk, ec.max_pages_per_seq

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    flags = jax.ShapeDtypeStruct((b,), jnp.bool_)
    dec = (be.params, i32(b, 1), be.cache.kv, i32(b, pmax), i32(b), flags)
    pre = (be.params, i32(b, c), be.cache.kv, i32(b, pmax), i32(b), i32(b),
           flags, i32(b))
    logits = jax.eval_shape(be._decode_fn, *dec)[0]
    lanes = [jax.ShapeDtypeStruct((b,), d) for d in
             (jnp.float32, jnp.int32, jnp.float32, jnp.uint32, jnp.int32)]
    return {mod: hlo_op_paths(fn.lower(*args).compile().as_text())
            for mod, fn, args in (
                (PROGRAMS["decode"], be._decode_fn, dec),
                (PROGRAMS["prefill"], be._prefill_fn, pre),
                (PROGRAMS["sampler"], sampler.sample_tokens,
                 (logits, *lanes)))}


def scope_of(path: str) -> str:
    """The first named scope in an op_name path, or ""."""
    for part in (path or "").split("/"):
        if part in SCOPES:
            return part
    return ""


def _op_key(name: str) -> str:
    """The HLO instruction an operation's event names: `fusion.203`
    also where the event spells it out (`%fusion.203 = bf16[...] ...`)."""
    return name.split(" = ", 1)[0].lstrip("%")


def _module_name(name: str) -> str:
    """`jit_decode(12345)` and `jit_decode` name one program."""
    return name.split("(", 1)[0]


def load(path: str, host_ops: bool = False, op_paths: dict | None = None,
         trace: trace_reduce.Trace | None = None) -> Program:
    """Read one .xplane.pb: the chips' operations, the program runs, the
    `window` and the `engine.step` spans as `trace_reduce.load` reads
    them (`trace`, where the caller has read it already; `host_ops` as
    there), the `serve.*` spans, and each operation tagged with the
    program whose run holds it and, from `op_paths` ({module: {op:
    path}}), its name path."""
    tr = trace or trace_reduce.load(path, host_ops=host_ops)
    ops, modules = [], []
    for chip_ops, chip_mods in zip(tr.ops, tr.modules):
        mods = sorted((s, t, _module_name(n)) for s, t, n in chip_mods)
        paths = op_paths or {}
        ops.append([(s, t, n, mod, paths.get(mod, {}).get(_op_key(n), ""))
                    for s, t, n, mod in _with_modules(chip_ops, mods)])
        modules.append(mods)
    return Program(window=tr.window(),
                   steps=[(s, t) for s, t, _ in tr.spans_named("engine.step")],
                   spans=serve_spans(path), ops=ops, modules=modules)


def serve_spans(path: str) -> list:
    """The `serve.*` host spans of one .xplane.pb with their `step` and
    `phase`, by start."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    st = _stats(e)
                    out.append(Span(e.start_ns, e.start_ns + e.duration_ns,
                                    e.name, int(st.get("step", -1)),
                                    str(st.get("phase", ""))))
    return sorted(out, key=lambda x: x.start)


def _with_modules(ops: list, mods: list) -> list:
    """Each operation (start, end, name) with the program whose run
    holds its midpoint ("" where none)."""
    starts = [s for s, _, _ in mods]
    out = []
    for s, t, n in ops:
        mid, mod = (s + t) / 2, ""
        i = bisect.bisect_right(starts, mid) - 1
        if i >= 0 and mods[i][0] <= mid <= mods[i][1]:
            mod = mods[i][2]
        out.append((s, t, n, mod))
    return out


def _clip(s, t, lo, hi) -> float:
    return max(0.0, min(t, hi) - max(s, lo))


def leaf_ops(prog: Program, chip: int = 0) -> list:
    """Chip `chip`'s operations that enclose no other (a loop's own
    event goes where its body's operations are listed)."""
    keep = {(s, t, n) for s, t, n in
            _leaf_ops([(s, t, n) for s, t, n, _, _ in prog.ops[chip]])}
    return [o for o in prog.ops[chip] if (o[0], o[1], o[2]) in keep]


def program_of(module: str) -> str:
    for name, mod in PROGRAMS.items():
        if module == mod:
            return name
    return "rest"


def per_program_ns(prog: Program, lo: float, hi: float) -> dict:
    """Chip 0's busy time in [lo, hi) by program: the union of the
    intervals of the operations each program's runs hold."""
    by: dict = {k: [] for k in (*PROGRAMS, "rest")}
    for s, t, _, mod, _ in prog.ops[0]:
        by[program_of(mod)].append((s, t))
    return {k: sum(_clip(s, t, lo, hi) for s, t in merge(v))
            for k, v in by.items()}


def runs(modules: list, program: str, lo: float, hi: float) -> list:
    """The runs [(start, end)] of `program` ("decode", "prefill",
    "sampler") among one chip's `modules` [(start, end, name)] that
    start in [lo, hi)."""
    mod = PROGRAMS[program]
    return [(s, t) for s, t, n in modules
            if _module_name(n) == mod and lo <= s < hi]


def run_ms(modules: list, program: str, lo: float, hi: float):
    """Mean device time (ms) of one run of `program` over the runs
    that start in [lo, hi) on every chip of `modules` (per chip
    [(start, end, name)]); None where it never ran."""
    d = [t - s for chip in modules for s, t in runs(chip, program, lo, hi)]
    return sum(d) / len(d) / 1e6 if d else None


def program_runs(prog: Program, program: str, lo: float, hi: float) -> list:
    """Chip 0's runs of `program` that start in [lo, hi)."""
    return runs(prog.modules[0], program, lo, hi)


def scope_ns(prog: Program, lo: float, hi: float) -> dict:
    """Chip 0's operation time in [lo, hi) by named scope ("" for an
    operation under none)."""
    out: dict = {}
    for s, t, _, _, p in leaf_ops(prog):
        d = _clip(s, t, lo, hi)
        if d > 0:
            k = scope_of(p)
            out[k] = out.get(k, 0.0) + d
    return out


def busy_ns(prog: Program, lo: float, hi: float) -> float:
    """Union of chip 0's operation intervals inside [lo, hi)."""
    return sum(_clip(s, t, lo, hi) for s, t in merge(prog.ops[0]))


def _open_span(spans: list, starts: list, t: float) -> str:
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and spans[i].start <= t < spans[i].end:
        return spans[i].name
    return "other"


def step_gaps(prog: Program, lo: float, hi: float) -> list:
    """Chip 0's idle intervals [(start, end)] inside the `engine.step`
    spans within [lo, hi)."""
    busy = merge(prog.ops[0])
    starts = [x[0] for x in busy]
    out = []
    for s0, t0 in prog.steps:
        a, b = max(s0, lo), min(t0, hi)
        if b <= a:
            continue
        cursor = a
        for i in range(max(bisect.bisect_right(starts, a) - 1, 0),
                       len(busy) + 1):
            s, t = busy[i] if i < len(busy) else (b, b)
            s, t = min(max(s, a), b), min(t, b)
            if s > cursor:
                out.append((cursor, s))
            cursor = max(cursor, t)
            if cursor >= b:
                break
    return out


def idle_in_step(prog: Program, lo: float, hi: float) -> dict:
    """Chip 0's idle time (ns) inside the `engine.step` spans within
    [lo, hi), summed by the `serve.*` span open at each gap's
    midpoint, "other" where none."""
    starts = [s.start for s in prog.spans]
    out: dict = {}
    for a, b in step_gaps(prog, lo, hi):
        label = _open_span(prog.spans, starts, (a + b) / 2)
        out[label] = out.get(label, 0.0) + (b - a)
    return out


def idle_overlap(prog: Program, lo: float, hi: float) -> dict:
    """The same idle time split by the time each gap overlaps each
    `serve.*` span (the spans are flat), "other" for the rest: unlike
    the midpoint, a gap's split does not flip between two spans as the
    host and device clocks line up."""
    starts = [s.start for s in prog.spans]
    out: dict = {}
    for a, b in step_gaps(prog, lo, hi):
        rest = b - a
        for sp in prog.spans[max(bisect.bisect_right(starts, a) - 1, 0):
                             bisect.bisect_left(starts, b)]:
            d = _clip(sp.start, sp.end, a, b)
            if d > 0:
                out[sp.name] = out.get(sp.name, 0.0) + d
                rest -= d
        out["other"] = out.get("other", 0.0) + rest
    return out


def step_index(prog: Program) -> list:
    """The engine step index of each `engine.step` span (-1 where no
    `serve.*` span lies inside it)."""
    starts = [s.start for s in prog.spans]
    out = []
    for s0, t0 in prog.steps:
        i = bisect.bisect_left(starts, s0)
        inside = i < len(prog.spans) and prog.spans[i].end <= t0
        out.append(prog.spans[i].step if inside else -1)
    return out


def spans_by_step(prog: Program) -> dict:
    """{step index: [Span]}."""
    out: dict = {}
    for s in prog.spans:
        out.setdefault(s.step, []).append(s)
    return out


def executed_steps(prog: Program, lo: float, hi: float) -> list:
    """`engine.step` spans in [lo, hi) that dispatched a forward, with
    their index: [(start, end, step)]."""
    by = spans_by_step(prog)
    return [(s, t, k) for (s, t), k in zip(prog.steps, step_index(prog))
            if lo <= s and t <= hi
            and any(x.name == "serve.dispatch" for x in by.get(k, ()))]


def span_ns(prog: Program, lo: float, hi: float) -> dict:
    """Time (ns) of each `serve.*` span name per executed step in
    [lo, hi)."""
    steps = executed_steps(prog, lo, hi)
    keep = {k for _, _, k in steps}
    out: dict = {}
    for s in prog.spans:
        if s.step in keep:
            out[s.name] = out.get(s.name, 0.0) + (s.end - s.start)
    return {k: v / len(steps) for k, v in out.items()}


def step_host_ns(prog: Program, lo: float, hi: float) -> float:
    """The host's own work (`HOST_WORK` spans) per executed step in
    [lo, hi)."""
    per = span_ns(prog, lo, hi)
    return sum(v for k, v in per.items() if k in HOST_WORK)


def summary(prog: Program) -> dict:
    """The reductions over the window, in seconds and milliseconds;
    `scoped_share` is the share of busy time under a named scope or in
    the sampler's program."""
    lo, hi = prog.window
    runs = {p: program_runs(prog, p, lo, hi) for p in PROGRAMS}
    busy = busy_ns(prog, lo, hi)
    scopes = scope_ns(prog, lo, hi)
    gaps = step_gaps(prog, lo, hi)
    scoped = merge([(s, t) for s, t, _, mod, path in prog.ops[0]
                    if scope_of(path) or mod == PROGRAMS["sampler"]])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy * 1e-9,
        "executed_steps": len(executed_steps(prog, lo, hi)),
        "forwards": {p: len(r) for p, r in runs.items()},
        "per_run_device_ms": {
            p: sum(t - s for s, t in r) / len(r) / 1e6
            for p, r in runs.items() if r},
        "per_program_s": {k: v * 1e-9 for k, v in
                          per_program_ns(prog, lo, hi).items()},
        "scope_s": {k or "(none)": v * 1e-9 for k, v in
                    sorted(scopes.items(), key=lambda kv: -kv[1])},
        "scoped_share": 100.0 * sum(_clip(s, t, lo, hi) for s, t in scoped)
        / max(busy, 1e-9),
        "idle_in_step_s": {k: v * 1e-9 for k, v in sorted(
            idle_in_step(prog, lo, hi).items(), key=lambda kv: -kv[1])},
        "idle_overlap_s": {k: v * 1e-9 for k, v in sorted(
            idle_overlap(prog, lo, hi).items(), key=lambda kv: -kv[1])},
        "long_idle_gaps": long_gaps(prog, gaps),
        "step_host_ms": step_host_ns(prog, lo, hi) / 1e6,
        "span_ms_per_step": {k: v / 1e6 for k, v in
                             span_ns(prog, lo, hi).items()},
    }


def long_gaps(prog: Program, gaps: list, floor_ns: float = 1e6,
              top: int = 5) -> dict:
    """The idle gaps inside steps of `floor_ns` or more (a host sync's
    round trip, not the slack between two operations): their count,
    share of the idle time in steps, quartiles (ms), and the `top`
    longest with the span open at each one's midpoint."""
    long = sorted((b - a, a, b) for a, b in gaps if b - a >= floor_ns)
    out = {"n": len(long), "share": 100.0 * sum(d for d, _, _ in long)
           / max(sum(b - a for a, b in gaps), 1e-9)}
    if len(long) >= 2:
        q = statistics.quantiles([d for d, _, _ in long], n=4)
        out.update(q1_ms=q[0] / 1e6, median_ms=q[1] / 1e6, q3_ms=q[2] / 1e6)
    starts = [s.start for s in prog.spans]
    out["longest"] = [[d / 1e6, _open_span(prog.spans, starts, (a + b) / 2)]
                      for d, a, b in long[::-1][:top]]
    return out
