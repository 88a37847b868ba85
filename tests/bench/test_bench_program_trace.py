"""The program's own spans, scopes and counters read back from a trace
(`program_trace`), on the tiny engine's CPU trace recorded by
`record_cpu_engine_trace.py`, on one recorded afresh, and through the
two forward readers and the profiling run."""
import json
import time
from pathlib import Path

import pytest

import bench_fixture
import program_trace as P
import trace_reduce as T

DATA = Path(__file__).resolve().parent / "data"
TRACE = DATA / "cpu_engine_trace.xplane.pb"
SIDE = json.loads((DATA / "cpu_engine_trace.json").read_text())


@pytest.fixture(scope="module")
def prog():
    return P.load(str(TRACE), host_ops=True, op_paths=SIDE["op_scopes"])


def span_steps(prog) -> list:
    """For each `engine.step` span, the `serve.*` spans inside it;
    asserts that every span lies inside one."""
    inside = [[] for _ in prog.steps]
    for s in prog.spans:
        home = [i for i, (a, b) in enumerate(prog.steps)
                if a <= s.start and s.end <= b]
        assert len(home) == 1, f"{s} lies in no engine.step span"
        inside[home[0]].append(s)
    return inside


def check_spans(prog) -> None:
    inside = span_steps(prog)
    steps = []
    for spans in inside:
        assert spans and {s.step for s in spans} == {spans[0].step}
        steps.append(spans[0].step)
        assert spans[0].name == "serve.schedule"
        for a, b in zip(spans, spans[1:]):
            assert a.end <= b.start, "serve.* spans are flat"
    assert steps == list(range(steps[0], steps[0] + len(steps)))
    executed = [sp for sp in inside
                if any(s.name == "serve.dispatch" for s in sp)]
    assert executed
    for spans in executed:
        names = [s.name for s in spans]
        assert {"serve.fund", "serve.account", "serve.apply"} <= set(names)
        for s in spans:
            if s.name in ("serve.pack", "serve.dispatch", "serve.sample"):
                assert s.phase in ("decode", "prefill")
        packs = [s.phase for s in spans if s.name == "serve.pack"]
        assert packs == [s.phase for s in spans
                         if s.name == "serve.dispatch"]


def test_spans_sit_in_their_engine_step_with_its_index(prog):
    check_spans(prog)
    kinds = {tuple(sorted((s.name, s.phase) for s in spans
                          if s.name in ("serve.dispatch", "serve.sample")))
             for spans in span_steps(prog)}
    # prefill alone, decode with chunks completing a prompt, decode alone
    assert (("serve.dispatch", "decode"),
            ("serve.sample", "decode")) in kinds
    assert any(("serve.sample", "prefill") in k
               and ("serve.dispatch", "decode") in k for k in kinds)


def test_forward_counters_equal_the_module_runs(prog):
    lo, hi = prog.window
    counters = SIDE["counters"]
    assert counters["engine/decode_forwards"] == len(
        P.program_runs(prog, "decode", lo, hi)) > 0
    assert counters["engine/prefill_forwards"] == len(
        P.program_runs(prog, "prefill", lo, hi)) > 0
    # every forward and every prompt completion samples once
    completions = sum(1 for s in prog.spans if s.name == "serve.sample"
                      and s.phase == "prefill")
    assert len(P.program_runs(prog, "sampler", lo, hi)) == (
        counters["engine/decode_forwards"] + completions)


def test_prefill_positions_and_tokens(prog):
    c = SIDE["counters"]
    assert c["backend/prefill_positions"] == \
        c["engine/prefill_forwards"] * 4 * 8      # max_batch x chunk
    assert c["backend/prefill_tokens"] == 20 + 5 + 13
    assert 0 < c["backend/prefill_tokens"] < c["backend/prefill_positions"]


def test_operations_carry_their_named_scopes(prog):
    by_module: dict = {}
    for _, _, _, mod, path in P.leaf_ops(prog):
        by_module.setdefault(mod, set()).add(P.scope_of(path))
    for mod in ("jit_decode", "jit_chunked_prefill"):
        assert {"embed", "attention", "mlp", "lm_head"} <= by_module[mod]
        assert "sampler" not in by_module[mod]
    assert by_module["jit_sample_tokens"] - {""} == {"sampler"}
    lo, hi = prog.window
    scopes = P.scope_ns(prog, lo, hi)
    total = sum(P._clip(s, t, lo, hi) for s, t, *_ in P.leaf_ops(prog))
    assert sum(scopes.values()) == pytest.approx(total)


def test_scope_of_name_paths():
    assert P.scope_of("jit(decode)/while/body/closed_call/mlp/dot") == "mlp"
    assert P.scope_of("jit(sample_tokens)/sampler/sort") == "sampler"
    assert P.scope_of("jit(decode)/while/body/add") == ""
    assert P.scope_of("") == ""
    hlo = ('  %fusion.3 = f32[4]{0} fusion(%p), kind=kLoop, calls=%c, '
           'metadata={op_type="dot" op_name="jit(f)/attention/dot"}\n'
           '  ROOT %copy.1 = f32[4]{0} copy(%fusion.3)\n')
    assert P.hlo_op_paths(hlo) == {"fusion.3": "jit(f)/attention/dot"}


def test_per_program_time_adds_up_to_the_busy_time(prog):
    lo, hi = prog.window
    per = P.per_program_ns(prog, lo, hi)
    assert per["decode"] > 0 and per["prefill"] > 0 and per["sampler"] > 0
    # one program runs at a time, so their busy times add up to the whole
    assert sum(per.values()) == pytest.approx(P.busy_ns(prog, lo, hi))


def test_idle_in_step_splits_the_idle_inside_steps(prog):
    lo, hi = prog.window
    idle = P.idle_in_step(prog, lo, hi)
    busy = T.Busy([(s, t) for s, t, *_ in prog.ops[0]])
    want = sum((min(b, hi) - max(a, lo)) - busy.within(max(a, lo), min(b, hi))
               for a, b in prog.steps)
    assert sum(idle.values()) == pytest.approx(want)
    assert set(idle) <= {s.name for s in prog.spans} | {"other"}
    assert idle.get("serve.sample", 0) > 0
    overlap = P.idle_overlap(prog, lo, hi)
    assert sum(overlap.values()) == pytest.approx(want)
    assert set(overlap) <= {s.name for s in prog.spans} | {"other"}


def test_idle_in_step_on_hand_made_intervals():
    spans = [P.Span(10, 20, "serve.fund", 1), P.Span(20, 50, "serve.sample",
                                                     1, "decode")]
    prog = P.Program(window=(0, 100), steps=[(5, 50), (60, 90)],
                     spans=spans, ops=[[(12, 16, "a", "jit_decode", ""),
                                        (22, 30, "b", "jit_decode", ""),
                                        (70, 80, "c", "jit_decode", "")]],
                     modules=[[(12, 30, "jit_decode")]])
    idle = P.idle_in_step(prog, 0, 100)
    # step 1: 5-12 before any span, 16-22 in fund, 30-50 in sample;
    # step 2: 60-70 and 80-90 in no span
    assert idle == {"other": 7 + 10 + 10, "serve.fund": 6,
                    "serve.sample": 20}
    assert P.idle_in_step(prog, 0, 25) == {"other": 7, "serve.fund": 6}
    # by overlap: 5-10 other, 10-12 and 16-20 fund, 20-22 and 30-50
    # sample, 60-70 and 80-90 other
    assert P.step_gaps(prog, 0, 100) == [(5, 12), (16, 22), (30, 50),
                                         (60, 70), (80, 90)]
    assert P.idle_overlap(prog, 0, 100) == {"other": 5 + 20,
                                            "serve.fund": 6,
                                            "serve.sample": 22}
    long = P.long_gaps(prog, P.step_gaps(prog, 0, 100), floor_ns=8, top=2)
    assert long == {"n": 3, "share": 100.0 * 40 / 53, "q1_ms": 10e-6,
                    "median_ms": 10e-6, "q3_ms": 20e-6,
                    "longest": [[20e-6, "serve.sample"], [10e-6, "other"]]}


def test_step_host_time(prog):
    lo, hi = prog.window
    steps = P.executed_steps(prog, lo, hi)
    keep = {k for _, _, k in steps}
    want = sum(s.end - s.start for s in prog.spans
               if s.step in keep and s.name in P.HOST_WORK) / len(steps)
    assert want > 0
    assert P.step_host_ns(prog, lo, hi) == pytest.approx(want)
    per = P.span_ns(prog, lo, hi)
    assert set(P.HOST_WORK) <= set(per)
    assert per["serve.dispatch"] > 0 and per["serve.sample"] > 0


def test_summary_reads_the_recorded_trace(prog):
    out = P.summary(prog)
    assert out["forwards"]["decode"] == SIDE["counters"][
        "engine/decode_forwards"]
    assert 0 < out["scoped_share"] <= 100
    assert out["step_host_ms"] > 0
    assert sum(out["per_program_s"].values()) > 0


@pytest.mark.parametrize("metric,program", [
    ("decode_forward_device_ms", "decode"),
    ("prefill_forward_device_ms", "prefill")])
def test_forward_readers(metric, program):
    import run as bench_run
    from window import Record
    tr = T.load(str(TRACE), host_ops=True)
    lo, hi = tr.window()
    rec = Record(t_start=0.0, t_end=1.0, t_grace_end=1.0, tracks=[],
                 steps=[], trace=tr, trace_window=(lo, hi))
    reader = bench_run.load_reader(bench_fixture.REPO, metric)
    runs = P.runs(tr.modules[0], program, lo, hi)
    assert len(runs) == SIDE["counters"][f"engine/{program}_forwards"]
    assert reader.read(rec) == pytest.approx(
        sum(t - s for s, t in runs) / len(runs) / 1e6)
    assert reader.UNIT == "ms"
    assert reader.read(Record(t_start=0.0, t_end=1.0, t_grace_end=1.0,
                              tracks=[], steps=[])) is None


def test_a_fresh_recording_has_the_spans_scopes_and_counts(tmp_path):
    import record_cpu_engine_trace
    out = tmp_path / "engine.xplane.pb"
    side = record_cpu_engine_trace.record(out)
    prog = P.load(str(out), host_ops=True, op_paths=side["op_scopes"])
    check_spans(prog)
    lo, hi = prog.window
    assert side["counters"]["engine/decode_forwards"] == len(
        P.program_runs(prog, "decode", lo, hi))
    assert side["counters"]["engine/prefill_forwards"] == len(
        P.program_runs(prog, "prefill", lo, hi))
    assert {"attention", "mlp", "lm_head", "sampler"} <= set(
        P.scope_ns(prog, lo, hi))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_fixture.tiny_root(tmp_path_factory.mktemp("bench"))


def test_profiling_run_turns_the_spans_on_and_restores_the_harness(root):
    import harness
    import jax
    import profile_cell
    import run as bench_run
    load, record = bench_run.trace_reduce.load, bench_run.Record
    start_trace = jax.profiler.start_trace
    rep = profile_cell.profile(root, "tiny.closed", 2 ** 31 + 5, 3.0, True,
                               check_chips=False,
                               t_process=time.perf_counter())
    assert rep["line"]["correct"], rep["line"]["checks"]
    prog = rep["program"]
    assert prog["executed_steps"] > 0 and prog["step_host_ms"] > 0
    assert prog["forwards"]["decode"] > 0
    # the window's forwards are the engine's counts over it
    counters = rep["counters"]
    assert counters["engine/decode_forwards"] >= prog["forwards"]["decode"]
    assert counters["engine/prefill_forwards"] >= prog["forwards"]["prefill"]
    assert 0 < counters["backend/prefill_tokens"] < counters[
        "backend/prefill_positions"]
    spans = {"serve.schedule", "serve.fund", "serve.pack", "serve.dispatch",
             "serve.sample", "serve.account", "serve.apply", "other"}
    assert set(prog["idle_in_step_s"]) <= spans
    assert set(prog["idle_overlap_s"]) <= spans
    assert sum(prog["idle_overlap_s"].values()) == pytest.approx(
        sum(prog["idle_in_step_s"].values()))
    kinds = rep["steps_by_kind"]
    assert kinds and all(k in ("D", "P", "DP", "D+c", "P+c", "DP+c")
                         for k in kinds)
    assert sum(v["n"] for v in kinds.values()) == prog["executed_steps"]
    assert bench_run.trace_reduce.load is load
    assert bench_run.Record is record
    assert "annotate" not in harness.Harness.__dict__
    assert jax.profiler.start_trace is start_trace
