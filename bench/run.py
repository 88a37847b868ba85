#!/usr/bin/env python3
"""Run one benchmark cell once, in one process, and print its result.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of `workloads` in BENCHMARK.json at the root of
the checkout; it names a configuration (`bench/configs/<name>.json`,
whose `model_type` names its architecture file `bench/arch/<type>.py`)
and a traffic mix (`bench/traffic/<name>.json`).  Per-layer metrics
are read by `bench/metrics/<name>.py`, the chip's peaks come from
`bench/peaks.json`.  Nothing here names a cell, a mix or a metric.

A run:
  1. set-up (`setup_s`, from process start): weights made on the
     device from the seed, the engine built, one short request served
     to compile its programs, then `ramp_s` seconds of the mix itself
     (a closed loop over shared documents first has one client per
     document compute its document);
  2. the window: `--seconds` of the mix, on the host clock; with
     `--trace 1` under the profiler, and the per-layer metrics read
     from the trace instead of the end-to-end ones;
  3. a grace period in which the window's requests may finish;
  4. the check: a sample of the greedy requests finished from the
     window's start on, drawn from the seed, longest included, is run
     through the plain float32 reference over its prompt and served
     tokens, and the widest gap by which a served token's logit lies
     below the reference's best is held to the configuration's limit.

The last line of stdout is one JSON object (`correct`, `attempted`,
`failed`, `metrics`, `device`, with `--trace 1` `breakdown`, and last
`checks`); the numbers compared are also the last lines of stderr.
Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import flops  # noqa: E402
import reference  # noqa: E402
import trace_reduce  # noqa: E402
import weights  # noqa: E402
from traffic import Generator, load_mix  # noqa: E402
from window import Record, end_to_end  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


# -- the cell ---------------------------------------------------------------

def load_cell(root: Path, name: str) -> dict:
    """The workload entry `name` with its configuration, mix and the
    benchmark's metric lists, all found by name."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = cells[name]
    confs = {c["name"]: c for c in bench["configs"]}
    conf = json.loads((root / confs[cell["config"]]["file"]).read_text())

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    return {"cell": cell, "conf": conf,
            "mix": load_mix(root / "bench" / "traffic"
                            / f"{cell['traffic']}.json"),
            "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
            "per_layer": [m for m in bench["per_layer"] if applies(m)]}


def engine_config(conf: dict, mix):
    from repro.serve import EngineConfig
    s = conf["serve"]
    page = s["page_size"]
    return EngineConfig(
        page_size=page, n_pages=s["n_pages"], max_batch=s["max_batch"],
        max_pages_per_seq=-(-(mix.max_prompt + mix.max_output) // page),
        prefill_chunk=s["prefill_chunk"], cache_dtype=s["cache_dtype"],
        scheduler=s["scheduler"], attn_impl=s["attn_impl"],
        mesh_shards=s["mesh_shards"])


def _load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(root: Path, name: str):
    """The per-layer reader `bench/metrics/<name>.py`."""
    return _load_module(root / "bench" / "metrics" / f"{name}.py",
                        f"bench_metric_{name}")


def load_arch(root: Path, conf: dict):
    """The architecture file `bench/arch/<model_type>.py` of a
    configuration: its program config, parameter leaves and reference."""
    kind = conf["model_type"]
    path = root / "bench" / "arch" / f"{kind}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no architecture file {path} for "
                                f"model_type {kind!r}")
    return _load_module(path, f"bench_arch_{kind}")


# -- device -----------------------------------------------------------------

def use_compile_cache(root: Path) -> None:
    """JAX's persistent compilation cache: where JAX_COMPILATION_CACHE_DIR
    says, else the fixed `<checkout>/.jax_cache`; every program cached."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def require_chips(n: int) -> None:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devs[0].platform!r})")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX finds {len(devs)}")


def device_line(chips: int) -> dict:
    import jax
    devs = jax.devices()[:chips]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": max((p for p in peaks if p is not None),
                                     default=None)}


def load_peaks(root: Path, kind: str) -> dict:
    table = json.loads((root / "bench" / "peaks.json").read_text())
    table = table["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json "
                       f"(have {sorted(table)})")
    return table[kind]


# -- the run ----------------------------------------------------------------

def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, policy: str = "exact", check_chips: bool = True,
             t_process: float = T_PROCESS, fault=None) -> dict:
    """One run of one cell; returns the result line as a dict.
    `check_chips=False` (no look for a chip, and the CPU's host-thread
    operations read as the device's in a trace) and `fault` (a callable
    that breaks the engine after it is built) exist for the tests only."""
    import jax

    spec = load_cell(root, workload)
    cell, conf, mix = spec["cell"], spec["conf"], spec["mix"]
    chips = cell["chips"]
    if conf["serve"]["mesh_shards"] != chips:
        raise ValueError(f"{workload}: {chips} chips but the configuration "
                         f"shards over {conf['serve']['mesh_shards']}")
    if check_chips:
        require_chips(chips)
    kind = jax.devices()[0].device_kind
    peak = load_peaks(root, kind) if check_chips else {
        "bf16_flops": 1.0, "hbm_bytes_per_s": 1.0}

    compiles = []

    def on_compile(ev, dur, **kw):
        if ev == COMPILE_EVENT:
            compiles.append(time.perf_counter())

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        return _run(root, spec, seed, seconds, trace, policy, t_process,
                    fault, peak, compiles, on_chip=check_chips)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)


def _run(root, spec, seed, seconds, trace, policy, t_process, fault, peak,
         compiles, on_chip) -> dict:
    import jax

    from harness import Harness
    from repro.core.policy import ArithmeticPolicy
    from repro.serve import ServeEngine
    from repro.serve.mesh import make_serve_mesh, param_shardings

    cell, conf, mix = spec["cell"], spec["conf"], spec["mix"]
    chips = cell["chips"]

    arch = load_arch(root, conf)
    cfg = arch.program_config(conf)
    mesh = make_serve_mesh(chips)
    params = weights.make(
        arch, conf, seed,
        param_shardings(mesh, cfg, weights.shapes(arch, conf)))
    eng = ServeEngine(cfg, params=params,
                      policy=ArithmeticPolicy(mode=policy),
                      ecfg=engine_config(conf, mix), seed=0)
    if fault is not None:
        fault(eng)
    gen = Generator(mix, conf["vocab_size"], seed)
    drv = Harness(eng, gen, mix)

    # set-up: compile with one short request, then ramp with the mix
    warm = gen.next_item()
    drv.submit(type(warm)(prompt=warm.prompt[:16], max_new_tokens=2,
                          sampled=warm.sampled, sample_seed=1), 0.0, False)
    drv.drain(list(drv.live), float("inf"))
    t_ramp = time.perf_counter()
    if mix.loop == "open":
        gen.set_pool(round(mix.rate_per_s * mix.ramp_s))
        drv.open_loop(gen.ramp_schedule(mix.ramp_s), t_ramp,
                      t_ramp + mix.ramp_s, False)
    else:
        if gen.documents:
            drv.start_documents()
        drv.closed_loop(time.perf_counter() + mix.ramp_s, False)
    registry = eng.obs.registry
    counters0 = {k: registry.count(k) for k in
                 ("backend/prompt_tokens", "backend/shared_tokens")}

    # the window
    if trace:
        log_dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(log_dir)
        drv.annotate = True
    t_start = time.perf_counter()
    setup_s = t_start - t_process
    n_compiles0 = len(compiles)
    first_traced = len(drv.steps)
    win = (jax.profiler.TraceAnnotation("window") if trace
           else contextlib.nullcontext())
    with win:
        if mix.loop == "open":
            offsets = gen.window_schedule(seconds)
            gen.set_pool(len(offsets))
            drv.open_loop(offsets, t_start, t_start + seconds, True)
        else:
            drv.closed_loop(t_start + seconds, True)
        t_end = time.perf_counter()
    t_stop = t_end
    if trace:
        jax.profiler.stop_trace()
        drv.annotate = False
        t_stop = time.perf_counter()
    traced = drv.steps[first_traced:]
    n_compiles = sum(1 for t in compiles[n_compiles0:] if t < t_end)

    # grace: the window's requests may finish.  It starts once the
    # profiler has written its trace, which can take longer than the
    # grace itself, so that a traced run waits as long as an untraced one
    in_window = [r for r, t in drv.tracks.items() if t.in_window]
    drv.drain(in_window, time.perf_counter() + mix.grace_s)
    t_grace_end = time.perf_counter()
    counters = {k: registry.count(k) - v for k, v in counters0.items()}

    rec = Record(t_start=t_start, t_end=t_end, t_grace_end=t_grace_end,
                 tracks=list(drv.tracks.values()), steps=drv.steps,
                 counters=counters,
                 shapes=flops.Shapes.of(conf), peak=peak)
    device = device_line(chips)
    breakdown = None
    if trace:
        try:
            tr = trace_reduce.load(trace_reduce.find_xplane(log_dir),
                                   host_ops=not on_chip)
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
        rec.trace, rec.trace_window = tr, tr.window()
        spans = tr.spans_named("engine.step")
        if len(spans) != len(traced):
            raise RuntimeError(f"the trace holds {len(spans)} engine.step "
                               f"spans for the {len(traced)} steps traced")
        for s, (lo, hi, _) in zip(traced, spans):
            s.span = (lo, hi)
        lo, hi = rec.trace_window
        device["busy_s"] = trace_reduce.busy_ns(tr, lo, hi) * 1e-9
        device["window_s"] = (hi - lo) * 1e-9
        breakdown = trace_reduce.breakdown(tr, lo, hi)

    if trace:
        metrics = {}
        for m in spec["per_layer"]:
            v = load_reader(root, m["name"]).read(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = dict(end_to_end(rec), setup_s=setup_s)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in spec["end_to_end"] if m["name"] in e2e}

    window_tracks = rec.window_tracks()
    stalled = [t.rid for t in window_tracks if t.done is None
               and (not t.tokens or t.tokens[-1] < t_grace_end - 2.0)]
    late = sorted(t.submitted - t.due for t in window_tracks) or [0.0]
    print(f"window: {seconds} s, {len(window_tracks)} requests due, "
          f"{sum(t.done is not None for t in window_tracks)} finished in "
          f"the grace of {mix.grace_s} s, {len(stalled)} stalled; "
          f"{len(rec.window_steps())} engine steps; profiler stopped in "
          f"{t_stop - t_end:.3f} s; generator late by "
          f"median {late[len(late) // 2] * 1e3:.3f} ms, max "
          f"{late[-1] * 1e3:.3f} ms; {n_compiles} compilations inside "
          f"the window", flush=True)

    # the check, once the program's state is freed
    served = [t.rid for t in rec.finished_tracks()]
    results = {rid: list(eng.requests[rid].generated) for rid in served}
    prompts = {rid: drv.prompts[rid] for rid in served}
    del drv, eng
    gc.collect()
    checks = check(arch, params, conf, mix, seed, rec, results, prompts)
    del params
    correct = all(c["value"] <= c["limit"] if c["kind"] == "max"
                  else c["value"] >= c["limit"] for c in checks.values())
    out = {"correct": bool(correct), "attempted": len(window_tracks),
           "failed": len(stalled), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                     for k, c in checks.items()}
    return out


def check(arch, params, conf, mix, seed, rec, results, prompts) -> dict:
    """Served greedy tokens against the plain float32 reference, over
    a sample of the greedy requests finished from the window's start on
    (those sent in the ramp among them)."""
    done = [t for t in rec.finished_tracks() if not t.sampled
            and len(results[t.rid]) == t.max_new_tokens]
    rng = np.random.default_rng([int(seed), 1])
    order = sorted(done, key=lambda t: -t.max_new_tokens)
    sample = order[:1]
    rest = [order[i] for i in rng.permutation(len(order))[:] if i > 0]
    for t in rest:
        if sum(x.max_new_tokens for x in sample) >= mix.check_tokens:
            break
        sample.append(t)
    worst, n = 0.0, 0
    for t in sample:
        served = np.asarray(results[t.rid], np.int32)
        prompt = np.asarray(prompts[t.rid], np.int32)
        seq = np.concatenate([prompt, served[:-1]])
        pos = np.arange(len(prompt) - 1, len(seq))
        g = reference.gaps(arch, params, conf, seq, pos, served,
                           mix.max_output)
        worst = max(worst, float(g.max()))
        n += len(served)
    limit = float(conf["correct"]["max_logit_gap"])
    return {"max_logit_gap": {"value": worst, "limit": limit, "kind": "max"},
            "tokens_compared": {"value": n, "limit": mix.check_tokens // 2,
                                "kind": "min"}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--policy", default="exact",
                    help="the program's arithmetic policy; `int8` is the "
                         "lower-precision control, never a benchmark run")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"no program at {ROOT / 'src' / 'repro'}: run from a "
              f"checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    use_compile_cache(ROOT)
    try:
        out = run_cell(ROOT, args.workload, args.seed, args.seconds,
                       bool(args.trace), policy=args.policy)
    except NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"correct: {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
