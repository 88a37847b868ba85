"""Record the CPU trace of a tiny serve engine that
test_bench_program_trace.py reads.

    JAX_PLATFORMS=cpu PYTHONPATH=src python tests/bench/record_cpu_engine_trace.py

A one-layer qwen3-shaped engine (d 64, vocab 256, 4 lanes, chunks of 8)
serves three requests inside one `window` span, each `engine.step()`
inside an `engine.step` span as the benchmark's harness wraps it, with
the program's own spans on (`eng.obs.profiling`).  Its steps prefill
chunks that complete no prompt, complete prompts, and decode alone.
XLA runs on one CPU thread and Python calls are not traced, which
keeps the file small.  Writes tests/bench/data/cpu_engine_trace.xplane.pb
and, beside it, cpu_engine_trace.json: the engine's counters over the
traced steps, and the named scope of each operation of the trace, from
the compiled programs' HLO `op_name` paths, since a CPU trace's
operations carry no name path.
"""
import dataclasses
import json
import os
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1] / "bench"))
OUT = HERE / "data" / "cpu_engine_trace.xplane.pb"
COUNTERS = ("engine/decode_forwards", "engine/prefill_forwards",
            "backend/prefill_tokens", "backend/prefill_positions")


def record(out: Path) -> dict:
    """Serve the requests under the profiler, copy the trace to `out`
    and write its side file; returns the side file's content."""
    import jax

    import program_trace
    import trace_reduce
    from repro import configs
    from repro.serve import EngineConfig, ServeEngine

    cfg = dataclasses.replace(configs.get_config("qwen3_8b", smoke=True),
                              n_layers=1)
    ecfg = EngineConfig(page_size=4, n_pages=64, max_batch=4,
                        max_pages_per_seq=16, prefill_chunk=8)
    eng = ServeEngine(cfg, ecfg=ecfg, seed=0)
    # compile every program once, outside the trace
    eng.submit(list(range(1, 12)), 3)
    eng.drain()
    for n, prompt_len, gen in ((0, 20, 3), (1, 5, 3), (2, 13, 2)):
        eng.submit([(7 * n + i) % cfg.vocab_size for i in range(prompt_len)],
                   gen)
    reg = eng.obs.registry
    before = {k: reg.count(k) for k in COUNTERS}
    tmp = tempfile.mkdtemp()
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # no event per Python call
        opts.enable_hlo_proto = False    # nor the programs' HLO
        jax.profiler.start_trace(tmp, profiler_options=opts)
        eng.obs.profiling = True
        with jax.profiler.TraceAnnotation("window"):
            while True:
                with jax.profiler.TraceAnnotation("engine.step"):
                    ev = eng.step()
                if ev is None:
                    break
            jax.block_until_ready(eng.backend.cache.kv)
        eng.obs.profiling = False
        jax.profiler.stop_trace()
        out.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(trace_reduce.find_xplane(tmp), out)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    scopes = {m: {op: program_trace.scope_of(path)
                  for op, path in ops.items() if program_trace.scope_of(path)}
              for m, ops in program_trace.engine_op_paths(eng).items()}
    ran = {(m, n) for _, _, n, m, _ in
           program_trace.load(str(out), host_ops=True).ops[0]}
    side = {"counters": {k: reg.count(k) - v for k, v in before.items()},
            "op_scopes": {m: {n: sc for n, sc in ops.items() if (m, n) in ran}
                          for m, ops in scopes.items()}}
    out.with_suffix("").with_suffix(".json").write_text(
        json.dumps(side, indent=1, sort_keys=True) + "\n")
    return side


def main() -> None:
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_cpu_multi_thread_eigen=false"
                               " intra_op_parallelism_threads=1")
    record(OUT)
    print(OUT, OUT.stat().st_size, "bytes")


if __name__ == "__main__":
    main()
