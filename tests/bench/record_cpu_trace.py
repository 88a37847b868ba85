"""Record the short CPU trace that test_bench_trace_reduce.py reads.

    JAX_PLATFORMS=cpu python tests/bench/record_cpu_trace.py

Three `engine.step` spans inside one `window` span, each running a
small jitted matmul to completion, with a `wait_arrival` sleep between
them: the host spans and operations the benchmark's reduction reads.
Writes tests/bench/data/cpu_trace.xplane.pb.
"""
import shutil
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp

BENCH = Path(__file__).resolve().parents[2] / "bench"
OUT = Path(__file__).resolve().parent / "data" / "cpu_trace.xplane.pb"


def main() -> None:
    import sys
    sys.path.insert(0, str(BENCH))
    import trace_reduce
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((384, 384), jnp.float32)
    f(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation("window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("engine.step"):
                    f(x).block_until_ready()
                with jax.profiler.TraceAnnotation("wait_arrival"):
                    time.sleep(0.005)
        jax.profiler.stop_trace()
        OUT.parent.mkdir(exist_ok=True)
        shutil.copy(trace_reduce.find_xplane(tmp), OUT)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(OUT, OUT.stat().st_size, "bytes")


if __name__ == "__main__":
    main()
