"""`bench/run.py` as a check starts it: without a TPU, or in a
directory that holds only the benchmark's own files, it exits non-zero
and prints no result line."""
import json
import os
import shutil
import subprocess
import sys

import bench_fixture

ARGS = ["--workload", "qwen3-8b-12l.chat-open", "--seed", "2147483649",
        "--seconds", "1", "--trace", "0"]


def run_cli(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(script)] + ARGS, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def no_result(proc) -> bool:
    for line in proc.stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return False
        except (json.JSONDecodeError, TypeError):
            continue
    return True


def test_exits_nonzero_without_a_tpu():
    proc = run_cli(bench_fixture.REPO, bench_fixture.BENCH / "run.py")
    assert proc.returncode != 0
    assert no_result(proc)
    assert "no TPU" in proc.stderr


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(bench_fixture.REPO / "BENCHMARK.json", tmp_path)
    bench = json.loads((bench_fixture.REPO / "BENCHMARK.json").read_text())
    for p in bench["paths"]:
        shutil.copytree(bench_fixture.REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = run_cli(tmp_path, tmp_path / "bench" / "run.py")
    assert proc.returncode != 0
    assert no_result(proc)
