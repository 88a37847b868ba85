"""A checkout of the benchmark at a size the CPU test run can hold.

`tiny_root(tmp)` copies `bench/` into `tmp` and adds a qwen3-shaped
configuration at a size the CPU holds (d 256, 4/2 heads x 64, d_ff
768, vocab 4096, 4 layers, bf16 as served, 8 lanes) and two small mixes,
one open-loop and one closed-loop with shared prefixes, as two cells
in a BENCHMARK.json that keeps the real metric entries.  The tests
drive `bench/run.py`'s `run_cell` on it with the look for a chip
skipped.
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

# widest gap of served greedy tokens below the float32 reference at
# this size: sound bf16 runs read up to 0.035 (open) and 0.026
# (closed) over 12 seeds each, the program's int8 path 0.100 and
# 0.060 at the least over 3 (see PERF.md)
TINY_LIMIT = 0.05

TINY = {
    "name": "tiny", "model_type": "qwen3", "hidden_size": 256, "intermediate_size": 768,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64,
    "num_hidden_layers": 4, "vocab_size": 4096, "hidden_act": "silu",
    "rms_norm_eps": 1e-06, "rope_theta": 1000000,
    "tie_word_embeddings": False,
    "serve": {"param_dtype": "bfloat16", "cache_dtype": "bfloat16",
              "compute_dtype": "bfloat16", "mesh_shards": 1,
              "page_size": 4, "max_batch": 8, "prefill_chunk": 16,
              "n_pages": 400, "attn_impl": "gather", "scheduler": "cost"},
    "correct": {"max_logit_gap": TINY_LIMIT},
}
MIXES = {
    "tiny-open": {
        "loop": "open", "rate_per_s": 20,
        "prompt": {"dist": "lognormal", "median": 16, "sigma": 0.5,
                   "min": 4, "max": 40},
        "output": {"dist": "uniform", "min": 8, "max": 16},
        "sampled_share": 0.5, "top_p": 0.95, "ramp_s": 0.5,
        "grace_s": 20, "check_tokens": 100},
    "tiny-closed": {
        "loop": "closed", "clients": 4, "pool": 16,
        "prefix": {"documents": 2, "length": 16},
        "prompt": {"dist": "uniform", "min": 4, "max": 12},
        "output": {"dist": "uniform", "min": 8, "max": 16},
        "ramp_s": 0.5, "grace_s": 20, "check_tokens": 100},
}
CELLS = ("tiny.open", "tiny.closed")


def tiny_root(tmp: Path) -> Path:
    root = Path(tmp)
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "bench/configs/tiny.json").write_text(json.dumps(TINY))
    for name, mix in MIXES.items():
        (root / f"bench/traffic/{name}.json").write_text(json.dumps(mix))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": "test",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "test size"}]
    bench["workloads"] = [
        {"name": "tiny.open", "config": "tiny", "traffic": "tiny-open",
         "chips": 1, "why": "test"},
        {"name": "tiny.closed", "config": "tiny", "traffic": "tiny-closed",
         "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = list(CELLS)
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root


def arch(conf: dict):
    """The architecture file of `conf`, as the harness loads it."""
    import run as bench_run
    return bench_run.load_arch(REPO, conf)


def run(root: Path, cell: str, seed: int, trace: bool = False, **kw) -> dict:
    import time

    import run as bench_run
    return bench_run.run_cell(root, cell, seed, 3.0, trace,
                              check_chips=False, t_process=time.perf_counter(),
                              **kw)
