"""The peaks table and the operation and byte counts."""
import json
from pathlib import Path

import pytest

import bench_fixture
from flops import Shapes

import run as bench_run


def test_unknown_device_kind_raises(tmp_path):
    with pytest.raises(KeyError, match="no peaks"):
        bench_run.load_peaks(bench_fixture.REPO, "TPU v9 imaginary")
    v5e = bench_run.load_peaks(bench_fixture.REPO, "TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9


def test_qwen3_8b_counts():
    conf = json.loads((bench_fixture.BENCH / "configs/qwen3-8b-12l.json")
                      .read_text())
    sh = Shapes.of(conf)
    # 12 layers of (q, k, v, o) + SwiGLU, plus the 4096 x 151,936 head
    assert sh.layer_weights == 4096 * 128 * (64 + 16) + 3 * 4096 * 12288
    assert sh.matmul_weights == 12 * sh.layer_weights + 4096 * 151936
    assert sh.kv_bytes_per_token == 48 * 1024        # 48 KiB per token
    # a prompt chunk's FLOPs are the sum of its tokens' FLOPs
    assert sh.prompt_flops(100, 3) == pytest.approx(
        sum(sh.token_flops(c) for c in (101, 102, 103)))
    least = sh.decode_least_bytes(lanes=32, distinct_kv_tokens=1000)
    assert least == 2 * sh.matmul_weights + 32 * 4096 * 2 + 1000 * 48 * 1024


def test_published_depth_is_the_only_cut():
    conf = json.loads((bench_fixture.BENCH / "configs/qwen3-8b-12l.json")
                      .read_text())
    bench = json.loads((bench_fixture.REPO / "BENCHMARK.json").read_text())
    [entry] = [c for c in bench["configs"] if c["name"] == "qwen3-8b-12l"]
    assert entry["reduced"] == ["num_hidden_layers"]
    assert Path(entry["file"]).name == "qwen3-8b-12l.json"
    assert conf["published"] == {"num_hidden_layers": 36}
    assert (conf["hidden_size"], conf["intermediate_size"],
            conf["num_attention_heads"], conf["num_key_value_heads"],
            conf["head_dim"], conf["vocab_size"]) == \
        (4096, 12288, 32, 8, 128, 151936)
