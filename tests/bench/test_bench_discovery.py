"""A configuration, a traffic mix and a per-layer metric are added as
files plus BENCHMARK.json entries, and the harness finds them without
an edit to its code.  The configuration is of another layout than the
benchmark's own (Llama: no QK-norm, other head counts), picked by its
`model_type` from `bench/arch/`."""
import json

import bench_fixture


def test_new_cell_and_metric_found_by_name(tmp_path):
    root = bench_fixture.tiny_root(tmp_path)
    code = {p.relative_to(root): p.read_bytes()
            for p in (root / "bench").rglob("*.py")}
    # a new configuration, a new mix and a new per-layer metric: files
    conf = dict(bench_fixture.TINY, name="tiny-wide", model_type="llama",
                num_attention_heads=8, num_key_value_heads=4)
    (root / "bench/configs/tiny-wide.json").write_text(json.dumps(conf))
    mix = dict(bench_fixture.MIXES["tiny-closed"], clients=2, prefix=None)
    (root / "bench/traffic/pairs.json").write_text(json.dumps(mix))
    (root / "bench/metrics/steps_in_window.py").write_text(
        'UNIT = "steps"\n\n\ndef read(rec):\n'
        '    return float(len(rec.window_steps()))\n')
    # ... and entries
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-wide", "source": "test",
                             "file": "bench/configs/tiny-wide.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-wide.pairs",
                               "config": "tiny-wide", "traffic": "pairs",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "steps_in_window", "unit": "steps",
                               "better": "higher", "source": "host_clock",
                               "layer": "scheduler", "moves": "tok_per_s",
                               "workloads": ["tiny-wide.pairs"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    out = bench_fixture.run(root, "tiny-wide.pairs", 3, trace=True)
    assert out["metrics"]["steps_in_window"]["value"] > 0
    assert out["metrics"]["steps_in_window"]["unit"] == "steps"
    assert "decode_step_device_ms" not in out["metrics"]   # not listed
    assert out["attempted"] > 0 and out["correct"], out["checks"]
    assert code == {p.relative_to(root): p.read_bytes()
                    for p in (root / "bench").rglob("*.py")
                    if p.name != "steps_in_window.py"}
