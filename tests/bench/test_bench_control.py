"""The lower-precision control comes out not correct.

The configuration serves in bfloat16; the nearest precision below is
int8, and the program has an int8 path of its own
(`ArithmeticPolicy(mode="int8")`, every dense and attention matmul
quantized to int8 per tensor), so the program with that path switched
on is the control.  At this test size sound bf16 runs read a widest
gap of up to 0.035 and the int8 path 0.060 or more (PERF.md), and the
limit 0.05 lies between; the chip readings at the cells' own size are
in PERF.md."""
import pytest

import bench_fixture


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_fixture.tiny_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell", bench_fixture.CELLS)
def test_int8_control_is_not_correct(root, cell):
    out = bench_fixture.run(root, cell, 2 ** 32 + 17, policy="int8")
    gap = out["checks"]["max_logit_gap"]
    assert not out["correct"]
    assert gap["value"] > gap["limit"]
