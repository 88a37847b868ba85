"""The check that decides `correct`, driven through the rest of a run
with the chip look skipped: sound runs pass, and a run whose timed path
is broken underneath comes out not correct.  The faults a serving cell
on one chip can have: a token altered where it is produced (the
sampler), and a step that returns its state unchanged (the decode step
writes no K/V into the pool).  The lower-precision control (the
program's own int8 path) is in test_bench_control.py."""
import pytest

import bench_fixture


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_fixture.tiny_root(tmp_path_factory.mktemp("bench"))


def altered_token(eng):
    vocab = eng.cfg.vocab_size
    sample = eng._sample_rows
    eng._sample_rows = lambda logits, rows: (sample(logits, rows) + 1) % vocab


def state_unchanged(eng):
    import jax
    import jax.numpy as jnp
    backend = eng.backend
    decode = backend._decode_fn

    def frozen(params, tokens, kv, *rest):
        before = jax.tree.map(jnp.copy, kv)
        logits, _ = decode(params, tokens, kv, *rest)
        return logits, before

    backend._decode_fn = frozen


@pytest.mark.parametrize("cell", bench_fixture.CELLS)
def test_sound_run_is_correct(root, cell):
    out = bench_fixture.run(root, cell, 2 ** 31 + 3)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert out["failed"] == 0 and out["attempted"] > 0


@pytest.mark.parametrize("fault", [altered_token, state_unchanged],
                         ids=["altered_token", "state_unchanged"])
@pytest.mark.parametrize("cell", bench_fixture.CELLS)
def test_fault_is_not_correct(root, cell, fault):
    out = bench_fixture.run(root, cell, 2 ** 31 + 3, fault=fault)
    assert not out["correct"]
    gap = out["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]
