"""The plain float32 reference matches the program's own forward
(`model.apply`) at the qwen3-8b SMOKE widths on the CPU, on weights the
benchmark made, for each architecture file (Qwen3 with QK-norm, Llama
without); and its gap reading is zero for the reference's own greedy
tokens."""
import dataclasses

import numpy as np
import pytest

import bench_fixture
import reference
import weights

# the program's qwen3-8b SMOKE widths, in float32
SMOKE = dict(bench_fixture.TINY, hidden_size=64, intermediate_size=128,
             num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             num_hidden_layers=2, vocab_size=256)
SMOKE["serve"] = dict(SMOKE["serve"], param_dtype="float32")


@pytest.fixture(scope="module", params=["qwen3", "llama"])
def setup(request):
    import jax
    from repro import configs

    conf = dict(SMOKE, model_type=request.param)
    arch = bench_fixture.arch(conf)
    cfg = dataclasses.replace(configs.get_config("qwen3_8b", smoke=True),
                              compute_dtype="float32", param_dtype="float32",
                              rope_theta=float(conf["rope_theta"]),
                              qk_norm=arch.QK_NORM)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff,
            cfg.vocab_size) == (conf["hidden_size"],
                                conf["num_attention_heads"],
                                conf["num_key_value_heads"],
                                conf["intermediate_size"], 256)
    assert arch.program_config(conf).qk_norm == cfg.qk_norm
    params = weights.make(arch, conf, seed=2 ** 33 + 11)
    assert ("q_norm" in params["layers"]["attn"]) == arch.QK_NORM
    tokens = np.random.default_rng(0).integers(2, 256, 77).astype(np.int32)
    return jax, arch, conf, cfg, params, tokens


def test_reference_matches_model_apply(setup):
    jax, arch, conf, cfg, params, tokens = setup
    from repro.models import model
    with jax.default_matmul_precision("highest"):
        want = model.apply(params, cfg, {"tokens": tokens[None]},
                           remat=False)[0][0]
    pos = np.arange(len(tokens))
    got = reference.logits(arch, params, conf, tokens, pos, rows=128)
    want = np.asarray(want)
    assert got.shape == want.shape
    assert np.max(np.abs(got - want)) <= 1e-4 * np.max(np.abs(want))


def test_gap_of_the_references_own_tokens_is_zero(setup):
    _, arch, conf, _, params, tokens = setup
    pos = np.arange(40, 77)
    lg = reference.logits(arch, params, conf, tokens, pos, rows=64)
    best = lg.argmax(axis=1).astype(np.int32)
    assert reference.gaps(arch, params, conf, tokens, pos, best,
                          64).max() == 0.0
    other = (best + 1) % 256
    assert reference.gaps(arch, params, conf, tokens, pos, other,
                          64).min() > 0.0


def test_weights_follow_the_seed():
    conf = dict(bench_fixture.TINY)
    arch = bench_fixture.arch(conf)
    a = weights.make(arch, conf, 5)["layers"]["attn"]["wq"]
    b = weights.make(arch, conf, 5)["layers"]["attn"]["wq"]
    c = weights.make(arch, conf, 5 + 2 ** 32)["layers"]["attn"]["wq"]
    assert np.array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))
    assert str(a.dtype) == "bfloat16"


def test_unknown_model_type_is_refused():
    with pytest.raises(FileNotFoundError, match="model_type"):
        bench_fixture.arch(dict(SMOKE, model_type="gpt_bigcode"))
