"""Launch-layer integration tests on a real (tiny) mesh.

Lower + compile + EXECUTE smoke configs on a (2, 2) in-process mesh —
the same code path the 512-device dry-run exercises, plus actual
numerics: a sharded train step must match the single-device train step.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.policy import ArithmeticPolicy
from repro.data import DataConfig, make_batch
from repro.launch import specs as specslib
from repro.launch import steps as stepslib
from repro.launch.mesh import make_smoke_mesh
from repro.models import model
from repro.optim import OptimizerConfig, adamw_init
from repro.parallel import sharding as sh

pytestmark = pytest.mark.skipif(jax.device_count() < 4,
                                reason="needs 4 host devices")


def _named(mesh, spec_tree):
    return jax.tree.map(
        lambda s: jax.sharding.NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))


@pytest.mark.parametrize("arch", ["qwen3_8b", "qwen2_moe_a2_7b",
                                  "rwkv6_3b"])
def test_sharded_train_step_matches_single_device(arch):
    cfg = configs.get_config(arch, smoke=True)
    mesh = make_smoke_mesh(2, 2)
    rules = sh.ShardingRules()
    opt_cfg = OptimizerConfig(lr=1e-3, warmup_steps=1, total_steps=10)

    params = model.init(jax.random.PRNGKey(0), cfg)
    opt = adamw_init(params)
    batch = make_batch(cfg, DataConfig(seq_len=16, global_batch=4), 0)

    # single-device reference
    ref_step = jax.jit(stepslib.make_train_step(cfg, opt_cfg))
    p_ref, _, m_ref = ref_step(params, opt, batch)

    # sharded
    pspecs = sh.param_specs(cfg, params, mesh, rules)
    psh = _named(mesh, pspecs)
    osh = _named(mesh, {"m": pspecs, "v": pspecs,
                        "step": jax.sharding.PartitionSpec()})
    bsh = _named(mesh, sh.batch_specs(cfg, mesh, 4))
    step = jax.jit(
        stepslib.make_train_step(cfg, opt_cfg, mesh=mesh, rules=rules),
        in_shardings=(psh, osh, bsh),
        out_shardings=(psh, osh,
                       jax.sharding.NamedSharding(
                           mesh, jax.sharding.PartitionSpec())))
    params_s = jax.device_put(params, psh)
    opt_s = jax.device_put(opt, osh)
    batch_s = jax.device_put(batch, bsh)
    p_out, _, m_out = step(params_s, opt_s, batch_s)

    assert float(m_out["loss"]) == pytest.approx(float(m_ref["loss"]),
                                                 rel=2e-3)
    # parameters after one step agree (sharded == unsharded math)
    ref_leaves = jax.tree.leaves(p_ref)
    out_leaves = jax.tree.leaves(p_out)
    worst = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                      - b.astype(jnp.float32))))
                for a, b in zip(ref_leaves, out_leaves))
    assert worst < 5e-3, worst


def test_sharded_decode_matches_single_device():
    cfg = configs.get_config("qwen3_8b", smoke=True)
    mesh = make_smoke_mesh(2, 2)
    rules = dataclasses.replace(sh.ShardingRules(), fsdp=False)
    b, s, max_len = 4, 12, 16

    params = model.init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    cache = model.init_cache(cfg, b, max_len, dtype=jnp.float32)

    prefill = jax.jit(stepslib.make_prefill_step(cfg))
    decode = jax.jit(stepslib.make_decode_step(cfg))
    logits_ref, cache_ref = prefill(params, {"tokens": tokens[:, :-1]},
                                    cache)
    dec_ref, _ = decode(params, tokens[:, -1:], cache_ref)

    pspecs = sh.param_specs(cfg, params, mesh, rules)
    psh = _named(mesh, pspecs)
    csh = _named(mesh, sh.cache_specs(cfg, mesh, b, rules))
    tok_sh = _named(mesh, sh.batch_specs(cfg, mesh, b)["tokens"])
    logits_sh = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("data", "model"))
    prefill_s = jax.jit(
        stepslib.make_prefill_step(cfg, mesh=mesh, rules=rules),
        in_shardings=(psh, {"tokens": tok_sh}, csh),
        out_shardings=(logits_sh, csh))
    decode_s = jax.jit(
        stepslib.make_decode_step(cfg, mesh=mesh, rules=rules),
        in_shardings=(psh, tok_sh, csh),
        out_shardings=(logits_sh, csh))

    params_d = jax.device_put(params, psh)
    cache_d = jax.device_put(model.init_cache(cfg, b, max_len,
                                              dtype=jnp.float32), csh)
    _, cache_d = prefill_s(params_d, {"tokens": jax.device_put(
        tokens[:, :-1], tok_sh)}, cache_d)
    dec_out, _ = decode_s(params_d, jax.device_put(tokens[:, -1:], tok_sh),
                          cache_d)
    # bf16 compute with sharded (reassociated) contractions: ~2e-2 noise
    np.testing.assert_allclose(np.asarray(dec_out, np.float32),
                               np.asarray(dec_ref, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_dryrun_cell_compiles_on_tiny_mesh():
    """The dry-run lowering path end-to-end on 4 devices (smoke config,
    reduced cell) — the in-process analogue of the 512-device sweep."""
    from repro.launch.dryrun import cost_analysis_dict, lower_cell
    cfg = configs.get_config("qwen3_8b", smoke=True)
    cell = configs.ShapeCell("t", 64, 4, "train")
    mesh = make_smoke_mesh(2, 2)
    lowered = lower_cell(cfg, cell, mesh, sh.ShardingRules(),
                         ArithmeticPolicy(), unroll=1)
    compiled = lowered.compile()
    assert cost_analysis_dict(compiled).get("flops", 0) > 0
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 0


def test_input_specs_cover_all_kinds():
    for arch in ("qwen3_8b", "musicgen_large", "internvl2_1b",
                 "zamba2_7b"):
        cfg = configs.get_config(arch)  # FULL config, shapes only
        for shape in configs.runnable_shapes(arch):
            cell = configs.SHAPES[shape]
            ins = specslib.input_specs(cfg, cell)
            assert "params" in ins
            leaves = jax.tree.leaves(ins)
            assert all(isinstance(l, jax.ShapeDtypeStruct) for l in leaves)
            if cell.kind == "decode":
                tok = ins["tokens"]
                assert tok.shape[1] == 1


def test_compile_cache_dir_rule(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins and the code sets nothing;
    otherwise the cache is the fixed in-checkout `.jax_cache`."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from repro.launch.compile_cache import (CHECKOUT_CACHE_DIR,
                                            use_compile_cache)
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert use_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert use_compile_cache() == str(CHECKOUT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(
            CHECKOUT_CACHE_DIR)
        assert CHECKOUT_CACHE_DIR.name == ".jax_cache"
        assert (CHECKOUT_CACHE_DIR.parent / "src" / "repro").is_dir()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        cc.reset_cache()
