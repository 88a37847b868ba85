"""Ahead-of-time compiles for a described TPU v5e: the Pallas kernels,
and the paged serve steps' handling of the KV pool.

Nothing here runs on a chip: the TPU compiler, installed with jaxlib,
lowers each kernel for a `v5e:2x2` topology that is described, not
attached, and refuses what Mosaic would refuse on the chip (block
shapes off the (8, 128) tiling, too much VMEM).  Interpret-mode parity
tests cannot see those faults.  Each kernel test asserts the compiled
program holds a `tpu_custom_call`, i.e. the kernel was lowered, not
interpreted; the step tests read the compiled program for slices and
copies of the KV pool, which the TPU compiler's own buffer assignment
decides.

The topology is described inside a module fixture, never at import
time: only one process may hold the TPU library, and the test workers
all import this module.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels.flash_attention import flash_attention_kernel
from repro.kernels.paged_attention import paged_attention
from repro.kernels.sc_matmul.sc_matmul import sc_matmul_quantized
from repro.models import model
from repro.serve import make_paged_chunked_prefill, make_paged_decode


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this jaxlib
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU executable written to the persistent cache cannot be read
    # back without a chip; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compiled_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


# (batch, chunk, q heads, kv heads, head dim, page, pool dtype):
# gemma-2b (8/1 x 256) and qwen3-8b (32/8 x 128) at published widths
PAGED_SHAPES = {
    "gemma2b_decode_f32": (8, 1, 8, 1, 256, 16, jnp.float32),
    "gemma2b_chunk32_bf16": (8, 32, 8, 1, 256, 16, jnp.bfloat16),
    "qwen3_8b_decode_f32": (8, 1, 32, 8, 128, 16, jnp.float32),
}


@pytest.mark.parametrize("name", sorted(PAGED_SHAPES))
def test_paged_attention_compiles_for_v5e(one_chip, name):
    b, s, h, kvh, hd, page, dt = PAGED_SHAPES[name]
    n_pages, pmax = 256, 32

    def fn(q, kp, vp, bt, pos):
        return paged_attention(q, kp, vp, bt, pos, interpret=False)

    text = _compiled_text(
        fn, one_chip, ((b, s, h, hd), dt), ((n_pages, page, kvh, hd), dt),
        ((n_pages, page, kvh, hd), dt), ((b, pmax), jnp.int32),
        ((b, s), jnp.int32))
    assert "tpu_custom_call" in text


def test_flash_attention_compiles_for_v5e(one_chip):
    def fn(q, k, v):
        return flash_attention_kernel(q, k, v, causal=True,
                                      interpret=False)[0]

    text = _compiled_text(fn, one_chip, ((1, 8, 512, 128), jnp.bfloat16),
                          ((1, 2, 512, 128), jnp.bfloat16),
                          ((1, 2, 512, 128), jnp.bfloat16))
    assert "tpu_custom_call" in text


def test_sc_matmul_compiles_for_v5e(one_chip):
    def fn(a, b):
        return sc_matmul_quantized(a, b, mode="int8", interpret=False)

    text = _compiled_text(fn, one_chip, ((256, 512), jnp.int8),
                          ((512, 256), jnp.int8))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("step", ["decode", "chunked_prefill"])
def test_paged_step_keeps_the_kv_pool_in_place(one_chip, step):
    """The paged step reads and writes each layer's pages straight in
    the stacked, donated pool: no instruction yields one layer's pool,
    and no copy of the whole pool is made.  qwen3-8b widths, 2 layers."""
    cfg = dataclasses.replace(configs.get_config("qwen3_8b"), n_layers=2)
    b, c, pmax, n_pages, page = 8, 16, 32, 512, 16
    kvh, hd = cfg.n_kv_heads, cfg.resolved_head_dim

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda s: sds(s.shape, jnp.bfloat16),
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), cfg)))
    pool = sds((cfg.n_layers, n_pages, page, kvh, hd), jnp.bfloat16)
    kv = {"k": pool, "v": pool}
    if step == "decode":
        fn = make_paged_decode(cfg)
        args = (params, sds((b, 1)), kv, sds((b, pmax)), sds((b,)),
                sds((b,), bool))
    else:
        fn = make_paged_chunked_prefill(cfg)
        args = (params, sds((b, c)), kv, sds((b, pmax)), sds((b,)),
                sds((b,)), sds((b,), bool), sds((b,)))
    text = jax.jit(fn, donate_argnums=(2,)).lower(*args).compile().as_text()
    one_layer = re.findall(rf"(?:=|->) bf16\[{n_pages},{page},{kvh},{hd}\]",
                           text)
    whole = (rf"bf16\[(?:{cfg.n_layers},{n_pages}|{cfg.n_layers * n_pages})"
             rf",{page},{kvh},{hd}\]")
    pool_copies = re.findall(rf"= \(?{whole}[^=\n]* copy(?:-start)?\(", text)
    assert not one_layer, one_layer[:3]
    assert not pool_copies, pool_copies[:3]
