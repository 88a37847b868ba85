"""Distribution-layer tests: ring attention, split-KV decode, compression,
sharding rules. Runs on 8 forced host devices (separate process group via
pytest-forked isn't available, so this file must NOT import before the
flag is set — conftest does not set it; we use a module-level guard)."""
import os
import sys

# must happen before jax initializes its backends; pytest imports this
# module before any other jax usage ONLY when run standalone — so guard:
if "jax" not in sys.modules:
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import AxisType  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro import configs  # noqa: E402
from repro.models import model  # noqa: E402
from repro.parallel import (  # noqa: E402
    ShardingRules,
    batch_specs,
    cache_specs,
    compressed_psum,
    init_compression,
    param_specs,
    ring_attention,
    split_kv_attention,
)
from repro.parallel.ring_attention import layer_dataflow_attention  # noqa: E402

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs 8 host devices (run standalone or first)")


def _mesh(shape, names):
    return jax.make_mesh(shape, names,
                         axis_types=(AxisType.Auto,) * len(shape))


def _ref_attention(q, k, v, causal=True):
    b, s, h, d = q.shape
    scale = 1.0 / d**0.5
    s_ = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        mask = jnp.tril(jnp.ones((s, s), bool))
        s_ = jnp.where(mask[None, None], s_, -1e30)
    p = jax.nn.softmax(s_, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


class TestRingAttention:
    def test_matches_full_attention(self):
        mesh = _mesh((8,), ("sp",))
        b, s, h, d = 2, 64, 4, 16
        key = jax.random.PRNGKey(0)
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (b, s, h, d), jnp.float32)
        k = jax.random.normal(kk, (b, s, h, d), jnp.float32)
        v = jax.random.normal(kv, (b, s, h, d), jnp.float32)

        ref = _ref_attention(q, k, v)

        fn = jax.shard_map(
            lambda q, k, v: ring_attention(q, k, v, axis_name="sp"),
            mesh=mesh,
            in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
            out_specs=P(None, "sp"))
        out = jax.jit(fn)(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_layer_dataflow_matches(self):
        mesh = _mesh((8,), ("sp",))
        b, s, h, d = 1, 64, 2, 8
        key = jax.random.PRNGKey(1)
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (b, s, h, d), jnp.float32)
        k = jax.random.normal(kk, (b, s, h, d), jnp.float32)
        v = jax.random.normal(kv, (b, s, h, d), jnp.float32)
        ref = _ref_attention(q, k, v)
        fn = jax.shard_map(
            lambda q, k, v: layer_dataflow_attention(q, k, v,
                                                     axis_name="sp"),
            mesh=mesh,
            in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
            out_specs=P(None, "sp"))
        out = jax.jit(fn)(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_non_causal(self):
        mesh = _mesh((8,), ("sp",))
        b, s, h, d = 1, 32, 2, 8
        q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (b, s, h, d))
                   for i in range(3))
        ref = _ref_attention(q, k, v, causal=False)
        fn = jax.shard_map(
            lambda q, k, v: ring_attention(q, k, v, axis_name="sp",
                                           causal=False),
            mesh=mesh,
            in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"))
        out = jax.jit(fn)(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


class TestSplitKV:
    def test_decode_matches_full(self):
        mesh = _mesh((8,), ("kvs",))
        b, s_cache, h, d = 2, 64, 4, 16
        key = jax.random.PRNGKey(2)
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (b, 1, h, d), jnp.float32)
        k = jax.random.normal(kk, (b, s_cache, h, d), jnp.float32)
        v = jax.random.normal(kv, (b, s_cache, h, d), jnp.float32)

        # reference: decode against full cache (query at position s_cache-1)
        scale = 1.0 / d**0.5
        s_ = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        p = jax.nn.softmax(s_, axis=-1)
        ref = jnp.einsum("bhqk,bkhd->bqhd", p, v)

        q_pos = jnp.full((b, 1), s_cache - 1, jnp.int32)
        kv_pos = jnp.broadcast_to(
            jnp.arange(s_cache, dtype=jnp.int32)[None], (b, s_cache))

        def f(q, k_loc, v_loc, kvp):
            return split_kv_attention(q, k_loc, v_loc, axis_name="kvs",
                                      q_positions=q_pos,
                                      kv_positions_local=kvp)

        fn = jax.shard_map(
            f, mesh=mesh,
            in_specs=(P(), P(None, "kvs"), P(None, "kvs"), P(None, "kvs")),
            out_specs=P())
        out = jax.jit(fn)(q, k, v, kv_pos)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_empty_slots_masked(self):
        """Slots with position INT32_MAX (> query pos) must not contribute."""
        mesh = _mesh((8,), ("kvs",))
        b, s_cache, h, d = 1, 32, 2, 8
        q = jax.random.normal(jax.random.PRNGKey(3), (b, 1, h, d))
        k = jax.random.normal(jax.random.PRNGKey(4), (b, s_cache, h, d))
        v = jax.random.normal(jax.random.PRNGKey(5), (b, s_cache, h, d))
        valid = 17  # only the first 17 slots are real
        kv_pos = jnp.where(jnp.arange(s_cache) < valid,
                           jnp.arange(s_cache),
                           jnp.iinfo(jnp.int32).max)[None]
        q_pos = jnp.full((b, 1), valid - 1, jnp.int32)

        scale = 1.0 / d**0.5
        s_ = jnp.einsum("bqhd,bkhd->bhqk", q[:, :, :, :],
                        k[:, :valid]) * scale
        ref = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s_, -1),
                         v[:, :valid])

        fn = jax.shard_map(
            lambda q, kl, vl, kp: split_kv_attention(
                q, kl, vl, axis_name="kvs", q_positions=q_pos,
                kv_positions_local=kp),
            mesh=mesh,
            in_specs=(P(), P(None, "kvs"), P(None, "kvs"), P(None, "kvs")),
            out_specs=P())
        out = jax.jit(fn)(q, k, v, jnp.broadcast_to(kv_pos, (b, s_cache)))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


class TestCompression:
    @pytest.mark.parametrize("mode", ["none", "bf16", "int8"])
    def test_psum_close_to_exact(self, mode):
        mesh = _mesh((8,), ("dp",))
        g = jax.random.normal(jax.random.PRNGKey(0), (8, 64), jnp.float32)
        state = init_compression({"w": g[0]}, mode)

        def f(g):
            out, _ = compressed_psum({"w": g}, state, "dp")
            return out["w"]

        fn = jax.shard_map(f, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))
        out = jax.jit(fn)(g.reshape(8, 1, 64).reshape(8, 64))
        exact = jnp.mean(g, axis=0)
        tol = {"none": 1e-6, "bf16": 1e-2, "int8": 3e-2}[mode]
        err = float(jnp.max(jnp.abs(out[0] - exact)))
        scale = float(jnp.max(jnp.abs(exact))) + 1e-9
        assert err / scale < tol

    def test_error_feedback_cumulative_convergence(self):
        """EF guarantees the CUMULATIVE applied update tracks the true sum:
        sum_t out_t -> sum_t exact_t (the per-step dither cancels)."""
        mesh = _mesh((8,), ("dp",))
        key = jax.random.PRNGKey(7)
        # gradient with a tiny component that int8 alone would always round
        # away (magnitude << scale/127) — EF must recover it over steps
        g = jax.random.normal(key, (8, 128), jnp.float32)
        g = g.at[:, 0].set(10.0)       # forces a coarse quantization scale
        g = g.at[:, 1].set(0.01)       # far below one quantization step

        def f(gl, err):
            st = CompressionStateLike("int8", {"w": err})
            out, new_st = compressed_psum({"w": gl}, st, "dp")
            return out["w"], new_st.error["w"]

        from repro.parallel.compress import CompressionState as \
            CompressionStateLike
        fn = jax.jit(jax.shard_map(f, mesh=mesh,
                               in_specs=(P("dp"), P("dp")),
                               out_specs=(P("dp"), P("dp"))))
        exact = jnp.mean(g, axis=0)
        n_steps = 20

        def run(use_ef):
            err = jnp.zeros_like(g)
            cum = jnp.zeros_like(exact)
            for _ in range(n_steps):
                out, new_err = fn(g, err)
                if use_ef:
                    err = new_err
                cum = cum + out[0]
            return float(jnp.abs(cum[1] / n_steps - exact[1]))

        with_ef = run(True)
        without_ef = run(False)
        assert with_ef < without_ef * 0.5 or with_ef < 1e-3, \
            (with_ef, without_ef)


class TestShardingRules:
    def test_param_specs_cover_all_leaves(self):
        mesh = _mesh((4, 2), ("data", "model"))
        for arch in ["qwen3_8b", "dbrx_132b", "rwkv6_3b", "zamba2_7b"]:
            cfg = configs.get_config(arch, smoke=True)
            shapes = jax.eval_shape(
                lambda: model.init(jax.random.PRNGKey(0), cfg))
            specs = param_specs(cfg, shapes, mesh)
            flat_shapes = jax.tree.leaves(shapes)
            flat_specs = jax.tree.leaves(
                specs, is_leaf=lambda x: isinstance(x, P))
            assert len(flat_shapes) == len(flat_specs)
            # every spec must be valid for its leaf's rank
            for sh, sp in zip(flat_shapes, flat_specs):
                assert len(sp) <= len(sh.shape), (sh.shape, sp)

    def test_big_leaves_are_sharded(self):
        mesh = _mesh((4, 2), ("data", "model"))
        cfg = configs.get_config("qwen3_8b", smoke=True)
        shapes = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), cfg))
        specs = param_specs(cfg, shapes, mesh)
        flat = jax.tree_util.tree_flatten_with_path(
            specs, is_leaf=lambda x: isinstance(x, P))[0]
        sharded = {jax.tree_util.keystr(kp): sp for kp, sp in flat}
        # attention + ffn weights must be TP-sharded
        assert any("wq" in k and "model" in str(s)
                   for k, s in sharded.items())
        assert any("w_up" in k and "model" in str(s)
                   for k, s in sharded.items())

    def test_batch_and_cache_specs(self):
        mesh = _mesh((4, 2), ("data", "model"))
        cfg = configs.get_config("qwen3_8b", smoke=True)
        bs = batch_specs(cfg, mesh, batch=8)
        assert bs["tokens"] == P("data", None)
        cs = cache_specs(cfg, mesh, batch=8)
        assert cs["k"] == P(None, "data", "model", None, None)
        # degenerate batch=1: no batch sharding
        bs1 = batch_specs(cfg, mesh, batch=1)
        assert bs1["tokens"] == P(None, None)
