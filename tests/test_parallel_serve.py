"""Pins for the serve-facing fixes in parallel/ring_attention.py and
parallel/split_kv.py (the mesh-serve PR's satellite): native GQA
(KV-head counts below the query-head count), sentinel masking under
non-causal attention, and caller-supplied kv positions travelling the
ring WITH their K/V chunk — the latent bugs the sharded paged backend
flushed out. Same 8-forced-host-devices setup as test_parallel.py."""
import os
import sys

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

from repro.parallel import ring_attention, split_kv_attention  # noqa: E402
from repro.parallel.ring_attention import (  # noqa: E402
    _repeat_kv,
    layer_dataflow_attention,
)

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs 8 host devices (run standalone or first)")

EMPTY = jnp.iinfo(jnp.int32).max


def _mesh():
    return jax.make_mesh((8,), ("sp",), axis_types=(AxisType.Auto,))


def _rand(key, shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


def _ref(q, k, v, causal=True):
    """Dense reference with KV heads repeated to the query heads."""
    k, v = _repeat_kv(q.shape[2], k, v)
    d = q.shape[-1]
    s_ = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (1.0 / d**0.5)
    if causal:
        mask = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool))
        s_ = jnp.where(mask[None, None], s_, -1e30)
    p = jax.nn.softmax(s_, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


class TestGQA:
    def test_repeat_kv_rejects_indivisible(self):
        k = v = jnp.zeros((1, 4, 3, 8))
        with pytest.raises(ValueError, match="multiple of KV heads"):
            _repeat_kv(4, k, v)

    def test_ring_attention_native_gqa(self):
        """KV heads < query heads go through the ring unrepeated: the
        helper expands them with the serve layer's grouping (q head i
        -> kv head i // g)."""
        b, s, h, kvh, d = 2, 64, 8, 2, 16
        q = _rand(0, (b, s, h, d))
        k = _rand(1, (b, s, kvh, d))
        v = _rand(2, (b, s, kvh, d))
        ref = _ref(q, k, v)
        fn = jax.shard_map(
            lambda q, k, v: ring_attention(q, k, v, axis_name="sp"),
            mesh=_mesh(),
            in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
            out_specs=P(None, "sp"))
        out = jax.jit(fn)(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_split_kv_native_gqa(self):
        b, s_cache, h, kvh, d = 2, 64, 4, 2, 8
        q = _rand(3, (b, 1, h, d))
        k = _rand(4, (b, s_cache, kvh, d))
        v = _rand(5, (b, s_cache, kvh, d))
        ref = _ref(q, k, v, causal=False)   # q at the last position
        q_pos = jnp.full((b, 1), s_cache - 1, jnp.int32)
        kv_pos = jnp.broadcast_to(
            jnp.arange(s_cache, dtype=jnp.int32)[None], (b, s_cache))
        fn = jax.shard_map(
            lambda q, kl, vl, kp: split_kv_attention(
                q, kl, vl, axis_name="sp", q_positions=q_pos,
                kv_positions_local=kp),
            mesh=_mesh(),
            in_specs=(P(), P(None, "sp"), P(None, "sp"), P(None, "sp")),
            out_specs=P())
        out = jax.jit(fn)(q, k, v, kv_pos)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_layer_dataflow_native_gqa(self):
        b, s, h, kvh, d = 1, 64, 4, 2, 8
        q = _rand(6, (b, s, h, d))
        k = _rand(7, (b, s, kvh, d))
        v = _rand(8, (b, s, kvh, d))
        ref = _ref(q, k, v)
        fn = jax.shard_map(
            lambda q, k, v: layer_dataflow_attention(q, k, v,
                                                     axis_name="sp"),
            mesh=_mesh(),
            in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
            out_specs=P(None, "sp"))
        out = jax.jit(fn)(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


class TestSentinelMasking:
    def test_ring_non_causal_masks_empty_slots(self):
        """Regression: with causal=False the causal comparison used to
        be the ONLY masking, so INT32_MAX-position (unwritten) slots
        contributed garbage K/V to non-causal attention."""
        b, s, h, d = 1, 64, 2, 8
        valid = 40
        q = _rand(9, (b, s, h, d))
        k = _rand(10, (b, s, h, d))
        v = _rand(11, (b, s, h, d))
        kv_pos = jnp.where(jnp.arange(s) < valid, jnp.arange(s),
                           EMPTY).astype(jnp.int32)[None]
        kv_pos = jnp.broadcast_to(kv_pos, (b, s))
        ref = _ref(q, k[:, :valid], v[:, :valid], causal=False)
        fn = jax.shard_map(
            lambda q, k, v, kp: ring_attention(
                q, k, v, axis_name="sp", causal=False,
                kv_positions=kp),
            mesh=_mesh(),
            in_specs=(P(None, "sp"),) * 3 + (P(None, "sp"),),
            out_specs=P(None, "sp"))
        out = jax.jit(fn)(q, k, v, kv_pos)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_split_kv_non_causal_masks_empty_slots(self):
        """split_kv_attention grew the causal flag alongside the fix:
        non-causal decode over a partially-written cache view attends
        every VALID slot and none of the sentinels."""
        b, s_cache, h, d = 1, 32, 2, 8
        valid = 17
        q = _rand(12, (b, 1, h, d))
        k = _rand(13, (b, s_cache, h, d))
        v = _rand(14, (b, s_cache, h, d))
        kv_pos = jnp.where(jnp.arange(s_cache) < valid,
                           jnp.arange(s_cache), EMPTY)[None]
        kv_pos = jnp.broadcast_to(kv_pos, (b, s_cache)).astype(jnp.int32)
        # q "position" BELOW some valid slots: non-causal must still
        # attend all 17 valid slots
        q_pos = jnp.zeros((b, 1), jnp.int32)
        ref = _ref(q, k[:, :valid], v[:, :valid], causal=False)
        fn = jax.shard_map(
            lambda q, kl, vl, kp: split_kv_attention(
                q, kl, vl, axis_name="sp", q_positions=q_pos,
                kv_positions_local=kp, causal=False),
            mesh=_mesh(),
            in_specs=(P(), P(None, "sp"), P(None, "sp"), P(None, "sp")),
            out_specs=P())
        out = jax.jit(fn)(q, k, v, kv_pos)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


class TestRingPositionsTravel:
    def test_permuted_kv_layout(self):
        """Regression for the single-device assumption: caller-supplied
        kv_positions used to be applied to EVERY arriving ring chunk
        (only correct when all shards share one position vector). Now a
        chunk's positions ppermute around the ring with it, so an
        arbitrary (e.g. paged) position layout masks exactly."""
        b, s, h, d = 1, 64, 2, 8
        q = _rand(15, (b, s, h, d))
        k = _rand(16, (b, s, h, d))
        v = _rand(17, (b, s, h, d))
        ref = _ref(q, k, v, causal=True)
        # scatter the sequence across shards: slot j holds position
        # perm[j], different on every shard — the old code got this
        # wrong for every chunk except the locally-resident one
        perm = np.random.default_rng(0).permutation(s).astype(np.int32)
        k_perm = k[:, perm]
        v_perm = v[:, perm]
        kv_pos = jnp.broadcast_to(jnp.asarray(perm)[None], (b, s))
        q_pos = jnp.broadcast_to(
            jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        fn = jax.shard_map(
            lambda q, k, v, qp, kp: ring_attention(
                q, k, v, axis_name="sp", causal=True,
                q_positions=qp, kv_positions=kp),
            mesh=_mesh(),
            in_specs=(P(None, "sp"),) * 5,
            out_specs=P(None, "sp"))
        out = jax.jit(fn)(q, k_perm, v_perm, q_pos, kv_pos)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_kv_chunk_shorter_than_query_chunk(self):
        """The sharded paged prefill rings a gathered cache view whose
        per-shard length differs from the query chunk length — the ring
        must not assume S_q == S_k."""
        b, sq, sk, h, d = 1, 16, 64, 2, 8
        q = _rand(18, (b, sq, h, d))
        k = _rand(19, (b, sk, h, d))
        v = _rand(20, (b, sk, h, d))
        # queries sit at the LAST sq positions of the sk-long history
        q_pos = jnp.broadcast_to(
            (sk - sq + jnp.arange(sq, dtype=jnp.int32))[None], (b, sq))
        kv_pos = jnp.broadcast_to(
            jnp.arange(sk, dtype=jnp.int32)[None], (b, sk))
        d_scale = 1.0 / d**0.5
        s_ = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d_scale
        mask = q_pos[0][:, None] >= jnp.arange(sk)[None, :]
        s_ = jnp.where(mask[None, None], s_, -1e30)
        ref = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s_, -1), v)
        fn = jax.shard_map(
            lambda q, k, v, qp, kp: ring_attention(
                q, k, v, axis_name="sp", q_positions=qp,
                kv_positions=kp),
            mesh=_mesh(),
            in_specs=(P(None, "sp"),) * 5,
            out_specs=P(None, "sp"))
        out = jax.jit(fn)(q, k, v, q_pos, kv_pos)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
