"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (brief (c)).

Every Pallas kernel runs in interpret mode on CPU; allclose against
ref.py over a grid of shapes, dtypes, modes — plus hypothesis property
tests on the kernels' invariants.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:
    # hypothesis is a dev-only dep (requirements-dev.txt): without it
    # only the @given property tests skip — the deterministic sweeps in
    # this module still run.
    class _StrategyStub:
        def __getattr__(self, name):
            return lambda *a, **k: None

    st = _StrategyStub()

    def given(*a, **k):
        return pytest.mark.skip(
            reason="property test needs hypothesis (requirements-dev.txt)")

    def settings(*a, **k):
        return lambda f: f

from repro.core import quantization as quantlib
from repro.core.policy import ArithmeticPolicy
from repro.core.quantization import SC_LEVELS
from repro.kernels import (
    attention_ref,
    flash_attention,
    sc_matmul,
    sc_matmul_ref,
)
from repro.kernels.sc_matmul.sc_matmul import sc_matmul_quantized


def _rand(key, shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


class TestScMatmulSweep:
    @pytest.mark.parametrize("m,k,n", [
        (128, 160, 128),     # single block
        (256, 160, 128),     # M-tiled
        (128, 320, 256),     # K- and N-tiled
        (64, 100, 96),       # ragged -> padding path
        (1, 40, 16),         # tiny
    ])
    @pytest.mark.parametrize("mode", ["int8", "artemis", "artemis_mxu"])
    def test_matches_oracle(self, m, k, n, mode):
        ka, kb = jax.random.split(jax.random.PRNGKey(m * 7 + k + n), 2)
        a = _rand(ka, (m, k))
        b = _rand(kb, (k, n))
        pol = ArithmeticPolicy(mode=mode, ste=False)
        out = sc_matmul(a, b, pol)
        sa = quantlib.quant_scale(a, 8)
        sb = quantlib.quant_scale(b, 8)
        aq, bq = quantlib.quantize(a, sa), quantlib.quantize(b, sb)
        # oracle needs block-padded K for artemis groups
        pad = (-k) % (160 if mode == "artemis" else 256)
        if pad:
            aq = jnp.pad(aq, ((0, 0), (0, pad)))
            bq = jnp.pad(bq, ((0, pad), (0, 0)))
        ref = sc_matmul_ref(aq, bq, mode=mode).astype(jnp.float32)
        ref = ref * sa * sb * (1 if mode == "int8" else SC_LEVELS)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=3e-4, atol=3e-4)

    @pytest.mark.parametrize("in_dtype", [jnp.float32, jnp.bfloat16])
    def test_dtype_sweep(self, in_dtype):
        a = _rand(jax.random.PRNGKey(0), (128, 160), in_dtype)
        b = _rand(jax.random.PRNGKey(1), (160, 128), in_dtype)
        out = sc_matmul(a, b, ArithmeticPolicy(mode="int8", ste=False))
        exact = a.astype(jnp.float32) @ b.astype(jnp.float32)
        rel = float(jnp.linalg.norm(out - exact) / jnp.linalg.norm(exact))
        assert rel < 0.05

    def test_int8_matches_quantized_dot_exactly(self):
        """int8 mode must be EXACT integer arithmetic (no approximation)."""
        key = jax.random.PRNGKey(2)
        aq = jax.random.randint(key, (128, 256), -127, 128, jnp.int32)
        bq = jax.random.randint(jax.random.fold_in(key, 1), (256, 128),
                                -127, 128, jnp.int32)
        out = sc_matmul_quantized(aq.astype(jnp.int8), bq.astype(jnp.int8),
                                  mode="int8", interpret=True)
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(aq @ bq))

    @settings(max_examples=10, deadline=None)
    @given(st.integers(1, 4), st.integers(1, 6), st.integers(1, 4))
    def test_property_artemis_error_bounded(self, mb, kb, nb):
        """Hypothesis: artemis output error vs exact int dot is bounded by
        the truncation + readout bound per K element."""
        m, k, n = mb * 32, kb * 40, nb * 32
        key = jax.random.PRNGKey(m + k + n)
        aq = jax.random.randint(key, (m, k), -127, 128, jnp.int32)
        bq = jax.random.randint(jax.random.fold_in(key, 1), (k, n),
                                -127, 128, jnp.int32)
        pad = (-k) % 160
        aqp = jnp.pad(aq, ((0, 0), (0, pad)))
        bqp = jnp.pad(bq, ((0, pad), (0, 0)))
        out = sc_matmul_ref(aqp.astype(jnp.int8), bqp.astype(jnp.int8),
                            mode="artemis")
        exact = (aq @ bq).astype(jnp.float32) / SC_LEVELS
        kp = k + pad
        # per product: <=1 unit floor truncation; per group: readout step
        groups = kp // 20
        bound = kp * 1.0 + groups * (20 * 127 / 255.0) + 1.0
        assert float(jnp.max(jnp.abs(out - exact))) <= bound


class TestFlashAttentionSweep:
    @pytest.mark.parametrize("b,hq,hkv,s,d", [
        (1, 4, 4, 128, 64),      # MHA single block
        (2, 8, 2, 256, 64),      # GQA 4:1
        (1, 4, 1, 256, 32),      # MQA
        (1, 2, 2, 200, 64),      # ragged seq -> padding
        (2, 4, 4, 384, 128),     # multi-block, wide head
    ])
    def test_matches_oracle(self, b, hq, hkv, s, d):
        key = jax.random.PRNGKey(b * 100 + hq + s)
        kq, kk, kv = jax.random.split(key, 3)
        q = _rand(kq, (b, hq, s, d))
        k = _rand(kk, (b, hkv, s, d))
        v = _rand(kv, (b, hkv, s, d))
        o, lse = flash_attention(q, k, v, causal=True, return_lse=True)
        o_ref, lse_ref = attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_ref),
                                   rtol=2e-4, atol=2e-4)

    def test_non_causal(self):
        key = jax.random.PRNGKey(9)
        q, k, v = (_rand(jax.random.fold_in(key, i), (1, 2, 128, 64))
                   for i in range(3))
        o, _ = flash_attention(q, k, v, causal=False, return_lse=True)
        o_ref, _ = attention_ref(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                                   rtol=2e-4, atol=2e-4)

    def test_bf16_inputs(self):
        key = jax.random.PRNGKey(10)
        q, k, v = (_rand(jax.random.fold_in(key, i), (1, 2, 128, 64),
                         jnp.bfloat16) for i in range(3))
        o, _ = flash_attention(q, k, v, causal=True, return_lse=True)
        o_ref, _ = attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(o, np.float32),
                                   np.asarray(o_ref, np.float32),
                                   rtol=2e-2, atol=2e-2)

    @settings(max_examples=8, deadline=None)
    @given(st.integers(1, 3), st.integers(0, 2), st.integers(1, 3))
    def test_property_lse_merge_associative(self, b, hp, sb):
        """Splitting the KV axis and LSE-merging partials == full attention
        (the invariant behind ring attention and split-KV decode)."""
        h, s, d = 2 ** hp, 64 * sb, 32
        key = jax.random.PRNGKey(b * 31 + h + s)
        kq, kk, kv = jax.random.split(key, 3)
        q = _rand(kq, (b, h, 64, d))
        k = _rand(kk, (b, h, s, d))
        v = _rand(kv, (b, h, s, d))
        o_full, lse_full = attention_ref(q, k, v, causal=False)
        # two halves merged via LSE
        half = s // 2
        if half == 0:
            return
        o1, l1 = attention_ref(q, k[:, :, :half], v[:, :, :half],
                               causal=False)
        o2, l2 = attention_ref(q, k[:, :, half:], v[:, :, half:],
                               causal=False)
        m = jnp.maximum(l1, l2)
        w1 = jnp.exp(l1 - m)[..., None]
        w2 = jnp.exp(l2 - m)[..., None]
        o = (o1 * w1 + o2 * w2) / (w1 + w2)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_full),
                                   rtol=1e-5, atol=1e-5)


class TestFlashWrapperFixes:
    """Regression pins for the kernel-wrapper bugfixes: shared
    interpret-mode resolution, the non-causal key-length mask (odd Sk
    through the padding wrapper), sliding-window masking, and the
    two-sided block-skip predicate (proved FLOP-free via the visited-
    block counter output)."""

    def _qkv(self, seed, b=1, hq=4, hkv=2, sq=16, sk=16, d=8):
        key = jax.random.PRNGKey(seed)
        kq, kk, kv = jax.random.split(key, 3)
        return (_rand(kq, (b, hq, sq, d)), _rand(kk, (b, hkv, sk, d)),
                _rand(kv, (b, hkv, sk, d)))

    def test_interpret_default_is_shared_and_matches_backend(self):
        import importlib
        fa = importlib.import_module(
            "repro.kernels.flash_attention.flash_attention")
        ops = importlib.import_module(
            "repro.kernels.flash_attention.ops")
        pa = importlib.import_module(
            "repro.kernels.paged_attention.paged_attention")
        assert fa._interpret_default() == (jax.default_backend() != "tpu")
        # single source of truth: ops and the paged kernel import THE
        # SAME probe, not private copies
        assert ops._interpret_default is fa._interpret_default
        assert pa._interpret_default is fa._interpret_default
        sc = importlib.import_module("repro.kernels.sc_matmul.sc_matmul")
        assert sc._interpret_default is fa._interpret_default

    def test_raw_kernel_default_interpret_resolves(self):
        """flash_attention_kernel's default must resolve via the probe
        (compiled Mosaic would fail off-TPU, so running on CPU with no
        explicit interpret IS the pin that the default is no longer a
        hardwired constant)."""
        from repro.kernels.flash_attention import flash_attention_kernel
        q, k, v = self._qkv(0)
        o, lse = flash_attention_kernel(q, k, v, bq=8, bk=8)
        o_ref, lse_ref = attention_ref(q, k, v)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_ref),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("sk", [13, 97, 130])
    def test_non_causal_odd_sk_matches_ref(self, sk):
        """Non-causal Sk that is NOT a block multiple pads through the
        wrapper and must match the oracle exactly (previously raised
        NotImplementedError after already mutating K/V)."""
        q, k, v = self._qkv(sk, sq=5, sk=sk)
        o, lse = flash_attention(q, k, v, causal=False, return_lse=True)
        o_ref, lse_ref = attention_ref(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_ref),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("window", [1, 3, 7, 100])
    def test_window_matches_ref(self, window):
        q, k, v = self._qkv(window, sq=24, sk=24)
        o = flash_attention(q, k, v, causal=True, window=window)
        o_ref, _ = attention_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                                   rtol=2e-4, atol=2e-4)

    def test_window_requires_causal(self):
        q, k, v = self._qkv(1)
        with pytest.raises(ValueError, match="causal"):
            flash_attention(q, k, v, causal=False, window=4)

    def test_causal_block_skip_counts(self):
        """Above-diagonal K blocks never execute: with bq=bk=4 over
        sq=sk=16, q block qi executes exactly qi+1 K blocks."""
        from repro.kernels.flash_attention.flash_attention import (
            flash_attention_block_counts,
        )
        q, k, v = self._qkv(2)
        nvis = np.asarray(flash_attention_block_counts(
            q, k, v, causal=True, bq=4, bk=4))
        per_block = nvis[0, 0, ::4]
        np.testing.assert_array_equal(per_block, [1.0, 2.0, 3.0, 4.0])

    def test_window_block_skip_is_two_sided(self):
        """With a sliding window, K blocks entirely below every query
        row's window are skipped too — the counter proves no FLOPs
        issue from either side, while outputs still match the oracle."""
        from repro.kernels.flash_attention.flash_attention import (
            flash_attention_block_counts,
        )
        bq = bk = 4
        q, k, v = self._qkv(4, sq=32, sk=32)
        window = 4
        nvis = np.asarray(flash_attention_block_counts(
            q, k, v, causal=True, window=window, bq=bq, bk=bk))
        nk = 32 // bk
        for qi in range(32 // bq):
            visited = sum(
                1 for ki in range(nk)
                if ki * bk <= qi * bq + bq - 1             # causal side
                and ki * bk + bk - 1 > qi * bq - window)   # window side
            assert nvis[0, 0, qi * bq] == visited, (qi, visited)
        # every q block past the first visits exactly 2 of its <=qi+1
        # causally-visible blocks — the window skip is doing real work
        assert nvis[0, 0, -1] == 2 < nk
        o = flash_attention(q, k, v, causal=True, window=window)
        o_ref, _ = attention_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                                   rtol=2e-4, atol=2e-4)

    def test_kv_len_blocks_past_length_never_execute(self):
        """Non-causal padded Sk: K blocks entirely past the true key
        length are skipped, and padded keys carry zero weight."""
        from repro.kernels.flash_attention.flash_attention import (
            flash_attention_block_counts,
        )
        q, k, v = self._qkv(6, sq=8, sk=16)
        nvis = np.asarray(flash_attention_block_counts(
            q, k, v, causal=False, kv_len=6, bq=4, bk=4))
        # kv_len=6 spans blocks 0-1 of 4; blocks 2-3 must not run
        assert (nvis == 2.0).all()
        from repro.kernels.flash_attention import flash_attention_kernel
        o, lse = flash_attention_kernel(q, k, v, causal=False, kv_len=6,
                                        bq=4, bk=4)
        o_ref, lse_ref = attention_ref(q, k[:, :, :6], v[:, :, :6],
                                       causal=False)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_ref),
                                   rtol=2e-4, atol=2e-4)
