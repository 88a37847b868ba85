"""Pallas TPU flash-attention kernel with the ARTEMIS LSE softmax.

Paper §III.C.2 + §III.D.3: ARTEMIS computes softmax in the division-free
log-sum-exp form (Eq. 5) and tracks y_max *online* with a comparator while
the QK^T MatMul streams out of the subarrays, overlapping softmax with the
S*V MatMul.  On TPU the idiomatic realization of exactly that dataflow is a
fused attention kernel with an online-softmax K/V stream — this kernel.

Features: causal masking, sliding-window masking (a query at row r keeps
keys in (r - window, r], matching `serve.paged_model._attn_core`), an
explicit key-length mask so the wrapper can pad Sk to a block multiple
without changing non-causal results, GQA/MQA (q-head -> kv-head folding
via the BlockSpec index map), and an LSE output per query — the LSE is
what makes the token-dataflow distributed merges (ring attention,
split-KV decode) exact, because Eq. 5 is associative across shards.

Grid: (batch, q_heads, Sq/bq, Sk/bk), K innermost; the output is a
revisited block accumulated across the K axis, and the (m, l) running
statistics live in (bq, 1) f32 VMEM scratch.  Per-row outputs (lse,
the visit count) are (B, Hq, Sq, 1) arrays so every block's last two
dims sit on the TPU's (8, 128) tiling.  Finalization (o /= l,
lse = m + log l) happens at the last K step.

Block skipping: under a causal mask, K blocks strictly above the
diagonal are skipped; with a sliding window, K blocks that fall entirely
below every query row's window are skipped too, and blocks entirely past
the key-length mask never run.  The `nvis` output counts the K blocks
that actually executed per (batch, head, q-row) — the interpret-mode
tests read it to assert skipped blocks issue no FLOPs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _interpret_default() -> bool:
    """Single source of truth for Pallas interpret-mode resolution:
    compiled Mosaic on TPU, the interpreter everywhere else.  Shared by
    `flash_attention_kernel`, `ops.flash_attention`, the paged kernel
    (`kernels.paged_attention`) and `sc_matmul_quantized`."""
    return jax.default_backend() != "tpu"


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, nvis_ref,
                  m_ref, l_ref, *, scale: float, causal: bool,
                  window: int | None, kv_len: int | None,
                  nk: int, bq: int, bk: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        nvis_ref[...] = jnp.zeros_like(nvis_ref)

    def _update():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)          # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                     # (bq, bk)
        if causal or kv_len is not None:
            rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            keep = jnp.full((bq, bk), True)
            if causal:
                keep &= rows >= cols
                if window is not None:
                    keep &= cols > rows - window
            if kv_len is not None:
                keep &= cols < kv_len
            s = jnp.where(keep, s, NEG_INF)

        m_prev = m_ref[...]                           # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        nvis_ref[0, 0] = nvis_ref[0, 0] + 1.0
        o_ref[0, 0] = (o_ref[0, 0] * alpha
                       + jax.lax.dot_general(
                           p, v, (((1,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32))

    # two-sided block skip: drop K blocks that are fully masked for the
    # whole q block — strictly above the diagonal (causal), entirely
    # below every row's sliding window (a block is below row r's window
    # iff its last col <= r - window; fully below ALL rows iff that
    # holds for the block's FIRST row qi*bq), or entirely past the
    # valid key length.  The TPU grid still visits them, but no FLOPs
    # issue — the nvis counter output is the proof the tests pin.
    visit = None
    if causal:
        visit = ki * bk <= qi * bq + bq - 1
        if window is not None:
            visit &= ki * bk + bk - 1 > qi * bq - window
    if kv_len is not None and kv_len < nk * bk:
        below_len = ki * bk < kv_len
        visit = below_len if visit is None else visit & below_len
    if visit is None:
        _update()
    else:
        pl.when(visit)(_update)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)            # (bq, 1)
        o_ref[0, 0] = o_ref[0, 0] / l
        lse_ref[0, 0] = m_ref[...] + jnp.log(l)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "kv_len", "scale", "bq", "bk",
                     "interpret"),
)
def _flash_attention_all(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    kv_len: int | None = None,
    scale: float | None = None,
    bq: int = 128,
    bk: int = 128,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Kernel entry returning every output: (o, lse, nvis) where nvis
    counts the K blocks that executed per (b, h, q-row) — see
    `flash_attention_block_counts`."""
    if interpret is None:
        interpret = _interpret_default()
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    assert sq % bq == 0 and sk % bk == 0, (sq, sk, bq, bk)
    nq, nk = sq // bq, sk // bk
    if window is not None and not causal:
        raise ValueError("window masking requires causal=True")
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    if scale is None:
        scale = 1.0 / (d**0.5)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        kv_len=kv_len, nk=nk, bq=bq, bk=bk,
    )
    row = pl.BlockSpec((1, 1, bq, 1), lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    o, lse, nvis = pl.pallas_call(
        kernel,
        grid=(b, hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            row,   # lse
            row,   # visited K blocks
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, sq, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hq, sq, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, hq, sq, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),   # m
                        pltpu.VMEM((bq, 1), jnp.float32)],  # l
        interpret=interpret,
    )(q, k, v)
    return o, lse[..., 0], nvis[..., 0]


def flash_attention_kernel(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    kv_len: int | None = None,
    scale: float | None = None,
    bq: int = 128,
    bk: int = 128,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Sk, D), Hq % Hkv == 0.

    Returns (o: (B, Hq, Sq, D) f32, lse: (B, Hq, Sq) f32).
    Sq/Sk must be multiples of bq/bk (ops.py pads; `kv_len` masks keys
    at positions >= kv_len so padded Sk stays exact for non-causal).
    `window` keeps keys in (row - window, row] per query row (causal
    only).  `interpret=None` resolves via `_interpret_default()`:
    compiled on TPU, interpreted elsewhere.
    """
    o, lse, _ = _flash_attention_all(
        q, k, v, causal=causal, window=window, kv_len=kv_len,
        scale=scale, bq=bq, bk=bk, interpret=interpret)
    return o, lse


def flash_attention_block_counts(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    kv_len: int | None = None,
    scale: float | None = None,
    bq: int = 128,
    bk: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Number of K blocks that actually executed per (B, Hq, Sq) row —
    every row of a q block shares one count.  The block-skip tests pin
    this against the analytic visit set to prove fully-masked blocks
    issue no FLOPs."""
    _, _, nvis = _flash_attention_all(
        q, k, v, causal=causal, window=window, kv_len=kv_len,
        scale=scale, bq=bq, bk=bk, interpret=interpret)
    return nvis
