"""Fused Pallas paged-attention kernel — block-table walk in-kernel.

The serving stack's gather path (`serve.paged_model._paged_attn_block`)
materializes every request's block table into a contiguous
(B, Smax, KV, Dh) view before attending — the data-movement cost paged
kernels exist to eliminate.  This kernel walks the per-request block
table INSIDE the kernel instead: the K/V operands are the raw page
pools (P, page, KV, Dh), and their `BlockSpec` index maps read the
scalar-prefetched block-table operand to fetch page
`block_tables[b, pi]` at grid step (b, h, pi) — a block-sparse gather
the compiler pipelines against compute, with nothing contiguous ever
built (paper §III.C.2/§III.D.3: attention streamed bank-by-bank out of
the arrays with the online LSE softmax).

Softmax is the same online (m, l) running-statistics scheme as
`kernels.flash_attention`: m/l live in f32 VMEM scratch carried across
the page axis (innermost grid dim), the context accumulates in the
revisited f32 output block, and the last page finalizes (o /= l).

TPU tiling: the last two dims of every block are either (8, 128)
multiples or the whole array dim, so Mosaic accepts any head count:

  q        (B, KV, G*S, Dh)  block (1, KV, G*S, Dh) — the wrapper folds
           the G query heads of each kv head into rows (row g*S + s);
  k/v      (P, page, KV, Dh) block (1, page, KV, Dh) — ONE page of
           every kv head per grid step, so each page is fetched once
           per row; the kernel selects kv head j statically;
  positions (B, G*S, 1)      block (1, G*S, 1) — query position per row;
  m, l     (KV, G*S, 1) VMEM scratch.

Masking reproduces `serve.paged_model._attn_core` bit-for-bit in
semantics: table slot pi covers absolute kv positions
[pi*page, (pi+1)*page), so the kv position of slot s in grid step pi IS
pi*page + s; a query at absolute position p keeps kv positions t with
t <= p (causal over the whole written prefix) and, under a sliding
window, t > p - window.  Trash-page and padding slots all sit at
t > p for every valid query, so per-lane length masking falls out of
`positions` alone — no separate length operand.

Page skipping: pages whose first kv position exceeds the row's maximum
query position carry only trash/unwritten slots and are skipped (no
FLOPs — the grid visits them, `pl.when` gates the body); with a window,
pages entirely below every query's window are skipped from the other
side.  The K/V index map clamps a skipped step onto the nearest visited
page, so a skipped step also issues no new DMA.  Query positions within
a row must be monotone non-decreasing (the serve builders emit
start_pos + arange), which makes row position 0 the min and row
position S-1 the max.

Grid: (B, Pmax), pages innermost.  S is the prefill chunk (or 1 for
decode), so one kernel covers both step shapes — the serve layer
selects it per `EngineConfig.attn_impl` with zero engine/scheduler
branches.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention.flash_attention import (
    NEG_INF,
    _interpret_default,
)


_HIGHEST = jax.lax.Precision.HIGHEST


def _page_range(pi, q_lo, q_hi, *, window, page, pmax):
    """(visit, clamped page slot) for grid step pi of a row whose query
    positions span [q_lo, q_hi]."""
    hi = jnp.minimum(q_hi // page, pmax - 1)
    visit = pi * page <= q_hi
    lo = jnp.zeros_like(hi)
    if window is not None:
        visit &= (pi + 1) * page - 1 > q_lo - window
        lo = jnp.minimum(jnp.maximum(q_lo - window + 1, 0) // page, hi)
    return visit, jnp.clip(pi, lo, hi)


def _paged_kernel(bt_ref, qlo_ref, qhi_ref, q_ref, pos_ref, k_ref, v_ref,
                  o_ref, m_ref, l_ref, *, scale: float,
                  window: int | None, page: int, pmax: int, kvh: int):
    bi = pl.program_id(0)
    pi = pl.program_id(1)

    @pl.when(pi == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    visit, _ = _page_range(pi, qlo_ref[bi], qhi_ref[bi], window=window,
                           page=page, pmax=pmax)

    @pl.when(visit)
    def _update():
        rows = q_ref.shape[2]
        qpos = pos_ref[0]                              # (R, 1) i32
        kvpos = pi * page + jax.lax.broadcasted_iota(
            jnp.int32, (rows, page), 1)
        keep = kvpos <= qpos
        if window is not None:
            keep &= kvpos > qpos - window
        # dots run in the operands' common dtype with f32 accumulation;
        # f32 operands get HIGHEST (full f32 on the MXU, not one bf16
        # pass), so an f32 pool gets exact fp32 attention
        dt = jnp.promote_types(q_ref.dtype, k_ref.dtype)
        prec = _HIGHEST if dt == jnp.float32 else None
        for j in range(kvh):
            q = q_ref[0, j].astype(dt)                 # (R, Dh)
            k = k_ref[0, :, j, :].astype(dt)           # (page, Dh)
            v = v_ref[0, :, j, :].astype(dt)           # (page, Dh)
            sc = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), precision=prec,
                preferred_element_type=jnp.float32,
            ) * scale                                  # (R, page)
            sc = jnp.where(keep, sc, NEG_INF)
            m_prev = m_ref[j]                          # (R, 1)
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(sc - m_new)
            l_ref[j] = l_ref[j] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            m_ref[j] = m_new
            o_ref[0, j] = o_ref[0, j] * alpha + jax.lax.dot_general(
                p.astype(dt), v, (((1,), (0,)), ((), ())),
                precision=prec, preferred_element_type=jnp.float32)

    @pl.when(pi == pmax - 1)
    def _finalize():
        for j in range(kvh):
            o_ref[0, j] = o_ref[0, j] / jnp.maximum(l_ref[j], 1e-30)


@functools.partial(
    jax.jit, static_argnames=("window", "scale", "interpret"),
)
def paged_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,
    positions: jax.Array,
    *,
    window: int | None = None,
    scale: float | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused paged attention over one layer's page pool.

    q:            (B, S, H, Dh) queries (S = chunk, or 1 for decode)
    k/v_pages:    (P, page, KV, Dh) the layer's page pool, H % KV == 0
    block_tables: (B, Pmax) i32 page ids per row, trash page 0 in
                  unused slots
    positions:    (B, S) i32 absolute query positions, monotone
                  non-decreasing within a row

    Returns the context tensor (B, S, H, Dh) f32.  A query at position
    p attends to kv positions t <= p (and t > p - window when set) of
    its own row's table — `_attn_core` semantics, computed without ever
    materializing the gathered view.  `interpret=None` resolves via the
    shared `_interpret_default()` platform probe.
    """
    if interpret is None:
        interpret = _interpret_default()
    b, s, h, hd = q.shape
    npages, page, kvh, hd_k = k_pages.shape
    if hd_k != hd or v_pages.shape != k_pages.shape:
        raise ValueError(
            f"pool/query shape mismatch: q {q.shape}, k_pages "
            f"{k_pages.shape}, v_pages {v_pages.shape}")
    if h % kvh:
        raise ValueError(f"H={h} not a multiple of KV={kvh}")
    group = h // kvh
    pmax = block_tables.shape[1]
    if block_tables.shape[0] != b or positions.shape != (b, s):
        raise ValueError(
            f"batch mismatch: q {q.shape}, block_tables "
            f"{block_tables.shape}, positions {positions.shape}")
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    if scale is None:
        scale = 1.0 / (hd**0.5)

    rows = group * s
    # (B, S, KV*G, Dh) -> (B, KV, G*S, Dh): row g*S + s of kv head j is
    # query head j*G + g at chunk position s
    qk = q.reshape(b, s, kvh, group, hd).transpose(0, 2, 3, 1, 4)
    qk = qk.reshape(b, kvh, rows, hd)
    positions = positions.astype(jnp.int32)
    pos_rows = jnp.tile(positions, (1, group))[:, :, None]   # (B, R, 1)
    page_range = functools.partial(_page_range, window=window, page=page,
                                   pmax=pmax)

    def kv_map(bi, pi, bt, qlo, qhi):
        _, slot = page_range(pi, qlo[bi], qhi[bi])
        return (bt[bi, slot], 0, 0, 0)

    kernel = functools.partial(
        _paged_kernel, scale=scale, window=window, page=page,
        pmax=pmax, kvh=kvh,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # block_tables drives the K/V index maps; each row's first and
        # last query position back the page-skip predicate
        num_scalar_prefetch=3,
        grid=(b, pmax),
        in_specs=[
            pl.BlockSpec((1, kvh, rows, hd),
                         lambda bi, pi, *_: (bi, 0, 0, 0)),
            pl.BlockSpec((1, rows, 1), lambda bi, pi, *_: (bi, 0, 0)),
            pl.BlockSpec((1, page, kvh, hd), kv_map),
            pl.BlockSpec((1, page, kvh, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, kvh, rows, hd),
                               lambda bi, pi, *_: (bi, 0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((kvh, rows, 1), jnp.float32),   # m
                        pltpu.VMEM((kvh, rows, 1), jnp.float32)],  # l
    )
    o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, rows, hd), jnp.float32),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), positions[:, 0], positions[:, -1],
      qk, pos_rows, k_pages, v_pages)
    o = o.reshape(b, kvh, group, s, hd).transpose(0, 3, 1, 2, 4)
    return o.reshape(b, s, h, hd)
