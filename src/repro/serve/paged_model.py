"""Paged-attention forward passes for the serving engine.

Three step builders, all jit-stable under continuous batching:

  make_paged_prefill(cfg, policy) ->
      (params, tokens (1, S_pad), kv, page_ids (P_req,)) -> (logits, kv)
    Whole-prompt prefill for ONE request through the standard
    `model.apply` in-sequence attention path, K/V scattered into the
    request's pages afterwards. Kept as the reference path (tests pin
    paged numerics against it); the engine itself uses the chunked
    builder below.

  make_paged_chunked_prefill(cfg, policy) ->
      (params, tokens (B, C), kv, block_tables (B, Pmax),
       start_pos (B,), chunk_lens (B,), active (B,),
       write_from (B,)) -> (logits, kv)
    One fixed-size chunk of C prompt tokens for up to B requests AT
    ONCE. Row b holds chunk_lens[b] valid tokens of request b's
    effective prompt starting at absolute position start_pos[b]; each
    chunk token's K/V is scattered into the row's pages first, then the
    row's block table is gathered back so queries attend to the
    request's whole written prefix (earlier chunks + this one) under a
    causal mask. Shapes are (max_batch, C) constants, so chunked
    prefill compiles exactly once — no per-bucket retraces — and a
    prompt longer than C simply spans multiple engine steps.
    write_from[b] masks the SCATTER (not the queries) for positions
    below it: a prefix-sharing hit already has those positions' K/V
    resident in shared pages, so the chunk recomputes the query (its
    logits are needed to sample when the chunk completes a prompt) but
    must not write into pages other requests reference.

  make_paged_decode(cfg, policy) ->
      (params, tokens (B, 1), kv, block_tables (B, Pmax),
       seq_lens (B,), active (B,)) -> (logits (B, V), kv)
    One token for every lane of a FIXED max-batch — the chunked pass
    with C == 1 query and the position taken from seq_lens.

Inactive rows / padding chunk positions scatter into each layer's
reserved trash page 0 and are excluded from every valid query's mask,
so the compiled steps never see a data-dependent shape.

Only attention families (dense / moe) are supported: paged KV is
meaningless for the recurrent-state families (rwkv6 / zamba2), which
serve through the state-slot pool (`state_model`) — `repro.serve.backend`
routes each family to its backend.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.policy import ArithmeticPolicy
from repro.kernels.paged_attention import paged_attention
from repro.models import layers as L
from repro.models import moe as M
from repro.models import model, transformer
from repro.models.config import ModelConfig
from repro.serve.paged_cache import TRASH_PAGE


def _check_family(cfg: ModelConfig) -> None:
    if cfg.family not in ("dense", "moe"):
        raise ValueError(
            f"paged serving supports dense/moe families, got {cfg.family!r}")
    if cfg.modality != "text":
        raise ValueError(
            f"paged serving supports text modality, got {cfg.modality!r}")


# ---------------------------------------------------------------------------
# whole-prompt prefill (reference path)
# ---------------------------------------------------------------------------


def make_paged_prefill(cfg: ModelConfig,
                       policy: ArithmeticPolicy = ArithmeticPolicy()):
    """Returns prefill(params, tokens, kv, page_ids) -> (logits, kv).

    tokens: (1, S_pad) i32, S_pad a page multiple; page_ids: (S_pad/page,)
    i32 pages owned by the request, in position order. Returns logits for
    ALL S_pad positions (the caller indexes the true last prompt position
    host-side) and the pool with the request's K/V written.
    """
    _check_family(cfg)

    def prefill(params, tokens, kv, page_ids):
        s_pad = tokens.shape[1]
        page = kv["k"].shape[2]
        dense = transformer.init_cache(cfg, 1, s_pad, kv["k"].dtype)
        logits, _, dense = model.apply(
            params, cfg, {"tokens": tokens}, policy=policy, cache=dense,
            remat=False)
        n_layers, _, _, kvh, hd = dense["k"].shape
        kp = dense["k"].reshape(n_layers, s_pad // page, page, kvh, hd)
        vp = dense["v"].reshape(n_layers, s_pad // page, page, kvh, hd)
        new_kv = {"k": kv["k"].at[:, page_ids].set(kp),
                  "v": kv["v"].at[:, page_ids].set(vp)}
        return logits[0], new_kv

    return prefill


# ---------------------------------------------------------------------------
# shared paged-attention step body (chunked prefill and decode)
# ---------------------------------------------------------------------------


def _attn_core(qg, kall, vall, positions, cfg: ModelConfig, policy):
    """Default (single-device) grouped-query attention over the
    gathered KV view. qg: (B, S, KV, G, Dh) grouped queries; kall/vall:
    (B, Smax, KV, Dh); positions: (B, S) absolute query positions.
    Returns the context tensor (B, S, KV, G, Dh).

    Pluggable seam: `ShardedPagedBackend` swaps in a mesh-sharded core
    (split-KV / ring attention over the same view) via the step
    builders' `attn_core` argument — the rest of the paged forward is
    layout-oblivious.
    """
    hd = qg.shape[-1]
    smax = kall.shape[1]
    scores = L.qeinsum("bskgd,btkd->bkgst", qg, kall, policy)
    scores = scores.astype(jnp.float32) * (hd ** -0.5)
    # page j of a block table holds positions [j*page, (j+1)*page), so
    # the gathered view's kv position IS its index t; causal within the
    # chunk because each query's own position bounds the mask
    t = jnp.arange(smax, dtype=jnp.int32)[None, None, :]  # (1, 1, Smax)
    keep = t <= positions[:, :, None]                     # (B, S, Smax)
    if cfg.attn_window:
        keep = keep & (t > positions[:, :, None] - cfg.attn_window)
    scores = jnp.where(keep[:, None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(qg.dtype)
    return L.qeinsum("bkgst,btkd->bskgd", probs, vall, policy)


def make_fused_paged_core(cfg: ModelConfig, policy: ArithmeticPolicy):
    """Build the fused-kernel occupant of the `paged_core` seam: a
    core(qg, ckl, cvl, block_tables, positions) -> (B, S, KV, G, Dh)
    that hands the RAW page pool to the Pallas paged-attention kernel
    (`repro.kernels.paged_attention`), which walks the block table
    in-kernel — no gathered (B, Smax, KV, Dh) view is ever built.

    The kernel computes exact fp32 masked softmax-attention, so it can
    only stand in for the default core under an exact arithmetic
    policy; quantized score/context einsums must keep the gather path.
    Interpret-mode resolution (compiled on TPU, interpreted on CPU)
    happens inside the kernel wrapper via the shared platform probe.
    """
    if policy.is_quantized():
        raise ValueError(
            f"attn_impl='fused' computes exact fp32 attention and "
            f"cannot reproduce quantized policy mode "
            f"{policy.mode!r}; use attn_impl='gather'")
    window = cfg.attn_window or None

    def core(qg, ckl, cvl, block_tables, positions):
        b, s, kvh, g, hd = qg.shape
        o = paged_attention(
            qg.reshape(b, s, kvh * g, hd), ckl, cvl, block_tables,
            positions, window=window, scale=hd ** -0.5)
        return o.astype(qg.dtype).reshape(b, s, kvh, g, hd)

    return core


def _paged_attn_block(lp, x, cfg: ModelConfig, policy, positions,
                      ck, cv, base, block_tables, page_idx, offset,
                      attn_core=None, paged_core=None):
    """One layer's attention with paged K/V. x: (B, S, d).

    ck/cv: the whole stacked pool flattened to (L*P, page, KV, Dh), in
    which this layer owns pages [base, base + P) and its trash page is
    base + TRASH_PAGE. block_tables (B, Pmax), page_idx (B, S): page
    ids within the layer, in [0, P); positions, offset: (B, S) — the
    absolute position of every query token and its slot in the page
    (trash page for inactive / padding tokens). Returns (attn_out,
    new ck, new cv), the pool changed only in the slots written here.

    Its parts run under the scopes `attention` (projections and core),
    `kv_write` (the scatter) and `kv_read` (the block-table gather).
    Two occupants share the attention seam at this call site:
    `attn_core` consumes the GATHERED (B, Smax, KV, Dh) view (default
    `_attn_core`; the sharded backend's mesh cores), while
    `paged_core(qg, ck, cv, block_tables, positions)` consumes the
    raw pool + block tables so the fused kernel can walk pages
    in-kernel — when it is set, the gather below never happens.
    """
    b, s, _ = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    p = lp["attn"]
    with jax.named_scope("attention"):
        qh = L.mm(x, p["wq"], policy).reshape(b, s, h, hd)
        kh = L.mm(x, p["wk"], policy).reshape(b, s, kvh, hd)
        vh = L.mm(x, p["wv"], policy).reshape(b, s, kvh, hd)
        if cfg.qk_norm:
            qh = L.headwise_rmsnorm(p["q_norm"], qh, cfg.norm_eps)
            kh = L.headwise_rmsnorm(p["k_norm"], kh, cfg.norm_eps)
        qh = L.apply_rope(qh, positions, cfg.rope_theta)
        kh = L.apply_rope(kh, positions, cfg.rope_theta)

    # scatter the new tokens' K/V into their (page, slot) coordinates,
    # in place in the carried pool
    with jax.named_scope("kv_write"):
        page_idx = page_idx + base
        ck = ck.at[page_idx, offset].set(kh.astype(ck.dtype))
        cv = cv.at[page_idx, offset].set(vh.astype(cv.dtype))

    with jax.named_scope("kv_read"):
        block_tables = block_tables + base
        if paged_core is None:
            # gather each row's block table back to a contiguous KV
            # view: (B, Pmax, page, KV, Dh) -> (B, Smax, KV, Dh),
            # position order — it already holds the K/V scattered
            # just above, so chunk tokens attend to earlier tokens of
            # the same chunk (the fused kernel reads the same pool)
            smax = block_tables.shape[1] * ck.shape[1]
            kall = ck[block_tables].reshape(b, smax, kvh, hd).astype(x.dtype)
            vall = cv[block_tables].reshape(b, smax, kvh, hd).astype(x.dtype)

    with jax.named_scope("attention"):
        qg = qh.reshape(b, s, kvh, h // kvh, hd)
        if paged_core is not None:
            ctx = paged_core(qg, ck, cv, block_tables, positions)
        else:
            core = attn_core if attn_core is not None else _attn_core
            ctx = core(qg, kall, vall, positions, cfg, policy)
        return L.mm(ctx.reshape(b, s, h * hd), p["wo"], policy), ck, cv


def _paged_forward(params, cfg: ModelConfig, policy, tokens, kv,
                   block_tables, positions, page_idx, offset,
                   attn_core=None, paged_core=None):
    """Full-model paged step: embed -> layers -> logits (B, S, V).

    kv holds the stacked pool (L, P, page, KV, Dh). The scan carries it
    flattened to (L*P, page, KV, Dh), a bitcast, and layer li scatters
    into and gathers from its own pages, at ids offset by li*P, in
    place: no layer's pool is sliced out or written back.

    Its parts run under stable named scopes (`embed`, `kv_read`,
    `attention`, `mlp`, `kv_write`, `lm_head`), which name the device
    operations of a profiler trace and change no computation."""
    dtype = jnp.dtype(cfg.compute_dtype)
    with jax.named_scope("embed"):
        x = transformer._embed_tokens(params, cfg, tokens, dtype)  # (B, S, d)
    n_layers, n_pages = kv["k"].shape[:2]
    pool_shape = kv["k"].shape

    def ln(lnp, y):
        return L.rmsnorm(lnp, y, cfg.norm_eps)

    def body(carry, lp):
        x, ck, cv, base = carry
        with jax.named_scope("attention"):
            xn = ln(lp["ln1"], x)
        h, ck, cv = _paged_attn_block(
            lp, xn, cfg, policy, positions, ck, cv, base, block_tables,
            page_idx, offset, attn_core=attn_core, paged_core=paged_core)
        x = x + h
        with jax.named_scope("mlp"):
            if cfg.family == "moe":
                f, _ = M.moe_ffn(lp["moe"], ln(lp["ln2"], x), cfg, policy)
            else:
                f = L.ffn(lp["ffn"], ln(lp["ln2"], x),
                          cfg.act, cfg.glu, policy)
        x = x + f
        return (x, ck, cv, base + n_pages), None

    flat = (n_layers * n_pages,) + pool_shape[2:]
    (x, ck, cv, _), _ = jax.lax.scan(
        body, (x, kv["k"].reshape(flat), kv["v"].reshape(flat),
               jnp.zeros((), jnp.int32)),
        params["layers"])
    with jax.named_scope("lm_head"):
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = transformer._logits(params, cfg, x)            # (B, S, V)
    return logits, {"k": ck.reshape(pool_shape), "v": cv.reshape(pool_shape)}


# ---------------------------------------------------------------------------
# chunked + batched prefill
# ---------------------------------------------------------------------------


def make_paged_chunked_prefill(cfg: ModelConfig,
                               policy: ArithmeticPolicy = ArithmeticPolicy(),
                               attn_core=None, paged_core=None):
    """Returns chunked_prefill(params, tokens, kv, block_tables,
    start_pos, chunk_lens, active, write_from) -> (logits (B, C, V), kv).

    Row b carries chunk_lens[b] valid prompt tokens of one request,
    starting at absolute position start_pos[b]; block_tables[b] must
    already contain the pages covering [0, start_pos[b] + chunk_lens[b])
    (unused slots: trash page). Logits are returned for every chunk
    position; the engine indexes the last VALID position host-side when
    a chunk completes its prompt. Padding positions, inactive rows, and
    positions below write_from[b] (already resident via prefix sharing)
    scatter to the trash page and never enter a valid query's mask —
    rerun positions still attend to their OWN K/V through the resident
    shared pages, which hold identical values by construction.
    """
    _check_family(cfg)

    def chunked_prefill(params, tokens, kv, block_tables, start_pos,
                        chunk_lens, active, write_from):
        b, c = tokens.shape
        page = kv["k"].shape[2]
        pmax = block_tables.shape[1]
        idx = jnp.arange(c, dtype=jnp.int32)[None, :]           # (1, C)
        positions = start_pos[:, None] + idx                    # (B, C)
        valid = active[:, None] & (idx < chunk_lens[:, None])
        do_write = valid & (positions >= write_from[:, None])
        slot = jnp.take_along_axis(
            block_tables, jnp.clip(positions // page, 0, pmax - 1), axis=1)
        page_idx = jnp.where(do_write, slot, TRASH_PAGE)
        offset = jnp.where(do_write, positions % page, 0)
        return _paged_forward(params, cfg, policy, tokens, kv,
                              block_tables, positions, page_idx, offset,
                              attn_core=attn_core, paged_core=paged_core)

    return chunked_prefill


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


def make_paged_decode(cfg: ModelConfig,
                      policy: ArithmeticPolicy = ArithmeticPolicy(),
                      attn_core=None, paged_core=None):
    """Returns decode(params, tokens, kv, block_tables, seq_lens, active)
    -> (logits (B, V), kv). One token per lane at a fixed batch shape."""
    _check_family(cfg)

    def decode(params, tokens, kv, block_tables, seq_lens, active):
        page = kv["k"].shape[2]
        positions = seq_lens[:, None]                           # (B, 1)

        # scatter coordinates; inactive lanes write to the trash page
        page_slot = jnp.take_along_axis(
            block_tables, (seq_lens // page)[:, None], axis=1)[:, 0]
        page_idx = jnp.where(active, page_slot, TRASH_PAGE)[:, None]
        offset = jnp.where(active, seq_lens % page, 0)[:, None]
        logits, kv = _paged_forward(params, cfg, policy, tokens, kv,
                                    block_tables, positions, page_idx,
                                    offset, attn_core=attn_core,
                                    paged_core=paged_core)
        return logits[:, 0], kv

    return decode
