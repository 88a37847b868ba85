"""Ring attention — the ARTEMIS token-based dataflow as a shard_map module.

Paper §III.D.1/Fig 5(b): tokens are sharded across banks; each bank
computes Q_i/K_i/V_i locally, then the K_i (and V_i) shards travel a
ring+broadcast network while each bank accumulates partial attention
scores, overlapped with softmax max-tracking and the next MatMul.

TPU-native translation (DESIGN.md §2): banks -> devices along a mesh axis,
ring network -> `jax.lax.ppermute` on ICI, "keep updating y_max as scores
stream out" -> the online-softmax merge carried across ring steps. The
compute of step t overlaps the permute of step t+1 by construction
(ppermute is async on TPU; XLA schedules the DMA alongside the matmuls).

Exactness: per-chunk partial (o, m, l) statistics merge associatively
(paper Eq. 5's log-sum-exp decomposition), so the sharded result is
bit-comparable to full attention up to fp reassociation — pinned in
tests/test_parallel.py.

Layout: q, k, v are (B, S_local, H, Dh) on each device, S sharded along
`axis_name`; causal masking uses global positions derived from
axis_index. Zig-zag (striped) sharding for causal load balance is the
dataflow's `stripe` option (beyond-paper optimization, §Perf).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30
_EMPTY = 2**31 - 1   # INT32_MAX position = unwritten cache slot


def _repeat_kv(h: int, k, v):
    """Native GQA: repeat (B, S, KV, D) K/V heads up to the H query
    heads (KV must divide H). Head order matches the serve layer's
    grouped-query reshape (q head i -> kv head i // g)."""
    kvh = k.shape[2]
    if kvh == h:
        return k, v
    if h % kvh:
        raise ValueError(
            f"query heads ({h}) must be a multiple of KV heads ({kvh})")
    g = h // kvh
    return jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)


def _chunk_attn(q, k, v, q_pos, k_pos, scale, causal):
    """Single-chunk attention partials.

    q: (B,Sq,H,D), k/v: (B,Sk,H,D) -> (o_unnorm (B,Sq,H,D), m, l (B,Sq,H)).
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    # INT32_MAX-position slots mark unwritten cache entries: masked under
    # BOTH modes (the causal comparison used to be the only thing hiding
    # them, so non-causal attention read garbage K/V — surfaced when the
    # sharded serve path started calling these with padded paged views)
    keep = (k_pos < _EMPTY)[:, None, None, :]
    if causal:
        keep = keep & (q_pos[:, None, :, None] >= k_pos[:, None, None, :])
    s = jnp.where(keep, s, NEG_INF)
    m = jnp.max(s, axis=-1)                       # (B,H,Sq)
    # guard fully-masked rows (m == NEG_INF): exp(s - m) would be exp(0)=1
    m_safe = jnp.maximum(m, -1e29)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(s <= NEG_INF / 2, 0.0, p)
    l = jnp.sum(p, axis=-1)                       # (B,H,Sq)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return o, jnp.moveaxis(m_safe, 1, 2), jnp.moveaxis(l, 1, 2)


def _merge(o1, m1, l1, o2, m2, l2):
    """Associative online-softmax merge of two partials ((B,Sq,H,D) etc.)."""
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    o = o1 * a1[..., None] + o2 * a2[..., None]
    l = l1 * a1 + l2 * a2
    return o, m, l


def ring_attention(q, k, v, *, axis_name: str, causal: bool = True,
                   scale: float | None = None,
                   q_positions=None, kv_positions=None):
    """Sequence-sharded attention over `axis_name` (call inside shard_map).

    q: (B, Sq_local, H, Dh); k, v: (B, Sk_local, H|KV, Dh) — KV-head
    counts that divide H are repeated internally (GQA), and the K/V
    chunk length may differ from the query chunk length (the sharded
    paged-serve path rings a gathered cache view past short prompt
    chunks). `q_positions`/`kv_positions` are per-device GLOBAL
    positions of the local chunks (defaults assume contiguous layout);
    a device's kv positions travel the ring WITH its K/V chunk, so
    striped / paged layouts mask correctly on every hop. Returns
    (B, Sq_local, H, Dh) in q.dtype.
    """
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, s_local, h, d = q.shape
    k, v = _repeat_kv(h, k, v)
    s_k = k.shape[1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if q_positions is None:
        q_positions = idx * s_local + jnp.arange(s_local, dtype=jnp.int32)
        q_positions = jnp.broadcast_to(q_positions[None], (b, s_local))
    if kv_positions is None:
        kv_positions = idx * s_k + jnp.arange(s_k, dtype=jnp.int32)
        kv_positions = jnp.broadcast_to(kv_positions[None], (b, s_k))

    qf = q.astype(jnp.float32)

    def body(carry, _):
        o, m, l, kc, vc, pc = carry
        oc, mc, lc = _chunk_attn(qf, kc.astype(jnp.float32),
                                 vc.astype(jnp.float32),
                                 q_positions, pc, scale, causal)
        o, m, l = _merge(o, m, l, oc, mc, lc)
        # ring step: pass the chunk (and its positions — they describe
        # the chunk, not the device) to the next device (paper Fig 5(b)
        # Rounds 3-4); ppermute overlaps with the next step's compute
        perm = [(i, (i + 1) % n) for i in range(n)]
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        pc = jax.lax.ppermute(pc, axis_name, perm)
        return (o, m, l, kc, vc, pc), None

    o0 = jnp.zeros((b, s_local, h, d), jnp.float32)
    m0 = jnp.full((b, s_local, h), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, s_local, h), jnp.float32)
    # mark the constant inits as device-varying over the ring axis (the body
    # outputs are varying; scan carries must type-match under shard_map vma)
    o0, m0, l0 = (jax.lax.pcast(a, axis_name, to="varying")
                  for a in (o0, m0, l0))
    (o, m, l, _, _, _), _ = jax.lax.scan(
        body, (o0, m0, l0, k, v, kv_positions), None, length=n)
    l = jnp.maximum(l, 1e-30)
    return (o / l[..., None]).astype(q.dtype)


def layer_dataflow_attention(q, k, v, *, axis_name: str,
                             causal: bool = True,
                             scale: float | None = None):
    """The LAYER-BASED dataflow baseline (paper Fig 8 'layer_*'): all-gather
    the full K/V onto every device, then attend locally. Same math, strictly
    more ICI bytes — the comparison benchmarks/collective_bytes.py measures.
    """
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, s_local, h, d = q.shape
    k, v = _repeat_kv(h, k, v)
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    kg = jax.lax.all_gather(k, axis_name, axis=1, tiled=True)
    vg = jax.lax.all_gather(v, axis_name, axis=1, tiled=True)
    q_pos = idx * s_local + jnp.arange(s_local, dtype=jnp.int32)
    q_pos = jnp.broadcast_to(q_pos[None], (b, s_local))
    k_pos = jnp.broadcast_to(
        jnp.arange(kg.shape[1], dtype=jnp.int32)[None], (b, kg.shape[1]))
    o, m, l = _chunk_attn(q.astype(jnp.float32), kg.astype(jnp.float32),
                          vg.astype(jnp.float32), q_pos, k_pos, scale,
                          causal)
    l = jnp.maximum(l, 1e-30)
    return (o / l[..., None]).astype(q.dtype)
