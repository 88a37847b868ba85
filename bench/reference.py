"""Plain float32 reference forwards, from the published equations.

Written in `jax.numpy`, importing nothing of the program.  Each
architecture file (`arch/<model_type>.py`) gives its `forward`; the
pre-norm GQA decoder that Qwen3 (hf:Qwen/Qwen3-8B) and Llama share is
`decoder` here:

    x = embed[tokens]
    per layer:
        h  = rmsnorm(x) * ln1
        q, k, v = h Wq, h Wk, h Wv        (GQA: H query, KV key/value heads)
        q, k = rmsnorm over head_dim * q_norm, k_norm   (QK-norm, Qwen3)
        q, k = RoPE(theta), rotate-half form
        x += softmax(q k^T / sqrt(hd), causal) v  Wo
        h  = rmsnorm(x) * ln2
        x += (silu(h Wgate) * (h Wup)) Wdown
    logits = (rmsnorm(x) * final_norm) Whead      (embed^T where tied)

Every operation is float32 under `jax.default_matmul_precision
("highest")`; the weights are the benchmark's own arrays, upcast one
layer at a time inside a scan, and attention runs in blocks of query
rows so that a long context fits beside the weights.
"""
from __future__ import annotations

import functools
import json

Q_BLOCK = 512
BUCKET = 512


def _rmsnorm(x, scale, eps):
    import jax
    import jax.numpy as jnp
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * scale.astype(jnp.float32)


def _rope(x, positions, theta):
    """x: (T, H, hd); rotate-half RoPE at `positions` (T,)."""
    import jax.numpy as jnp
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """Causal GQA attention. q: (T, H, hd), k/v: (T, KV, hd) -> (T, H*hd),
    computed Q_BLOCK query rows at a time."""
    import jax
    import jax.numpy as jnp
    t, h, hd = q.shape
    kvh = k.shape[1]
    g = h // kvh
    qb = q.reshape(t // Q_BLOCK, Q_BLOCK, kvh, g, hd)
    kpos = jnp.arange(t)

    def block(args):
        i, qi = args
        s = jnp.einsum("qkgd,tkd->kgqt", qi, k) * hd ** -0.5
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, None, None, :] <= qpos[None, None, :, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v)

    out = jax.lax.map(block, (jnp.arange(t // Q_BLOCK), qb))
    return out.reshape(t, h * hd)


def decoder(params, tokens, positions, conf: dict, *, qk_norm: bool):
    """Logits (P, V) of the rows at `positions` of `tokens` (T,)."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    heads, kv_heads = conf["num_attention_heads"], conf["num_key_value_heads"]
    head_dim, eps = conf["head_dim"], float(conf["rms_norm_eps"])
    theta = float(conf["rope_theta"])
    t = tokens.shape[0]
    pos = jnp.arange(t)
    x = params["embed"][tokens].astype(f32)

    def layer(x, lp):
        lp = jax.tree.map(lambda a: a.astype(f32), lp)
        h = _rmsnorm(x, lp["ln1"]["scale"], eps)
        a = lp["attn"]
        q = (h @ a["wq"]).reshape(t, heads, head_dim)
        k = (h @ a["wk"]).reshape(t, kv_heads, head_dim)
        v = (h @ a["wv"]).reshape(t, kv_heads, head_dim)
        if qk_norm:
            q = _rmsnorm(q, a["q_norm"], eps)
            k = _rmsnorm(k, a["k_norm"], eps)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        x = x + _attention(q, k, v) @ a["wo"]
        h = _rmsnorm(x, lp["ln2"]["scale"], eps)
        f = lp["ffn"]
        x = x + (jax.nn.silu(h @ f["w_gate"]) * (h @ f["w_up"])) @ f["w_down"]
        return x, None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    rows = _rmsnorm(x[positions], params["final_norm"]["scale"], eps)
    head = params["embed"].T if conf["tie_word_embeddings"] else params["head"]
    return rows @ head.astype(f32)


def _gaps(forward, params, tokens, positions, served):
    """Per row: the reference's best logit minus its logit of the
    token that was served there."""
    import jax.numpy as jnp
    out = forward(params, tokens, positions)
    picked = jnp.take_along_axis(out, served[:, None], axis=1)[:, 0]
    return jnp.max(out, axis=1) - picked


@functools.lru_cache(maxsize=None)
def _compiled(arch, conf_json: str, gaps: bool):
    import jax
    conf = json.loads(conf_json)

    def forward(params, tokens, positions):
        return arch.forward(params, tokens, positions, conf)

    if gaps:
        return jax.jit(functools.partial(_gaps, forward))
    return jax.jit(forward)


def _padded(arch, conf, gaps, params, tokens, positions, rows, *extra):
    import jax
    import jax.numpy as jnp
    import numpy as np
    n = len(tokens)
    t = -(-n // BUCKET) * BUCKET
    seq = np.zeros((t,), np.int32)
    seq[:n] = tokens
    pos = np.zeros((rows,), np.int32)
    pos[:len(positions)] = positions
    args = [jnp.asarray(seq), jnp.asarray(pos)]
    for e in extra:
        pad = np.zeros((rows,), np.int32)
        pad[:len(e)] = e
        args.append(jnp.asarray(pad))
    f = _compiled(arch, json.dumps(conf, sort_keys=True), gaps)
    with jax.default_matmul_precision("highest"):
        out = f(params, *args)
    return np.asarray(out[:len(positions)])


def logits(arch, params, conf: dict, tokens, positions, rows: int):
    """Reference logits (len(positions), V) in float32 at `positions`
    of the token sequence `tokens`, by the forward of architecture
    `arch`.  The sequence is padded to a multiple of BUCKET and the
    positions to `rows`, so that one run compiles few shapes."""
    return _padded(arch, conf, False, params, tokens, positions, rows)


def gaps(arch, params, conf: dict, tokens, positions, served, rows: int):
    """For each row at `positions`: how far the logit of `served` (the
    token served there) lies below the reference's best, in logits."""
    return _padded(arch, conf, True, params, tokens, positions, rows, served)
