#!/usr/bin/env python3
"""Serve a published model through the engine on a TPU and check it.

    python chip_smoke.py [--seed N]               # one chip
    python chip_smoke.py [--seed N] --four-chips  # four chips

One chip: gemma-2b at its full published config (`configs/gemma_2b.py`
FULL: 18 layers, d_model 2048, 8 query / 1 KV heads x 256, d_ff 16384,
vocab 256,000; 2.51 B parameters), random weights from `--seed`, with
f32 parameters, KV pool and compute.  The engine's own entry points
(`EngineConfig`, `ServeEngine`, `submit`, `drain`) serve the same eight
requests twice, once per paged attention path: `attn_impl="gather"`
and `attn_impl="fused"` (the Pallas paged-attention kernel).  Checks:

  * every logit row the engine sampled a token from (the last prompt
    position, then each decode step) matches a plain full forward
    (`model.apply`) of the same tokens;
  * fused matches gather: logits close, greedy tokens identical;
  * the compiled fused decode step holds a `tpu_custom_call`: the
    kernel was compiled for the chip, not interpreted.

Four chips (`--four-chips`, and nothing else): qwen3-8b at published
widths with bf16 parameters (f32 compute), tensor-parallel over a
4-way mesh (`mesh_shards=4`, the KV pool split by KV head):

  * cut to 8 layers, served on one chip and on four: logits close,
    greedy tokens identical;
  * all 36 layers on four chips (16.4 GB of parameters, more than one
    chip holds, created already sharded): a drain completes and every
    sampled logit row is finite.

Tolerance: everything runs under `jax.default_matmul_precision
("highest")`, so f32 matmuls are full f32 on the MXU and two correct
computations of the same logits differ only by summation order (about
1e-6 of the largest logit).  `TOL` = 1e-3 of the largest reference
logit, per request, leaves room for that across 36 layers; a single
bf16 matmul pass (about 4e-3 relative per operation) or a paging or
masking fault breaks it.

The last line of stdout is one JSON object, printed only when every
check passed:
    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}
The script exits non-zero without it when JAX finds no TPU, when it is
run outside a checkout of this repository, or when any check fails.
It runs in one process and starts no other.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"
TOL = 1e-3           # max |engine - reference| / max |reference|
PAGE = 16            # KV page size, tokens
MAX_BATCH = 8        # engine lanes = requests served together
CHUNK = 32           # prefill chunk, tokens
PROMPT_LENS = (64, 256)   # inclusive range of prompt lengths
GEN_LENS = (16, 32)       # inclusive range of new tokens per request


def fail(msg: str) -> None:
    print(f"FAIL: {msg}", flush=True)
    sys.exit(1)


def make_requests(rng, vocab: int, n: int = MAX_BATCH):
    """n (prompt, max_new_tokens) pairs drawn from `rng`."""
    out = []
    for _ in range(n):
        p = int(rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1))
        g = int(rng.integers(GEN_LENS[0], GEN_LENS[1] + 1))
        out.append((rng.integers(0, vocab, p).astype("int32"), g))
    return out


def engine_config(**kw):
    from repro.serve import EngineConfig
    pmax = -(-(PROMPT_LENS[1] + GEN_LENS[1]) // PAGE)
    return EngineConfig(page_size=PAGE, n_pages=MAX_BATCH * pmax + 1,
                        max_batch=MAX_BATCH, max_pages_per_seq=pmax,
                        prefill_chunk=CHUNK, **kw)


def serve(cfg, params, ecfg, requests, seed: int, label: str):
    """Drain `requests` through a fresh engine after a one-request
    warm-up drain that compiles its steps.  Returns the engine, the
    logit rows it sampled from ({(rid, k): (V,) row that chose token
    k}) and the wall seconds of both drains."""
    import jax
    from repro.serve import ServeEngine

    eng = ServeEngine(cfg, params=params, ecfg=ecfg, seed=seed)
    rows = {}
    sample_rows = eng._sample_rows

    def recording_sample_rows(logits, lanes):
        # observation only: keep each row the sampler is handed
        for row, req in lanes:
            rows[(req.rid, len(req.generated))] = logits[row]
        return sample_rows(logits, lanes)

    eng._sample_rows = recording_sample_rows
    t0 = time.perf_counter()
    eng.submit(requests[0][0][:CHUNK + 8], 2)
    eng.drain()
    compile_s = time.perf_counter() - t0
    rids = [eng.submit(p, g) for p, g in requests]
    t0 = time.perf_counter()
    eng.drain()
    jax.block_until_ready(list(rows.values()))
    drain_s = time.perf_counter() - t0
    out = eng.results()
    n_tok = sum(len(out[r]) for r in rids)
    print(f"{label}: {len(rids)} requests, {n_tok} tokens | warm-up "
          f"drain (compiles) {compile_s:.1f} s | drain {drain_s:.2f} s",
          flush=True)
    for rid, (p, g) in zip(rids, requests):
        if len(out[rid]) != g:
            fail(f"{label}: request {rid} produced {len(out[rid])} of "
                 f"{g} tokens")
    return eng, rids, rows


def rows_of(rows, rid: int, n: int):
    import jax.numpy as jnp
    return jnp.stack([rows[(rid, k)] for k in range(n)])


def rel_err(a, b) -> float:
    import jax.numpy as jnp
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def check_close(label: str, a, b, rids, requests, results) -> None:
    """Per request: the logit rows of two runs agree within TOL and
    their greedy tokens are identical."""
    worst = 0.0
    for (ra, rb), (_, g) in zip(rids, requests):
        if not (results[0][ra] == results[1][rb]).all():
            fail(f"{label}: greedy tokens differ for request {ra}")
        worst = max(worst, rel_err(rows_of(a, ra, g), rows_of(b, rb, g)))
    print(f"{label}: greedy tokens identical; max logit error "
          f"{worst:.3g} of the largest logit (tolerance {TOL})",
          flush=True)
    if not worst <= TOL:
        fail(f"{label}: logit error {worst:.3g} > {TOL}")


def check_reference(label: str, forward, params, rows, rids, requests,
                    results) -> None:
    """Each sampled logit row against `forward`, a plain full forward
    of the prompt plus the tokens generated before it."""
    import jax.numpy as jnp
    import numpy as np

    width = PROMPT_LENS[1] + GEN_LENS[1]
    worst = 0.0
    for rid, (prompt, g) in zip(rids, requests):
        seq = np.zeros((1, width), np.int32)
        toks = np.concatenate([prompt, results[rid][:-1]])
        seq[0, :len(toks)] = toks
        ref = forward(params, jnp.asarray(seq))[len(prompt) - 1:
                                                len(prompt) - 1 + g]
        worst = max(worst, rel_err(rows_of(rows, rid, g), ref))
    print(f"{label}: max logit error vs full forward {worst:.3g} of the "
          f"largest logit (tolerance {TOL})", flush=True)
    if not worst <= TOL:
        fail(f"{label}: logit error vs full forward {worst:.3g} > {TOL}")


def cache_entries(cache_dir: str) -> int:
    """Entries in the compile cache directory (0 before it exists)."""
    path = Path(cache_dir)
    return len(list(path.iterdir())) if path.is_dir() else 0


def peak_bytes() -> str:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 1e9:.2f} GB"


def one_chip(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import configs
    from repro.models import model
    from repro.serve.mesh import init_params, make_serve_mesh

    cfg = dataclasses.replace(configs.get_config("gemma_2b"),
                              compute_dtype="float32",
                              param_dtype="float32")
    print(f"model: {cfg.name} full config, {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, {cfg.n_heads}/{cfg.n_kv_heads} heads x "
          f"{cfg.resolved_head_dim}, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab_size}; {cfg.param_count() / 1e9:.2f} B parameters "
          f"in {cfg.param_dtype}, compute {cfg.compute_dtype}, KV pool "
          f"float32", flush=True)
    t0 = time.perf_counter()
    params = init_params(make_serve_mesh(1), cfg, jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    print(f"params: {time.perf_counter() - t0:.1f} s to initialize; peak "
          f"device memory {peak_bytes()}", flush=True)
    requests = make_requests(np.random.default_rng(seed), cfg.vocab_size)

    runs = {}
    for impl in ("gather", "fused"):
        runs[impl] = serve(cfg, params, engine_config(attn_impl=impl),
                           requests, seed, f"attn_impl={impl}")
        print(f"attn_impl={impl}: peak device memory {peak_bytes()}",
              flush=True)

    eng, _, _ = runs["fused"]
    b, pmax = eng.ecfg.max_batch, eng.ecfg.max_pages_per_seq
    text = eng.backend._decode_fn.lower(
        eng.backend.params, jnp.zeros((b, 1), jnp.int32),
        eng.backend.cache.kv, jnp.zeros((b, pmax), jnp.int32),
        jnp.zeros((b,), jnp.int32), jnp.zeros((b,), bool),
    ).compile().as_text()
    if "tpu_custom_call" not in text:
        fail("the compiled fused decode step holds no tpu_custom_call")
    print("attn_impl=fused: compiled decode step holds a tpu_custom_call "
          "(the paged kernel is compiled, not interpreted)", flush=True)

    results = {k: run[0].results() for k, run in runs.items()}
    forward = jax.jit(lambda p, t: model.apply(p, cfg, {"tokens": t},
                                               remat=False)[0][0])
    for impl, (_, rids, rows) in runs.items():
        check_reference(f"attn_impl={impl}", forward, params, rows, rids,
                        requests, results[impl])
    check_close("fused vs gather", runs["fused"][2], runs["gather"][2],
                list(zip(runs["fused"][1], runs["gather"][1])), requests,
                (results["fused"], results["gather"]))


def four_chips(seed: int) -> None:
    import gc

    import jax.numpy as jnp
    import numpy as np
    from repro import configs

    full = dataclasses.replace(configs.get_config("qwen3_8b"),
                               compute_dtype="float32",
                               param_dtype="bfloat16")
    cut = dataclasses.replace(full, n_layers=8)
    requests = make_requests(np.random.default_rng(seed), full.vocab_size)

    print(f"model: {cut.name} cut to {cut.n_layers} of {full.n_layers} "
          f"layers, published widths, {cut.param_count() / 1e9:.2f} B "
          f"parameters in {cut.param_dtype}", flush=True)
    one = serve(cut, None, engine_config(), requests, seed,
                f"{cut.n_layers} layers, 1 chip")
    four = serve(cut, one[0].params, engine_config(mesh_shards=4),
                 requests, seed, f"{cut.n_layers} layers, 4 chips")
    check_close(f"{cut.n_layers} layers, 4 chips vs 1 chip", four[2], one[2],
                list(zip(four[1], one[1])), requests,
                (four[0].results(), one[0].results()))
    del one, four
    gc.collect()

    print(f"model: {full.name} all {full.n_layers} layers, "
          f"{full.param_count() / 1e9:.2f} B parameters in "
          f"{full.param_dtype}, created sharded over 4 chips", flush=True)
    label = f"{full.n_layers} layers, 4 chips"
    _, _, rows = serve(full, None, engine_config(mesh_shards=4), requests,
                       seed, label)
    if not all(bool(jnp.isfinite(r).all()) for r in rows.values()):
        fail(f"{label}: non-finite logits")
    print(f"{label}: all sampled logit rows finite; peak device memory "
          f"(device 0) {peak_bytes()}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and requests")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip tensor-parallel phase")
    args = ap.parse_args()
    if not (SRC / "repro").is_dir():
        fail(f"no {SRC / 'repro'}: run chip_smoke.py from a checkout of "
             f"the repository")
    sys.path.insert(0, str(SRC))
    import jax

    from repro.launch.compile_cache import use_compile_cache
    from repro.serve.mesh import describe_devices

    cache_dir = use_compile_cache()
    dev = describe_devices()
    print(f"device: platform {dev['platform']}, kind {dev['kind']}, "
          f"count {dev['count']}", flush=True)
    print(f"compile cache: {cache_dir} ({cache_entries(cache_dir)} entries "
          f"at start)", flush=True)
    if dev["platform"] != "tpu":
        fail(f"JAX finds no TPU (platform {dev['platform']!r})")
    want = 4 if args.four_chips else 1
    if dev["count"] < want:
        fail(f"needs {want} chips, JAX finds {dev['count']}")
    with jax.default_matmul_precision("highest"):
        if args.four_chips:
            four_chips(args.seed)
        else:
            one_chip(args.seed)
    print(f"compile cache: {cache_entries(cache_dir)} entries at end",
          flush=True)
    print(json.dumps({"ok": True, "device": dev}))


if __name__ == "__main__":
    main()
