"""Serving launcher — static batch or the continuous-batching engine.

Two modes (the paper is inference-oriented; this is the serve driver):

  --mode static   the original continuous-batching-lite path: requests
                  with different prompt lengths are left-padded into one
                  batch, prefilled once, then decoded in lockstep with
                  greedy sampling against the dense KV cache.
  --mode engine   the `repro.serve` engine: per-request lifecycles with
                  chunked+batched prefill composed with decode into
                  mixed steps by the ARTEMIS-cost-aware scheduler,
                  driven by a synthetic Poisson trace (`--prefill-chunk`
                  sets the chunk size, `--seed` the trace/params seed).
                  EVERY family routes through the same engine: the
                  attention archs (dense/moe) serve over the paged KV
                  backend (COW prefix sharing, `--prefix-groups` et
                  al.), the recurrent archs (rwkv6/zamba2) over the
                  state-slot backend (`--n-slots` sizes its pool) — see
                  repro.serve.backend. `--temperature/--top-k/--top-p/
                  --sample-seed` switch the trace to stochastic decode
                  on per-request RNG lanes (`--sampled-fraction` mixes
                  greedy and sampled requests) — deterministic for a
                  fixed seed, independent of batch composition.
                  `--mesh-shards N` (attention archs) serves tensor-
                  parallel over the sharded paged backend; on CPU set
                  XLA_FLAGS=--xla_force_host_platform_device_count=N.

The ARTEMIS arithmetic policy applies to every matmul in both modes.

Wall-clock use here is intentional (the CLI reports real prefill /
decode / drain seconds next to the virtual-clock metrics) and carries
`repro: allow[wall-clock-in-serve]` markers — the virtual-clock
contract applies to serve-layer logic, not to the driver timing it.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro import configs
from repro.core.policy import ArithmeticPolicy
from repro.launch import steps as stepslib
from repro.launch.compile_cache import use_compile_cache
from repro.models import frontend, model
from repro.serve.mesh import describe_devices


def serve(arch: str = "qwen3_8b", smoke: bool = True,
          batch: int = 4, prompt_len: int = 32, gen_len: int = 16,
          policy_mode: str = "exact", seed: int = 0,
          params=None) -> dict:
    """Static-batch serving: one prefill, lockstep decode."""
    cfg = configs.get_config(arch, smoke=smoke)
    policy = ArithmeticPolicy(mode=policy_mode)
    if params is None:
        params = model.init(jax.random.PRNGKey(seed), cfg)

    prefill = jax.jit(stepslib.make_prefill_step(cfg, policy))
    decode = jax.jit(stepslib.make_decode_step(cfg, policy))

    key = jax.random.PRNGKey(seed + 1)
    tokens = jax.random.randint(
        key, frontend.token_shape(cfg, batch, prompt_len), 2,
        cfg.vocab_size, dtype=jnp.int32)
    max_len = prompt_len + gen_len + frontend.n_prefix_tokens(cfg)
    cache = model.init_cache(cfg, batch, max_len, dtype=jnp.float32)

    bt = {"tokens": tokens}
    if cfg.modality == "vlm":
        bt["prefix_embeds"] = frontend.synth_prefix_embeds(
            jax.random.PRNGKey(seed + 2), cfg, batch)

    t0 = time.time()  # repro: allow[wall-clock-in-serve] -- real demo wall time, printed for the operator
    logits, cache = prefill(params, bt, cache)
    logits.block_until_ready()
    t_prefill = time.time() - t0  # repro: allow[wall-clock-in-serve] -- real demo wall time, printed for the operator

    out_tokens = []
    nxt = stepslib.greedy_sample(logits)
    t0 = time.time()  # repro: allow[wall-clock-in-serve] -- real demo wall time, printed for the operator
    for _ in range(gen_len):
        # (B,) -> (B, 1); audio's (B, C) broadcasts to (B, 1, C) the
        # same way, so one expression covers both modalities
        step_tok = nxt[:, None]
        logits, cache = decode(params, step_tok, cache)
        nxt = stepslib.greedy_sample(logits)
        out_tokens.append(nxt)
    jax.block_until_ready(out_tokens[-1])
    t_decode = time.time() - t0  # repro: allow[wall-clock-in-serve] -- real demo wall time, printed for the operator

    gen = jnp.stack(out_tokens, axis=1)
    return {
        "generated": gen,
        "prefill_s": t_prefill,
        "decode_s": t_decode,
        "decode_tok_per_s": batch * gen_len / max(t_decode, 1e-9),
        "cache_index": int(cache["index"]),
    }


def serve_engine(arch: str = "qwen3_8b", smoke: bool = True,
                 n_requests: int = 16, arrival_rate: float = 200.0,
                 prompt_len: int = 32, gen_len: int = 16,
                 policy_mode: str = "exact", seed: int = 0,
                 page_size: int = 8, n_pages: int = 256,
                 max_batch: int = 8, scheduler: str = "cost",
                 prefill_chunk: int = 32, prefix_sharing: bool = True,
                 prefix_groups: int = 0, prefix_len: int = 0,
                 n_slots: int = 0, sampled_fraction: float = 0.0,
                 temperature: float = 0.8, top_k: int = 0,
                 top_p: float = 1.0, sample_seed: int = -1,
                 observability: str = "metrics",
                 trace_json: str | None = None,
                 mesh_shards: int = 1, attn_impl: str = "gather",
                 params=None) -> dict:
    """Continuous-batching serving over a synthetic Poisson trace (any
    family — the engine routes to the right sequence backend). With
    `sampled_fraction > 0` that share of requests decodes stochastic
    (temperature/top-k/top-p on per-request RNG lanes, deterministic
    for a fixed trace seed); the rest stay greedy. `trace_json` (which
    implies observability="trace") exports the run's structured event
    log as Chrome trace-event JSON — open it at https://ui.perfetto.dev
    over the virtual ARTEMIS clock."""
    from repro.serve import (EngineConfig, ServeEngine, TrafficConfig,
                             export_chrome_trace, synth_trace)
    from repro.serve.traffic import trace_stats
    cfg = configs.get_config(arch, smoke=smoke)
    policy = ArithmeticPolicy(mode=policy_mode)
    if trace_json is not None:
        observability = "trace"
    max_len = prefix_len + prompt_len + gen_len
    ecfg = EngineConfig(
        page_size=page_size, n_pages=n_pages, max_batch=max_batch,
        max_pages_per_seq=max(1, -(-max_len // page_size)) + 1,
        prefill_chunk=prefill_chunk, scheduler=scheduler,
        prefix_sharing=prefix_sharing, n_slots=n_slots,
        max_seq_len=max(max_len + 1, 2), observability=observability,
        mesh_shards=mesh_shards, attn_impl=attn_impl)
    eng = ServeEngine(cfg, params=params, policy=policy, ecfg=ecfg,
                      seed=seed)
    trace = synth_trace(TrafficConfig(
        n_requests=n_requests, arrival_rate=arrival_rate,
        prompt_len_min=max(1, prompt_len // 2), prompt_len_max=prompt_len,
        gen_len_min=max(1, gen_len // 2), gen_len_max=gen_len,
        vocab_size=cfg.vocab_size, seed=seed,
        n_prefix_groups=prefix_groups, prefix_len=prefix_len,
        sampled_fraction=sampled_fraction, temperature=temperature,
        top_k=top_k, top_p=top_p, sample_seed=sample_seed))
    eng.submit_trace(trace)
    t0 = time.time()  # repro: allow[wall-clock-in-serve] -- real demo wall time, printed for the operator
    eng.drain()
    wall = time.time() - t0  # repro: allow[wall-clock-in-serve] -- real demo wall time, printed for the operator
    m = eng.metrics()
    m["wall_s"] = wall
    m["wall_tok_per_s"] = m["n_generated_tokens"] / max(wall, 1e-9)
    if trace_json is not None:
        export_chrome_trace(
            eng.events, trace_json,
            metadata={"arch": arch, "seed": seed,
                      "scheduler": scheduler, **trace_stats(trace)})
    return {"metrics": m, "results": eng.results(),
            "events": eng.events, "attribution": eng.attribution()}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mode", default="static",
                    choices=["static", "engine"])
    ap.add_argument("--arch", default="qwen3_8b")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="static batch size / engine decode lanes")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--policy", default="exact",
                    choices=["exact", "int8", "artemis", "artemis_mxu"])
    ap.add_argument("--n-requests", type=int, default=16,
                    help="engine: synthetic trace length")
    ap.add_argument("--arrival-rate", type=float, default=200.0,
                    help="engine: Poisson arrivals per virtual second")
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--n-pages", type=int, default=256)
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="engine: prompt tokens per prefill chunk")
    ap.add_argument("--scheduler", default="cost",
                    choices=["cost", "fcfs"])
    ap.add_argument("--no-prefix-sharing", action="store_true",
                    help="engine: disable COW prefix/page sharing "
                         "(paged-KV backend)")
    ap.add_argument("--n-slots", type=int, default=0,
                    help="engine: state-slot pool size for recurrent "
                         "archs (0 = auto: batch lanes + 1)")
    ap.add_argument("--prefix-groups", type=int, default=0,
                    help="engine: shared-prefix trace groups (0 = "
                         "independent prompts)")
    ap.add_argument("--prefix-len", type=int, default=0,
                    help="engine: tokens shared within a prefix group")
    ap.add_argument("--seed", type=int, default=0,
                    help="params + synthetic trace seed")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="engine: sampling temperature for sampled "
                         "requests (0 = all-greedy trace)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="engine: top-k truncation for sampled "
                         "requests (0 = none)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="engine: nucleus mass for sampled requests "
                         "(1.0 = none)")
    ap.add_argument("--sample-seed", type=int, default=-1,
                    help="engine: fixed RNG-lane seed for every "
                         "sampled request (-1 = per-request seeds "
                         "from the trace rng)")
    ap.add_argument("--sampled-fraction", type=float, default=None,
                    help="engine: fraction of requests decoded "
                         "stochastically (default: 1.0 when "
                         "--temperature > 0, else 0)")
    ap.add_argument("--observability", default="metrics",
                    choices=["metrics", "trace"],
                    help="engine: 'trace' retains the structured "
                         "event log (span assembly / Perfetto export)")
    ap.add_argument("--trace-json", default=None, metavar="PATH",
                    help="engine: export the run as Chrome trace-event "
                         "JSON to PATH (implies --observability trace)")
    ap.add_argument("--mesh-shards", type=int, default=1,
                    help="engine: tensor-parallel degree — >1 serves "
                         "attention archs over the sharded paged "
                         "backend (on CPU, simulate devices with "
                         "XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--attn-impl", default="gather",
                    choices=["gather", "fused"],
                    help="engine: paged attention core — 'fused' walks "
                         "the block table inside the Pallas kernel "
                         "(exact policy, mesh_shards=1; interpreted "
                         "off-TPU)")
    args = ap.parse_args()
    print(f"compile cache: {use_compile_cache()}")
    dev = describe_devices()
    print(f"device: {dev['platform']} {dev['kind']} x{dev['count']}")
    sampled_fraction = args.sampled_fraction
    if sampled_fraction is None:
        sampled_fraction = 1.0 if args.temperature > 0 else 0.0
    elif sampled_fraction > 0 and args.temperature <= 0:
        ap.error("--sampled-fraction > 0 requires --temperature > 0")

    if args.mode == "static":
        out = serve(arch=args.arch, smoke=not args.full, batch=args.batch,
                    prompt_len=args.prompt_len, gen_len=args.gen_len,
                    policy_mode=args.policy, seed=args.seed)
        print(f"prefill {out['prefill_s']*1e3:.0f}ms | decode "
              f"{out['decode_tok_per_s']:.1f} tok/s | "
              f"generated shape {out['generated'].shape}")
        return

    out = serve_engine(
        arch=args.arch, smoke=not args.full, n_requests=args.n_requests,
        arrival_rate=args.arrival_rate, prompt_len=args.prompt_len,
        gen_len=args.gen_len, policy_mode=args.policy, seed=args.seed,
        page_size=args.page_size, n_pages=args.n_pages,
        max_batch=args.batch, scheduler=args.scheduler,
        prefill_chunk=args.prefill_chunk,
        prefix_sharing=not args.no_prefix_sharing,
        prefix_groups=args.prefix_groups, prefix_len=args.prefix_len,
        n_slots=args.n_slots, sampled_fraction=sampled_fraction,
        temperature=args.temperature, top_k=args.top_k,
        top_p=args.top_p, sample_seed=args.sample_seed,
        observability=args.observability, trace_json=args.trace_json,
        mesh_shards=args.mesh_shards, attn_impl=args.attn_impl)
    m = out["metrics"]
    line = (f"engine: {m['n_done']} requests, "
            f"{m['n_generated_tokens']} tokens "
            f"({m['n_sampled_tokens']} sampled) | "
            f"{m['wall_tok_per_s']:.1f} tok/s wall | "
            f"p50 {m['p50_latency_s']*1e3:.3f}ms "
            f"p99 {m['p99_latency_s']*1e3:.3f}ms "
            f"p99-ttft {m['p99_ttft_s']*1e3:.3f}ms (virtual) | "
            f"cache util {m['cache_utilization']:.2f} "
            f"(logical {m['logical_cache_utilization']:.2f})")
    if "prefix_hit_rate" in m:       # paged-KV backend extras
        line += (f" | prefix hits {m['n_prefix_hits']} "
                 f"(rate {m['prefix_hit_rate']:.2f}) | "
                 f"{m['n_cow_forks']} COW forks")
    if "n_state_slots" in m:         # state-slot backend extras
        line += f" | {m['n_state_slots']} state slots"
    print(line + f" | {m['n_preemptions']} preemptions")
    print(f"energy: {m['total_energy_J']*1e6:.2f} uJ total "
          f"({m['energy_per_token_J']*1e9:.2f} nJ/token) | "
          f"prefill {m['prefill_energy_J']*1e6:.2f} uJ / "
          f"decode {m['decode_energy_J']*1e6:.2f} uJ | "
          f"busy {m['busy_virtual_s']*1e3:.3f} of "
          f"{m['virtual_time_s']*1e3:.3f} virtual ms")
    if args.trace_json:
        print(f"trace: wrote {args.trace_json} "
              f"({m['n_events']} counted events) — open at "
              f"https://ui.perfetto.dev")


if __name__ == "__main__":
    main()
