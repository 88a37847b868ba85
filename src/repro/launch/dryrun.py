"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

THE FIRST TWO LINES must run before ANY other import (jax locks the device
count on first backend init) — brief MULTI-POD DRY-RUN §0.
"""
import os
os.environ["XLA_FLAGS"] = (
    os.environ.get("REPRO_DRYRUN_XLA_FLAGS",
                   "--xla_force_host_platform_device_count=512"))

# ruff: noqa: E402
import argparse
import dataclasses
import json
import time
import traceback

import jax

from repro import configs
from repro.core.policy import ArithmeticPolicy
from repro.launch import mesh as meshlib
from repro.launch import specs as specslib
from repro.launch import steps as stepslib
from repro.models.config import ModelConfig
from repro.optim import OptimizerConfig
from repro.parallel import sharding as sh
from repro.roofline import analyze, model_flops, parse_collectives
from repro.roofline.model import HW_V5E

CACHE_PATH = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                          "experiments", "dryrun_cache.json")


def _cache_key(arch, shape, mesh_tag, rules: sh.ShardingRules,
               policy_mode: str) -> str:
    return f"{arch}|{shape}|{mesh_tag}|{dataclasses.asdict(rules)}|" \
           f"{policy_mode}"


def _load_cache() -> dict:
    try:
        with open(CACHE_PATH) as f:
            return json.load(f)
    except Exception:
        return {}


def _save_cache(cache: dict) -> None:
    os.makedirs(os.path.dirname(os.path.abspath(CACHE_PATH)), exist_ok=True)
    tmp = CACHE_PATH + ".tmp"
    with open(tmp, "w") as f:
        json.dump(cache, f, indent=1, sort_keys=True)
    os.replace(tmp, CACHE_PATH)


# ---------------------------------------------------------------------------
# per-cell lowering
# ---------------------------------------------------------------------------


def cost_analysis_dict(compiled) -> dict:
    """`compiled.cost_analysis()` as a plain dict."""
    return dict(compiled.cost_analysis())


def lower_cell(cfg: ModelConfig, cell: configs.ShapeCell, mesh,
               rules: sh.ShardingRules = sh.ShardingRules(),
               policy: ArithmeticPolicy = ArithmeticPolicy(),
               donate: bool = True, unroll: int | bool = True):
    """Returns the lowered computation for one cell on one mesh.

    unroll=True fully unrolls the layer scan so `cost_analysis()` counts
    every layer (XLA counts a while-loop body once regardless of trip
    count — EXPERIMENTS.md §Dry-run methodology). Inner SSM chunk scans
    stay rolled; `inner_scan_correction` fixes their accounting.
    """
    if cell.kind != "train":
        # serving wants TP-resident weights: FSDP's per-layer all-gather
        # costs ICI + a gathered copy every step — §Perf H3. But only
        # when the TP-sharded bf16 residency actually fits: dbrx-132b at
        # 16.5 GiB/device must keep FSDP (H3 iteration 2).
        tp = mesh.shape.get("model", 1)
        resident_gib = cfg.param_count() * 2 / tp / 2**30
        if resident_gib <= 4.0:
            rules = dataclasses.replace(rules, fsdp=False)
    ins = specslib.input_specs(cfg, cell)
    pspecs = sh.param_specs(cfg, ins["params"], mesh, rules)
    psh = sh.named(mesh, pspecs)

    if cell.kind == "train":
        opt_specs = {"m": pspecs, "v": pspecs,
                     "step": sh.replicated_spec()}
        osh = sh.named(mesh, opt_specs)
        bsh = sh.named(mesh, sh.batch_specs(cfg, mesh, cell.global_batch))
        metrics_sh = sh.named(mesh, sh.replicated_spec())
        step = stepslib.make_train_step(
            cfg, OptimizerConfig(), policy, mesh=mesh, rules=rules,
            unroll=unroll)
        jitted = jax.jit(
            step,
            in_shardings=(psh, osh, bsh),
            out_shardings=(psh, osh, metrics_sh),
            donate_argnums=(0, 1) if donate else ())
        lowered = jitted.lower(ins["params"], ins["opt_state"], ins["batch"])

    elif cell.kind == "prefill":
        csh = sh.named(mesh, sh.cache_specs(cfg, mesh, cell.global_batch,
                                            rules))
        bspecs = sh.batch_specs(cfg, mesh, cell.global_batch)
        bspecs.pop("labels", None)
        bsh = sh.named(mesh, bspecs)
        bax = sh.batch_axes(mesh)
        lead = (bax if cell.global_batch >= meshlib.mesh_chips(mesh) //
                mesh.shape["model"] else None,)
        if cfg.modality == "audio":   # last-token logits: (B, C, V)
            lead = lead + (None,)
        logits_sh = sh.named(mesh, sh.logits_spec(lead))
        step = stepslib.make_prefill_step(cfg, policy, mesh=mesh,
                                          rules=rules, unroll=unroll)
        jitted = jax.jit(
            step,
            in_shardings=(psh, bsh, csh),
            out_shardings=(logits_sh, csh),
            donate_argnums=(2,) if donate else ())
        lowered = jitted.lower(ins["params"], ins["batch"], ins["cache"])

    else:  # decode
        csh = sh.named(mesh, sh.cache_specs(cfg, mesh, cell.global_batch,
                                            rules))
        bspecs = sh.batch_specs(cfg, mesh, cell.global_batch)
        tok_sh = sh.named(mesh, bspecs["tokens"])
        bax = sh.batch_axes(mesh)
        lead = (bax if cell.global_batch > 1 else None,)
        if cfg.modality == "audio":   # last-token logits: (B, C, V)
            lead = lead + (None,)
        logits_sh = sh.named(mesh, sh.logits_spec(lead))
        step = stepslib.make_decode_step(cfg, policy, mesh=mesh,
                                         rules=rules, unroll=unroll)
        jitted = jax.jit(
            step,
            in_shardings=(psh, tok_sh, csh),
            out_shardings=(logits_sh, csh),
            donate_argnums=(2,) if donate else ())
        lowered = jitted.lower(ins["params"], ins["tokens"], ins["cache"])

    return lowered


def inner_scan_correction(cfg: ModelConfig, cell: configs.ShapeCell,
                          chips: int) -> dict:
    """Analytic flop/byte correction for ROLLED inner chunk scans.

    rwkv6/mamba2 evaluate their recurrences as a lax.scan over sequence
    chunks; with the layer scan unrolled, each layer contributes its chunk
    body ONCE to cost_analysis while the real trip count is nc = ceil(S /
    chunk). We add (nc-1)/nc of the analytic per-layer chunk-scan work.
    Chunk bodies contain no collectives (token-local by construction), so
    only flops/bytes need correcting. Per-device values (divided by chips,
    matching cost_analysis units).
    """
    if cfg.family not in ("rwkv6", "zamba2") or cell.kind == "decode":
        return {"flops": 0.0, "bytes": 0.0}
    s = cell.seq_len
    b = cell.global_batch
    chunk = cfg.chunk_size
    nc = -(-s // chunk)
    if nc <= 1:
        return {"flops": 0.0, "bytes": 0.0}
    lch = chunk
    if cfg.family == "rwkv6":
        h = cfg.d_model // cfg.ssm_head_dim
        n = cfg.ssm_head_dim
        # per chunk: amat 2·B·H·L²·N (einsum) ×2 (score+apply)
        #          + bonus/inter/state ≈ 6·B·L·H·N·N
        per_chunk = (4.0 * b * h * lch * lch * n
                     + 6.0 * b * lch * h * n * n)
        layers = cfg.n_layers
    else:  # zamba2 / mamba2 SSD
        h, p, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
        # scores 2·B·L²·N + y 2·B·L²·H·P + inter/state ≈ 6·B·L·H·N·P
        per_chunk = (2.0 * b * lch * lch * n
                     + 2.0 * b * lch * lch * h * p
                     + 6.0 * b * lch * h * n * p)
        layers = cfg.n_layers
    mult = 3.0 if cell.kind == "train" else 1.0   # fwd+bwd
    extra_flops = per_chunk * (nc - 1) * layers * mult / chips
    # byte traffic of the chunk body ~ flops / 8 (einsum-dominated,
    # operands revisited once per contraction) — a coarse but bounded-
    # error estimate, recorded separately in the row
    return {"flops": extra_flops, "bytes": extra_flops / 8.0}


def _probe_layers(cfg: ModelConfig) -> tuple:
    """(L1, L2, unit) reduced layer counts for the cost probes. For zamba2
    the differencing unit is one GROUP (shared block + period mamba
    layers), so probes are whole multiples of the period."""
    if cfg.family == "zamba2":
        p = cfg.shared_attn_period
        return p, 2 * p, "group"
    return 2, 4, "layer"


def _probe_cost(cfg: ModelConfig, cell, mesh, rules, policy,
                n_layers: int):
    """Compile a reduced-L FULLY-UNROLLED probe; return (cost, coll)."""
    pcfg = dataclasses.replace(cfg, n_layers=n_layers)
    lowered = lower_cell(pcfg, cell, mesh, rules, policy, donate=True,
                         unroll=True)
    compiled = lowered.compile()
    cost = cost_analysis_dict(compiled)
    coll = parse_collectives(compiled.as_text())
    return cost, coll


def run_cell(arch: str, shape: str, mesh, mesh_tag: str,
             rules: sh.ShardingRules = sh.ShardingRules(),
             policy: ArithmeticPolicy = ArithmeticPolicy(),
             cache: dict | None = None, verbose: bool = True,
             force: bool = False, probes: bool = True) -> dict:
    """Lower + compile + analyze one cell.

    Accounting methodology (EXPERIMENTS.md §Dry-run):
      1. FULL-config ROLLED compile — the deliverable (proves the cell
         lowers+compiles on this mesh) + realistic peak memory.
      2. Two reduced-layer FULLY-UNROLLED probes (L1, L2); their cost
         difference is the exact per-layer flops/bytes/collectives
         (XLA counts a while body once regardless of trip count, so the
         rolled compile alone undercounts the layer loop L-fold).
      3. total = probe(L1) + (L_units - L1_units) · per_unit
         (+ analytic correction for rolled inner SSM chunk scans).
    """
    key = _cache_key(arch, shape, mesh_tag, rules, policy.mode)
    if cache is not None and key in cache and not force \
            and cache[key].get("status") == "ok":   # errors retry
        if verbose:
            print(f"[cached] {arch} × {shape} × {mesh_tag}")
        return cache[key]

    cfg = configs.get_config(arch)
    cell = configs.SHAPES[shape]
    t0 = time.time()
    row: dict = {"arch": arch, "shape": shape, "mesh": mesh_tag,
                 "status": "ok"}
    try:
        lowered = lower_cell(cfg, cell, mesh, rules, policy, unroll=1)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = cost_analysis_dict(compiled)
        coll = parse_collectives(compiled.as_text())
        chips = meshlib.mesh_chips(mesh)

        if probes:
            l1, l2, unit = _probe_layers(cfg)
            cost_a, coll_a = _probe_cost(cfg, cell, mesh, rules, policy, l1)
            cost_b, coll_b = _probe_cost(cfg, cell, mesh, rules, policy, l2)
            if unit == "group":
                p = cfg.shared_attn_period
                units1, units2 = l1 / p, l2 / p
                total_units = cfg.n_layers / p   # tail ~ fractional group
            else:
                units1, units2 = l1, l2
                total_units = cfg.n_layers
            du = units2 - units1

            def _extrap(a, b):
                per_unit = (b - a) / du
                return a + (total_units - units1) * per_unit

            cost = {
                "flops": _extrap(cost_a.get("flops", 0.0),
                                 cost_b.get("flops", 0.0)),
                "bytes accessed": _extrap(
                    cost_a.get("bytes accessed", 0.0),
                    cost_b.get("bytes accessed", 0.0)),
            }
            coll = dataclasses.replace(
                coll_a,
                raw_bytes=_extrap(coll_a.raw_bytes, coll_b.raw_bytes),
                wire_bytes=_extrap(coll_a.wire_bytes, coll_b.wire_bytes),
                ops={k: int(_extrap(coll_a.ops.get(k, 0),
                                    coll_b.ops.get(k, 0)))
                     for k in set(coll_a.ops) | set(coll_b.ops)},
                bytes_by_kind={
                    k: _extrap(coll_a.bytes_by_kind.get(k, 0),
                               coll_b.bytes_by_kind.get(k, 0))
                    for k in set(coll_a.bytes_by_kind)
                    | set(coll_b.bytes_by_kind)})
            row["probe"] = f"{unit}:{l1}/{l2}"

        corr = inner_scan_correction(cfg, cell, chips)
        cost["flops"] = cost.get("flops", 0.0) + corr["flops"]
        cost["bytes accessed"] = (cost.get("bytes accessed", 0.0)
                                  + corr["bytes"])
        n_tokens = (cell.global_batch if cell.kind == "decode"
                    else cell.global_batch * cell.seq_len)
        mflops = model_flops(cfg, n_tokens, cell.kind,
                             kv_len=cell.seq_len)
        peak_bytes = (mem.argument_size_in_bytes
                      + mem.temp_size_in_bytes
                      + mem.output_size_in_bytes
                      - mem.alias_size_in_bytes)
        rep = analyze(arch, shape, mesh_tag, chips, cost, coll, mflops,
                      peak_bytes)
        row.update(rep.row())
        row.update({
            "collectives": coll.summary(),
            "coll_ops": coll.ops,
            "arg_bytes": mem.argument_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "out_bytes": mem.output_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "t_lower_s": round(t_lower, 1),
            "t_compile_s": round(t_compile, 1),
            "fits_hbm": bool(peak_bytes < HW_V5E.hbm_gib * 2**30),
        })
        if verbose:
            print(f"[ok] {arch} × {shape} × {mesh_tag}: "
                  f"dom={row['dominant']} "
                  f"t=({row['t_compute_s']:.2e},{row['t_memory_s']:.2e},"
                  f"{row['t_collective_s']:.2e})s "
                  f"mem/dev={row['bytes_per_device_gib']:.2f}GiB "
                  f"lower={t_lower:.0f}s compile={t_compile:.0f}s")
    except Exception as e:
        row["status"] = "error"
        row["error"] = f"{type(e).__name__}: {e}"
        row["traceback"] = traceback.format_exc()[-2000:]
        if verbose:
            print(f"[ERR] {arch} × {shape} × {mesh_tag}: {row['error']}")

    if cache is not None:
        cache[key] = row
        _save_cache(cache)
    return row


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="one arch (default: all)")
    ap.add_argument("--shape", default=None, help="one shape (default: all)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true",
                    help="run single-pod AND multi-pod")
    ap.add_argument("--force", action="store_true", help="ignore cache")
    ap.add_argument("--policy", default="exact",
                    choices=["exact", "int8", "artemis_mxu"])
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--no-probes", action="store_true",
                    help="compile-success check only (multi-pod pass)")
    args = ap.parse_args()

    rules = sh.ShardingRules(fsdp=not args.no_fsdp,
                             seq_parallel=args.seq_parallel)
    policy = ArithmeticPolicy(mode=args.policy)
    cache = _load_cache()

    meshes = []
    if args.both_meshes or not args.multi_pod:
        meshes.append((meshlib.make_production_mesh(multi_pod=False),
                       "pod1_16x16"))
    if args.both_meshes or args.multi_pod:
        meshes.append((meshlib.make_production_mesh(multi_pod=True),
                       "pod2_2x16x16"))

    archs = [configs.canon(args.arch)] if args.arch else list(configs.ARCHS)
    n_ok = n_err = n_skip = 0
    for arch in archs:
        shapes = ([args.shape] if args.shape
                  else list(configs.SHAPES))
        runnable = set(configs.runnable_shapes(arch))
        for shape in shapes:
            if shape not in runnable:
                print(f"[skip] {arch} × {shape}: documented skip "
                      f"(DESIGN.md §Arch-applicability)")
                n_skip += 1
                continue
            for mesh, tag in meshes:
                # the multi-pod pass proves the `pod` axis shards; the
                # roofline table is single-pod only (brief §Dry-run 3)
                probes = not args.no_probes and tag.startswith("pod1")
                row = run_cell(arch, shape, mesh, tag, rules, policy,
                               cache=cache, force=args.force,
                               probes=probes)
                if row["status"] == "ok":
                    n_ok += 1
                else:
                    n_err += 1
    print(f"\ndry-run complete: {n_ok} ok, {n_err} errors, "
          f"{n_skip} documented skips")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
