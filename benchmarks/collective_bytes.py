"""Beyond-paper: token vs layer dataflow measured in REAL lowered HLO.

The paper compares its token dataflow to the layer dataflow inside its
simulator (Fig 8). Here we make the same comparison on the TPU mapping:
ring attention (shard_map + ppermute — the token dataflow) vs all-gather
attention (the layer dataflow), lowered on 8 host devices, with ICI bytes
parsed from the compiled HLO. The paper's 'binary before the bus'
compression insight is measured as the bf16-vs-f32 K/V transfer delta.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax.sharding import AxisType
from jax.sharding import PartitionSpec as P

from repro.parallel.ring_attention import (
    layer_dataflow_attention,
    ring_attention,
)
from repro.roofline import parse_collectives


N_SHARDS = 8


def _lower(fn, mesh, shapes, dtype):
    specs = (P(None, "sp"),) * 3
    sm = jax.shard_map(fn, mesh=mesh, in_specs=specs, out_specs=P(None, "sp"))
    args = [jax.ShapeDtypeStruct(s, dtype) for s in shapes]
    return jax.jit(sm).lower(*args).compile()


def _ring(q, k, v):
    # the barrier keeps the K/V carry in its INPUT dtype on the wire —
    # without it XLA rewrites the scan carry to f32 (every use converts),
    # silently widening the ppermute payload
    k, v = jax.lax.optimization_barrier((k, v))
    return ring_attention(q, k, v, axis_name="sp")


def run() -> list[dict]:
    if jax.device_count() < 8:
        print("needs 8 devices — set "
              "XLA_FLAGS=--xla_force_host_platform_device_count=8")
        return []
    mesh = jax.make_mesh((8,), ("sp",), axis_types=(AxisType.Auto,))
    rows = []
    b, s, h, d = 2, 8192, 16, 128
    shapes = [(b, s, h, d)] * 3
    print(f"attention B={b} S={s} H={h} D={d} over {N_SHARDS}-way "
          f"sequence shard")
    print(f"{'dataflow':28s} {'ICI bytes/dev':>14s} {'ops':>24s}")
    for name, fn, dtype, loop_steps in [
        # ring permutes sit in a scan body: HLO counts them ONCE, the
        # ring executes them (n-1) times -> explicit correction factor
        ("token (ring, bf16 K/V)", _ring, jnp.bfloat16, N_SHARDS - 1),
        ("token (ring, f32 K/V)", _ring, jnp.float32, N_SHARDS - 1),
        ("layer (all-gather, bf16)",
         lambda q, k, v: layer_dataflow_attention(q, k, v,
                                                  axis_name="sp"),
         jnp.bfloat16, 1),
        ("layer (all-gather, f32)",
         lambda q, k, v: layer_dataflow_attention(q, k, v,
                                                  axis_name="sp"),
         jnp.float32, 1),
    ]:
        compiled = _lower(fn, mesh, shapes, dtype)
        st = parse_collectives(compiled.as_text())
        total = st.wire_bytes * loop_steps   # ring-weighted wire bytes
        print(f"{name:28s} {total/1e6:12.1f}MB {st.summary():>24s}"
              + (f" x{loop_steps} steps" if loop_steps > 1 else ""))
        rows.append({"dataflow": name, "ici_wire_bytes": total,
                     "ops": st.ops})
    if len(rows) == 4:
        r = rows[2]["ici_wire_bytes"] / max(rows[0]["ici_wire_bytes"], 1)
        print(f"\nring vs all-gather WIRE bytes: {r:.2f}x — equal totals "
              f"(both move the full K/V once past every device); the "
              f"token dataflow's win is OVERLAP: per-step permutes "
              f"pipeline behind score blocks while the bulk gather "
              f"serializes up front — the paper's Fig 6 argument.")
        print("bf16-vs-f32 wire: NOT measurable on the CPU backend "
              "(XLA:CPU legalizes bf16 carries/permutes to f32 — both "
              "rows show f32 payloads); on TPU the permute ships bf16, "
              "halving wire bytes (the paper's 'binary before the bus').")
    return rows


if __name__ == "__main__":
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")
    run()
