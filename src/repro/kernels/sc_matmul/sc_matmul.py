"""Pallas TPU kernel for the ARTEMIS stochastic-analog MAC (paper §III.A).

TPU adaptation of the in-DRAM pipeline (DESIGN.md §2):

  * the DRAM bit-line AND over 128-bit TCU streams becomes the closed-form
    floor(m_a*m_b/128) evaluated on the VPU over VMEM-resident blocks;
  * the MOMCAP group-of-20 analog accumulation + quantizing A_to_B readout
    happens inside the K-loop, per group, exactly as the tiles do it;
  * the NSC partial-sum reduction is the revisited f32 output block
    accumulated across the K grid axis (K is the innermost grid dimension,
    the standard TPU matmul accumulation pattern);
  * sign handling mirrors §III.C.1: positive and negative product
    magnitudes are accumulated separately and subtracted after readout.

Three modes:
  artemis      faithful pipeline (VPU element work, O(bm*bk*bn) per block)
  int8         plain int8 MXU matmul, int32 accumulation (Q(8-bit) ladder)
  artemis_mxu  beyond-paper fast path: value-dot minus rbar * sign-dot —
               two MXU matmuls approximating the floor-truncation bias
               (error analysis in benchmarks/table5_calibration.py)

Block shapes: bm/bn default 128 (MXU/VREG lane alignment); bk must be a
multiple of the MOMCAP depth (20) in artemis mode so analog groups never
straddle VMEM blocks — default 160 (8 groups; sublane-aligned for f32/int8).
Operands arrive pre-quantized int8 (ops.py owns scales); outputs are in "SC
product units" (x128 smaller than integer dot units for artemis modes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.flash_attention.flash_attention import _interpret_default

ACC_DEPTH = 20  # MOMCAP consecutive accumulations (paper §III.A.2)


def _readout(x: jax.Array, readout_bits: int | None) -> jax.Array:
    """Inline A_to_B quantizing readout (analog.readout_quantize, no noise)."""
    if readout_bits is None:
        return x
    levels = float(2**readout_bits - 1)
    full_scale = float(ACC_DEPTH * 127)
    delta = full_scale / levels
    return jnp.clip(jnp.round(x * (1.0 / delta)), 0.0, levels) * delta


def _sc_matmul_kernel(a_ref, b_ref, o_ref, *, nk: int, mode: str,
                      readout_bits: int | None, rbar: float):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a = a_ref[...].astype(jnp.int32)   # (bm, bk) signed
    b = b_ref[...].astype(jnp.int32)   # (bk, bn) signed

    if mode == "int8":
        # exact int8 dot; int32 accumulation on the MXU
        o_ref[...] += jax.lax.dot_general(
            a_ref[...], b_ref[...],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        return

    if mode == "artemis_mxu":
        value = jax.lax.dot_general(
            a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        ).astype(jnp.float32)
        sgn_a = jnp.sign(a).astype(jnp.int8)
        sgn_b = jnp.sign(b).astype(jnp.int8)
        signs = jax.lax.dot_general(
            sgn_a, sgn_b, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        ).astype(jnp.float32)
        o_ref[...] += (value - rbar * signs) * (1.0 / 128.0)
        return

    assert mode == "artemis", mode
    bk = a.shape[1]
    assert bk % ACC_DEPTH == 0, "bk must be a multiple of the MOMCAP depth"
    ma = jnp.abs(a).astype(jnp.float32)
    mb = jnp.abs(b).astype(jnp.float32)
    sa = jnp.sign(a).astype(jnp.float32)
    sb = jnp.sign(b).astype(jnp.float32)

    acc = jnp.zeros_like(o_ref, dtype=jnp.float32)
    for g in range(bk // ACC_DEPTH):
        sl = slice(g * ACC_DEPTH, (g + 1) * ACC_DEPTH)
        # one MOMCAP group: (bm, 20, bn) floor products on the VPU
        p = jnp.floor(ma[:, sl, None] * mb[None, sl, :] * (1.0 / 128.0))
        s = sa[:, sl, None] * sb[None, sl, :]
        pos = jnp.sum(jnp.where(s > 0, p, 0.0), axis=1)
        neg = jnp.sum(jnp.where(s < 0, p, 0.0), axis=1)
        acc += _readout(pos, readout_bits) - _readout(neg, readout_bits)
    o_ref[...] += acc


@functools.partial(
    jax.jit,
    static_argnames=("mode", "readout_bits", "rbar", "bm", "bn", "bk",
                     "interpret"),
)
def sc_matmul_quantized(
    aq: jax.Array,
    bq: jax.Array,
    *,
    mode: str = "artemis",
    readout_bits: int | None = 8,
    rbar: float = 63.5,
    bm: int = 128,
    bn: int = 128,
    bk: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Blocked ARTEMIS matmul over pre-quantized int8 operands.

    aq: (M, K) int8, bq: (K, N) int8; M, N, K must be multiples of the block
    shapes (ops.py pads).  Returns (M, N): int32 for mode="int8" (integer
    dot units), float32 in SC product units otherwise.
    `interpret=None` resolves via the shared `_interpret_default()`
    platform probe: compiled on TPU, interpreted elsewhere.
    """
    if interpret is None:
        interpret = _interpret_default()
    if bk is None:
        bk = 160 if mode == "artemis" else 256
    m, k = aq.shape
    k2, n = bq.shape
    assert k == k2, (aq.shape, bq.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    nk = k // bk
    out_dtype = jnp.int32 if mode == "int8" else jnp.float32

    kernel = functools.partial(
        _sc_matmul_kernel, nk=nk, mode=mode, readout_bits=readout_bits,
        rbar=rbar,
    )
    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((bk, bn), lambda mi, ni, ki: (ki, ni)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        interpret=interpret,
    )(aq, bq)
