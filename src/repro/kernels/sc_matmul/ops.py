"""jit'd public wrapper around the sc_matmul Pallas kernel.

Owns quantization (per ArithmeticPolicy), block padding and
dequantization; interpret mode resolves inside `sc_matmul_quantized`.
`sc_linear` is the drop-in matmul used by repro.models when a policy
routes a layer through the kernel path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import quantization as q
from repro.core.policy import ArithmeticPolicy
from repro.core.quantization import SC_LEVELS
from repro.kernels.sc_matmul.sc_matmul import sc_matmul_quantized


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def sc_matmul(
    a: jax.Array,
    b: jax.Array,
    policy: ArithmeticPolicy = ArithmeticPolicy(mode="artemis"),
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """ARTEMIS matmul through the Pallas kernel. a: (M, K), b: (K, N) float.

    Semantically equivalent to repro.core.artemis_matmul for 2-D operands
    (modulo sigma_analog, which is emulation-only) — pinned by
    tests/test_kernels.py.
    """
    if bk is None:
        bk = 160 if policy.mode == "artemis" else 256
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    m, k = a.shape
    _, n = b.shape
    sa = q.quant_scale(a, 8, policy.act_quant_axis)
    sb = q.quant_scale(b, 8, policy.weight_quant_axis)
    aq = _pad_to(_pad_to(q.quantize(a, sa), 0, bm), 1, bk)
    bq = _pad_to(_pad_to(q.quantize(b, sb), 0, bk), 1, bn)
    out = sc_matmul_quantized(
        aq, bq, mode=policy.mode, readout_bits=policy.readout_bits,
        rbar=policy.rbar, bm=bm, bn=bn, bk=bk, interpret=interpret,
    )[:m, :n]
    if policy.mode == "int8":
        out = out.astype(jnp.float32) * sa * sb
    else:
        out = out.astype(jnp.float32) * SC_LEVELS * sa * sb
    if policy.ste:
        exact = jnp.matmul(a, b)
        out = exact + jax.lax.stop_gradient(out - exact)
    return out
