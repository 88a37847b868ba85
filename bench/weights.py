"""Random weights from the seed, made by the benchmark and not the program.

One jitted call makes every leaf on the device, in the type it is
served in, with the shardings the program places its parameters with
(so on four chips no device ever holds the whole model).  The pytree
is the layout the engine takes, as the architecture file's
`leaf_specs` lists it (`embed`, stacked `layers`, `final_norm`,
`head`); the plain reference reads the same arrays by the same names,
as the published equations use them.

Scales: every matmul weight N(0, 1/d_in), the embedding N(0, 1), and
every RMSNorm scale 1 + N(0, 0.1^2), so that a norm the program drops
or misplaces changes the logits.
"""
from __future__ import annotations

import numpy as np

NORM_NOISE = 0.1


def seed_key(seed: int):
    """A JAX key from any whole-number seed (JAX's own PRNGKey keeps
    only the low 32 bits, so two seeds 2**32 apart would collide)."""
    import jax
    hi, lo = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(lo)), int(hi))


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, x in flat.items():
        node = out
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = x
    return out


def _init_fn(specs: dict, dtype):
    import jax
    import jax.numpy as jnp

    def init(key):
        keys = jax.random.split(key, len(specs))
        flat = {}
        for k, (path, (shape, kind, std)) in zip(keys, specs.items()):
            z = jax.random.normal(k, shape, jnp.float32)
            x = 1.0 + NORM_NOISE * z if kind == "norm" else std * z
            flat[path] = x.astype(dtype)
        return _nest(flat)

    return init


def shapes(arch, conf: dict):
    """ShapeDtypeStructs of the parameter pytree, as served."""
    import jax
    import jax.numpy as jnp
    dtype = jnp.dtype(conf["serve"]["param_dtype"])
    return jax.eval_shape(_init_fn(arch.leaf_specs(conf), dtype),
                          jax.random.PRNGKey(0))


def make(arch, conf: dict, seed: int, shardings=None):
    """The parameter pytree of architecture `arch` (`arch/<model_type>.py`)
    on the device(s), from `seed`."""
    import jax
    import jax.numpy as jnp
    dtype = jnp.dtype(conf["serve"]["param_dtype"])
    return jax.jit(_init_fn(arch.leaf_specs(conf), dtype),
                   out_shardings=shardings)(seed_key(seed))
