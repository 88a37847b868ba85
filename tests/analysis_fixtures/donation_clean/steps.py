# virtual-path: src/repro/serve/fixture_donation_ok.py
"""Clean: donated buffers rebound before any further read.

The idiomatic serve-loop shapes the rule must NOT flag: same-atom
read-then-rebind (`pool = step(..., pool)`), rebinding a prefix
(`self.cache = ...` refreshes `self.cache.kv`), reads BEFORE the
donating call, and donation killed on every path of a branch.
"""
import functools

import jax


@functools.partial(jax.jit, donate_argnums=(1,))
def fused_update(params, pool):
    return pool


def rebind_same_atom(params, pool):
    pool = fused_update(params, pool)
    pool = fused_update(params, pool)
    return pool


def read_before_call(params, pool):
    peak = pool.nbytes
    pool = fused_update(params, pool)
    return pool, peak


def make_steps(cfg):
    def decode(params, tokens, pool):
        return tokens, pool

    return jax.jit(decode, donate_argnums=(2,))


def rebind_on_every_path(params, tokens, pool, greedy: bool):
    decode = make_steps(None)
    if greedy:
        logits, pool = decode(params, tokens, pool)
    else:
        logits, pool = decode(params, tokens, pool)
        logits = logits * 2.0
    return logits, pool.shape


class Cache:
    def __init__(self, step, kv):
        self._decode = jax.jit(step, donate_argnums=(2,))
        self.kv = kv

    def step(self, params, tokens, cache):
        logits, new_kv = self._decode(params, tokens, cache.kv)
        cache = cache.replace(kv=new_kv)
        return logits, cache.kv


class SpannedCache:
    """The donating call inside a `with` block, rebound in the same
    block: the `with` atom is its header only, so the call is seen
    once, where it stands."""

    def __init__(self, step, kv, span):
        self._decode = jax.jit(step, donate_argnums=(2,))
        self.kv = kv
        self.span = span

    def step(self, params, tokens):
        with self.span("dispatch"):
            logits, kv = self._decode(params, tokens, self.kv)
            self.kv = kv
        return logits, self.kv
