"""Per-request stochastic sampling with batch-invariant RNG lanes.

One jitted, fixed-shape batched sampler serves every token the engine
ever samples — decode rounds AND prefill-completion first tokens, over
BOTH sequence backends — at the engine's one compiled
`(max_batch, vocab)` shape. Per lane it applies the standard chain

    temperature scaling -> top-k mask -> top-p (nucleus) mask
    -> Gumbel-max draw

and a `temperature == 0` lane short-circuits to plain argmax,
bit-identical to `launch.steps.greedy_sample` (the greedy
token-identity suites are the anchor this rides on).

## The RNG-lane determinism contract

The key for a draw is a pure function of exactly two values:

    key = fold_in(PRNGKey(request.seed), request_local_position)

where `request_local_position` is how many tokens the request has
generated so far (`len(req.generated)` at sampling time). Nothing else
ever enters the key — not the engine step count, not the batch lane,
not which other requests share the step, not whether the token comes
from a decode round or a prefill-completion chunk. Consequences, all
pinned by tests/test_sampling.py + tests/test_serve_backend.py:

  * batch invariance — a request samples the same tokens alone or
    packed with any other requests, under any chunk size;
  * preemption replay — recompute-style preemption re-prefills the
    effective prompt and re-samples position `len(generated)` with the
    SAME key it would have used un-preempted, so recovery is
    bit-identical (given the backends' per-lane logits are themselves
    batch-invariant — a contract `serve.backend` records);
  * scheduler independence — cost vs fcfs composition cannot change
    any request's sampled stream.

Each lane draws its own Gumbel noise from its own key (vmap of
per-lane draws == each lane drawn alone), so garbage rows for idle
lanes cannot perturb live ones and there is no shared RNG stream to
race on.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# Metrics-registry keys the engine publishes sampler activity under
# (repro.serve.obs.MetricsRegistry): one counter per token drawn on a
# non-greedy RNG lane, one per greedy argmax token. Defined here so the
# sampler's observable surface lives next to the sampling contract.
N_SAMPLED_KEY = "sampler/n_sampled_tokens"
N_GREEDY_KEY = "sampler/n_greedy_tokens"


def lane_key(seed, pos):
    """RNG key for a request's `pos`-th sampled token: a pure function
    of (request seed, request-local position) and nothing else — see
    the module docstring for why that is the whole determinism story."""
    return jax.random.fold_in(jax.random.PRNGKey(seed), pos)


def _sample_lane(logits, temperature, top_k, top_p, seed, pos):
    """One lane: temperature -> top-k -> top-p -> Gumbel-max. Greedy
    (temperature <= 0) reduces to argmax over the RAW logits, which is
    exactly `greedy_sample`."""
    v = logits.shape[-1]
    greedy = temperature <= 0.0
    # greedy lanes still trace the sampled branch; give them a safe
    # divisor so no inf/nan can leak out of operations XLA may not
    # short-circuit
    t = jnp.where(greedy, jnp.ones((), jnp.float32),
                  temperature.astype(jnp.float32))
    scaled = logits.astype(jnp.float32) / t
    # top-k: keep the k largest scaled logits (0 = keep all)
    keff = jnp.clip(jnp.where(top_k > 0, top_k, v) - 1, 0, v - 1)
    kth = jnp.sort(scaled)[::-1][keff]
    keep = scaled >= kth
    # top-p on the top-k-masked distribution: keep the minimal
    # descending-prob set whose mass reaches top_p (the top token
    # always survives: its exclusive cumulative mass is 0)
    probs = jax.nn.softmax(jnp.where(keep, scaled, -jnp.inf))
    sp = jnp.sort(probs)[::-1]
    exclusive = jnp.cumsum(sp) - sp
    cutoff = jnp.min(jnp.where(exclusive < top_p, sp, jnp.inf))
    keep = keep & (probs >= cutoff)
    g = jax.random.gumbel(lane_key(seed, pos), (v,), jnp.float32)
    sampled = jnp.argmax(jnp.where(keep, scaled, -jnp.inf) + g)
    return jnp.where(greedy, jnp.argmax(logits), sampled).astype(jnp.int32)


@jax.jit
def sample_tokens(logits, temperature, top_k, top_p, seed, pos):
    """Batched sampler: `(B, V)` logits + per-lane `(B,)` params ->
    `(B,)` i32 tokens. The engine calls this at its fixed
    `(max_batch, vocab)` shape, so it compiles once per geometry; rows
    the caller does not use (idle lanes, non-completing chunks) cost
    nothing but flops — every lane's draw is independent. Its device
    operations run under the named scope `sampler`."""
    with jax.named_scope("sampler"):
        return jax.vmap(_sample_lane)(
            jnp.asarray(logits), jnp.asarray(temperature, jnp.float32),
            jnp.asarray(top_k, jnp.int32), jnp.asarray(top_p, jnp.float32),
            jnp.asarray(seed, jnp.uint32), jnp.asarray(pos, jnp.int32))
