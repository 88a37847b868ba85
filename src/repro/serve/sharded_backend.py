"""Tensor-parallel paged serving — `ShardedPagedBackend`.

The multi-device sibling of `PagedKVBackend`, reached only through the
`serve.mesh` seam (`make_backend` routes paged families here when the
engine's `ServeMesh` has more than one shard). Everything host-side is
INHERITED UNCHANGED: the page allocator, block tables, PrefixIndex
admission matching, COW forks, eviction, and the scheduler's
`PagedBudget` all operate on LOGICAL page ids, so prefix sharing and
preemption are mesh-oblivious by construction — a shared logical page
is shared on every shard at once, and `PagedBudget`'s whole-page
charging already prices the mesh-wide allocation (each shard holds the
same logical pages, a head/sequence slice each). What this subclass
changes is exactly one thing: the jitted step factory (`_steps`).

Device layout (pure TP over one mesh axis, `parallel.sharding` rules
with FSDP off):

  parameters   committed via `mesh.param_shardings` in the base
               class's `_place_params` (attention heads / FFN columns
               over "model")
  KV pool      committed via `mesh.kv_pool_sharding`: partitioned on
               the KV-HEAD axis when `n_kv_heads % n_shards == 0`,
               replicated otherwise
  page tables  host-side numpy, never sharded

With the pool head-partitioned, the unmodified paged forward is
already tensor-parallel: jit sees committed operands plus pinned
`out_shardings` and GSPMD partitions the attention einsums along the
head axis — no custom collectives. When KV heads do NOT divide the TP
degree (small models, wide meshes), the pool stays replicated and the
step builders swap the paged forward's `attn_core` seam for the
ARTEMIS token dataflow expressed over the mesh: decode merges
per-shard partial attention with `parallel.split_kv_attention`'s
psum/pmax LSE reduction, and prefill chunks ring the gathered KV view
with `parallel.ring_attention` (paper Fig 5(b), banks -> devices).

Exactness: both cores compute the same masked softmax-attention as the
default `_attn_core` up to float reassociation; the conformance suite
(tests/test_serve_backend.py) pins a sharded drain token-identical to
the single-device `PagedKVBackend` reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.policy import ArithmeticPolicy
from repro.models.config import ModelConfig
from repro.parallel.ring_attention import ring_attention
from repro.parallel.split_kv import split_kv_attention
from repro.serve.backend import EngineConfig, PagedKVBackend
from repro.serve.mesh import (ServeMesh, kv_pool_sharding,
                              make_serve_mesh, replicated,
                              replicated_spec, seq_sharded_spec)
from repro.serve.obs import ShardStepEvent, Tracer
from repro.serve.paged_model import (
    make_paged_chunked_prefill,
    make_paged_decode,
)
from repro.serve.request import Request

__all__ = ["ShardedPagedBackend"]


def _dataflow_attn_core(smesh: ServeMesh):
    """An `attn_core` for `paged_model`'s pluggable seam that runs the
    token dataflow over the serve mesh. Used when the KV pool is
    replicated (KV heads don't divide the TP degree): parallelism
    comes from sharding the SEQUENCE axis of the gathered KV view.

    The gathered view's kv position IS its slot index t (page j of a
    block table covers positions [j*page, (j+1)*page)), and every
    valid query position >= its own written slots, so the plain causal
    mask q_pos >= kv_pos reproduces the default core's `t <=
    positions` masking — trash-page and padding slots all sit at
    t > position for every valid query.
    """
    mesh, ax = smesh.handle, smesh.axis
    # placement vocabulary comes from the mesh seam, not ad-hoc specs
    # (shard-spec-discipline): rep = replicated, seq = the gathered
    # view's sequence axis over the TP axis
    rep = replicated_spec(smesh)
    seq = seq_sharded_spec(smesh)

    def core(qg, kall, vall, positions, cfg: ModelConfig, policy):
        b, s, kvh, g, hd = qg.shape
        h = kvh * g
        smax = kall.shape[1]
        # merged head index = kv*g + j, so q head i reads kv head i//g
        # — the same grouping _repeat_kv applies to K/V
        q = qg.reshape(b, s, h, hd)
        kv_pos = jnp.broadcast_to(
            jnp.arange(smax, dtype=jnp.int32)[None], (b, smax))
        if s > 1:
            # prefill chunk: queries sequence-sharded, each device's KV
            # slice travels the ring past every query shard (its
            # positions ride along, so masking is exact on every hop)
            def ring(qc, kc, vc, qp, kp):
                return ring_attention(qc, kc, vc, axis_name=ax,
                                      causal=True, q_positions=qp,
                                      kv_positions=kp)
            ctx = jax.shard_map(
                ring, mesh=mesh,
                in_specs=(seq, seq, seq, seq, seq),
                out_specs=seq)(q, kall, vall, positions, kv_pos)
        else:
            # decode: one query per lane, replicated; each shard scores
            # its KV slice and one pmax + two psums merge the LSE stats
            def split(qc, kc, vc, qp, kp):
                return split_kv_attention(qc, kc, vc, axis_name=ax,
                                          q_positions=qp,
                                          kv_positions_local=kp)
            ctx = jax.shard_map(
                split, mesh=mesh,
                in_specs=(rep, seq, seq, rep, seq),
                out_specs=rep)(q, kall, vall, positions, kv_pos)
        return ctx.reshape(b, s, kvh, g, hd)

    return core


@functools.lru_cache(maxsize=None)
def _sharded_paged_steps(cfg: ModelConfig, policy: ArithmeticPolicy,
                         smesh: ServeMesh, chunk: int, smax: int):
    """Jitted mesh-sharded paged steps, cached per
    (cfg, policy, mesh, geometry) — same share-the-compile rationale
    as `backend._paged_steps`. Output shardings are pinned (logits
    replicated, KV pool per `paged_pool_spec`) so donation reuses the
    committed pool buffers; inputs inherit placement from the
    committed params/pool and the host-side batch arrays."""
    n = smesh.n_shards
    heads_tp = cfg.n_kv_heads % n == 0
    core = None
    if (not heads_tp and not cfg.attn_window
            and smax % n == 0 and chunk % n == 0):
        core = _dataflow_attn_core(smesh)
    repl = replicated(smesh)
    kv_ns = kv_pool_sharding(smesh, cfg)
    kv_sh = {"k": kv_ns, "v": kv_ns}
    prefill = jax.jit(
        make_paged_chunked_prefill(cfg, policy, attn_core=core),
        donate_argnums=(2,), out_shardings=(repl, kv_sh))
    decode = jax.jit(
        make_paged_decode(cfg, policy, attn_core=core),
        donate_argnums=(2,), out_shardings=(repl, kv_sh))
    return prefill, decode


class ShardedPagedBackend(PagedKVBackend):
    """Tensor-parallel paged KV backend (see module docstring).

    Inherits the whole `SequenceBackend` protocol implementation from
    `PagedKVBackend` — admission, sharing, COW, funding, release, and
    invariants are logical-page operations that never see the mesh.
    Overrides: `_steps` (mesh-sharded jitted forwards) and the two
    execution entry points, which additionally account per-shard work
    (`backend/shard_*` registry counters + one `ShardStepEvent` per
    shard per forward for the Chrome trace's shard tracks)."""

    families = ("dense", "moe")

    def __init__(self, cfg: ModelConfig, ecfg: EngineConfig,
                 policy: ArithmeticPolicy, params, obs: Tracer, clock,
                 mesh: ServeMesh | None = None):
        mesh = mesh if mesh is not None \
            else make_serve_mesh(ecfg.mesh_shards)
        if mesh.is_single:
            raise ValueError(
                "ShardedPagedBackend needs a multi-shard ServeMesh; "
                "single-device serving uses PagedKVBackend "
                "(mesh_shards=1)")
        if ecfg.attn_impl != "gather":
            raise ValueError(
                f"attn_impl={ecfg.attn_impl!r} has no multi-device "
                f"path (the fused paged kernel is single-device; the "
                f"mesh cores own the sharded gather view) — set "
                f"attn_impl='gather' or mesh_shards=1")
        super().__init__(cfg, ecfg, policy, params, obs, clock,
                         mesh=mesh)
        reg = obs.registry
        reg.set_gauge("backend/shard_count", mesh.n_shards)
        reg.set_gauge(
            "backend/shard_kv_heads",
            cfg.n_kv_heads // mesh.n_shards
            if cfg.n_kv_heads % mesh.n_shards == 0 else cfg.n_kv_heads)

    def _steps(self, policy: ArithmeticPolicy):
        smax = self.ecfg.max_pages_per_seq * self.ecfg.page_size
        return _sharded_paged_steps(self.cfg, policy, self.mesh,
                                    self.ecfg.prefill_chunk, smax)

    # -- execution (adds per-shard accounting) ------------------------------

    def prefill_step(self, chunks: list[tuple[Request, int]]):
        logits = super().prefill_step(chunks)
        self._note_shard_step("prefill", sum(n for _, n in chunks))
        return logits

    def decode_step(self, reqs: list[Request]):
        logits = super().decode_step(reqs)
        self._note_shard_step("decode", len(reqs))
        return logits

    def _note_shard_step(self, phase: str, n_tokens: int) -> None:
        reg = self._obs.registry
        reg.inc("backend/shard_steps")
        reg.inc("backend/shard_tokens", n_tokens)
        now = self._now()
        for shard in range(self.mesh.n_shards):
            self._obs.emit(ShardStepEvent(
                ts=now, shard=shard, n_shards=self.mesh.n_shards,
                phase=phase, n_tokens=n_tokens))

    def snapshot_metrics(self) -> dict:
        m = super().snapshot_metrics()
        reg = self._obs.registry
        m["n_shards"] = self.mesh.n_shards
        m["shard_steps"] = int(reg.count("backend/shard_steps"))
        return m
