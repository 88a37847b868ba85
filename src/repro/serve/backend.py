"""Backend-agnostic sequence-memory API for the serving engine.

The engine, scheduler, and request lifecycle never touch pages, block
tables, prefix hashes, copy-on-write, or state slots directly: they
talk to a `SequenceBackend` through the narrow protocol below, and the
backend owns every family-specific device structure. Two backends
implement it:

  PagedKVBackend   — attention families (dense / moe). K/V grows with
                     the sequence, so memory is a pool of fixed-size
                     token pages: refcounting allocator, PrefixIndex
                     admission matching, copy-on-write forks, trash
                     page 0 for jit-stable idle lanes (a mechanical
                     extraction of the pre-backend engine, behavior
                     pinned token-identical by tests/test_serve.py).
  StateSlotBackend — recurrent families (rwkv6 / zamba2). Per-sequence
                     state is FIXED-SIZE (wkv matrices / SSD + conv
                     states / a bounded attention ring), so memory is a
                     pool of whole state slots: a request holds exactly
                     one slot from admission to release, decode can
                     never run out mid-flight, and preemption recovers
                     by recompute (the slot is dropped and the
                     effective prompt re-prefills into a fresh one).

## Protocol contract

Engine-owned request fields: `state`, `lane`, `generated`, `seq_len`,
`prefill_pos`. Backend-owned: `req.mem`, an opaque object the engine
must never inspect; it is created by `admit()` and destroyed by
`release()` (which must be idempotent — releasing a request without
`mem` is a no-op).

  validate(prompt_len, max_new_tokens)
      Raise ValueError if the request can never be served (exceeds the
      block table / pool / max_seq_len). Called at submit().
  admit(req) -> AdmitPlan
      Attach fresh sequence memory to an already-laned request. May
      start `req.prefill_pos`/`req.seq_len` past 0 when a leading run
      of the effective prompt is already resident (the prefix-share
      discount, reported as AdmitPlan.shared_tokens). Must not evict.
  probe_shared(req) -> int
      Read-only admission probe: leading effective-prompt tokens
      already resident in shareable memory. No side effects; safe to
      call every scheduling round (backends may memoize).
  budget() -> BudgetProbe
      A planning snapshot of free capacity for ONE scheduler decide():
      the scheduler charges candidate chunks/admissions against it
      without touching real allocator state.
  can_fund(req, n_tokens) -> bool
      Read-only: could the backend absorb n_tokens more tokens for
      `req` from FREE capacity, with no eviction?
  prepare_decode(reqs, evict)
      Make every listed decode request writable for one more token
      (grow a page at a boundary, COW-fork a shared page, ...).
      `reqs` arrive oldest-admission first; under memory pressure the
      backend calls `evict(exclude=..., newer_than=...) -> bool` and
      the ENGINE picks + preempts the newest victim (preemption policy
      stays engine-owned). Skip requests whose state changed mid-loop.
  fund_prefill(req, want, evict) -> int
      Reserve memory so `req` can absorb up to `want` more effective-
      prompt tokens; returns the granted count (possibly 0). May evict
      only requests admitted after `req` (via `evict(newer_than=req)`).
  prefill_step(chunks) -> logits (max_batch, C, V)
      Execute one composed chunk batch ([(req, n)] with n > 0, already
      funded) against device state, ADVANCE each request's
      `prefill_pos`/`seq_len`, and return per-position logits (row i =
      chunks[i]; the engine samples row i at position n-1 when a chunk
      completes its prompt). Device state is backend-internal — the
      engine never sees it.
  decode_step(reqs) -> logits (max_batch, V)
      One token for every request (row = req.lane; idle lanes are
      backend-masked). The engine samples, appends, and bumps
      `seq_len` — the backend must have made the write target safe in
      prepare_decode().

      BATCH-INVARIANCE CONSTRAINT: a request's per-lane logits from
      decode_step AND from prefill_step's last valid position must
      depend only on the request's own token history — bit-identical
      regardless of batch composition, lane placement, chunk
      boundaries, and recompute-after-preemption. The engine samples
      every emitted token through `repro.serve.sampler`, whose
      per-request RNG lanes make sampled streams deterministic ONLY
      under this contract (greedy argmax tolerates logit noise;
      sampled decode does not). Both existing backends satisfy it by
      construction (per-lane independent forwards at fixed compiled
      shapes); the sampled conformance suite in
      tests/test_serve_backend.py pins it for any future backend.
  release(req)
      Drop all of req's sequence memory (refcounts for shared pages, a
      whole slot, ...) and clear `req.mem`. Called on preemption and
      completion.
  utilization() -> (physical, logical)
      Fractions of the memory pool in use, sampled per executed step;
      logical >= physical when memory is shared across requests.
  snapshot_metrics() -> dict
      Backend-specific counters merged into engine.metrics().
  check_invariants()
      Assert internal consistency (no aliasing/leaks, indexed memory
      resident, ...); the conformance suite calls it after every step.

## Event-emission contract (observability)

`make_backend` hands every backend the engine's `repro.serve.obs`
Tracer (`obs`) and virtual-clock read (`clock() -> float`). A backend
participates in observability through exactly three channels:

  events — memory-lifecycle transitions the backend alone can see are
      emitted as TYPED obs events stamped with `clock()`, never as raw
      tuples: today `ShareEvent` (admission matched a resident prefix)
      and `CowForkEvent` (a write forked a co-owned page). Emit
      through `obs.emit(...)`; the Tracer decides whether the event is
      retained (level="trace") or only counted (level="metrics") — the
      backend must not branch on the level itself. Events must be
      emitted AT the transition (inside admit()/fund_prefill()/
      prepare_decode()), so span assembly sees them between the
      request's admit and finish/preempt markers, and their
      timestamps must be the current clock() — never a remembered one.
  registry — monotone counters go into `obs.registry` under the
      "backend/" prefix (the ONE namespace allowed to differ between
      backends; every other registry namespace must be
      backend-independent — the conformance suite pins this).
      `snapshot_metrics()` reads the registry back so its dict stays
      derivable from the registry alone. Every `prefill_step` counts
      `backend/prefill_positions` (max_batch x prefill_chunk) and
      `backend/prefill_tokens` (the chunks' valid tokens).
  spans — each forward opens `obs.span("serve.pack", phase=...)`
      around its host arrays and their transfers and
      `obs.span("serve.dispatch", phase=...)` around the jitted call,
      with phase "decode" or "prefill"; they are host spans on the
      profiler's clock while `obs.profiling` is set, and no-ops
      otherwise.

A new backend that has nothing to share or fork simply emits nothing —
span assembly and the trace exporter treat backend events as optional
annotations, never required structure.

Adding a third backend (e.g. hybrid paged+slot for models mixing
attention and SSM layers) means implementing this class and routing
its families in `make_backend` — engine and scheduler need no changes.

## Static enforcement (`repro.analysis`)

The machine-checkable half of these contracts is enforced by the AST
checker (`PYTHONPATH=src python -m repro.analysis`, CI job `analyze`):
`backend-protocol` pins implementer signatures against the abstract
protocol below; `registry-namespace` pins the "backend/"-only registry
rule above (and the four serve namespaces everywhere else);
`wall-clock-in-serve` / `rng-key-discipline` / `host-sync-in-jit` /
`retrace-hazard` guard the virtual clock, the sampler's RNG-lane
derivation, and the compile-once jit design this module's
`_paged_steps`/`_slot_steps` factories implement. See the "Static
analysis" section of README.md for rules and suppression syntax.
"""
from __future__ import annotations

import abc
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.policy import ArithmeticPolicy
from repro.models.config import ModelConfig
from repro.serve.mesh import (
    ServeMesh,
    kv_pool_sharding,
    make_serve_mesh,
    param_shardings,
)
from repro.serve.obs import CowForkEvent, ShareEvent, Tracer
from repro.serve.paged_cache import (
    TRASH_PAGE,
    PageAllocator,
    PrefixIndex,
    cow_copy_page,
    init_paged_cache,
)
from repro.serve.paged_model import (
    make_fused_paged_core,
    make_paged_chunked_prefill,
    make_paged_decode,
)
from repro.serve.request import Request, RequestState
from repro.serve.state_model import (
    TRASH_SLOT,
    init_slot_pool,
    make_slot_decode,
    make_slot_prefill_chunk,
    reset_slot,
)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Serve configuration: engine-level knobs (batch lanes, chunk
    size, scheduler policy) plus the memory-pool geometry each backend
    interprets — paged backends read the page_* fields, state-slot
    backends read n_slots/max_seq_len."""
    page_size: int = 8
    n_pages: int = 128             # includes the reserved trash page 0
    max_batch: int = 4             # batch lanes (compiled batch width)
    max_pages_per_seq: int = 16    # block-table width
    prefill_chunk: int = 32        # prompt tokens per prefill chunk
    cache_dtype: str = "float32"
    scheduler: str = "cost"        # "cost" | "fcfs"
    scheme: str = "token_PP"       # hwsim dataflow used for pricing
    prefix_sharing: bool = True    # COW page sharing for common prefixes
    n_slots: int = 0               # state-slot pool size incl. trash
    #                                slot 0 (0 = auto: max_batch + 1)
    max_seq_len: int = 512         # per-sequence prompt+gen cap for
    #                                state-slot backends (sizes zamba2's
    #                                attention ring)
    observability: str = "metrics"   # "metrics" = counters/histograms
    #                                  only, no per-event retention;
    #                                  "trace" = keep the full typed
    #                                  event log for span assembly and
    #                                  Chrome trace export
    mesh_shards: int = 1             # tensor-parallel degree: 1 = the
    #                                  single-device strict no-op; > 1
    #                                  routes paged families through
    #                                  ShardedPagedBackend on a
    #                                  serve-mesh (serve/mesh.py)
    attn_impl: str = "gather"        # paged attention core: "gather"
    #                                  materializes the block table into
    #                                  a contiguous KV view (reference
    #                                  path); "fused" walks the block
    #                                  table inside the Pallas paged-
    #                                  attention kernel (exact-policy,
    #                                  single-device; interpreted off-TPU)

    def __post_init__(self):
        if self.page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {self.page_size}")
        if self.n_pages < 2:
            raise ValueError(
                f"n_pages must be >= 2 (page 0 is the reserved trash "
                f"page), got {self.n_pages}")
        if self.max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {self.max_batch}")
        if self.max_pages_per_seq < 1:
            raise ValueError(
                f"max_pages_per_seq must be >= 1, got "
                f"{self.max_pages_per_seq}")
        if self.prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {self.prefill_chunk}")
        if self.scheduler not in ("cost", "fcfs"):
            raise ValueError(f"unknown scheduler {self.scheduler!r}")
        if self.n_slots != 0 and self.n_slots < 2:
            raise ValueError(
                f"n_slots must be 0 (auto) or >= 2 (slot 0 is the "
                f"reserved trash slot), got {self.n_slots}")
        if self.max_seq_len < 2:
            raise ValueError(
                f"max_seq_len must be >= 2, got {self.max_seq_len}")
        if self.observability not in Tracer.LEVELS:
            raise ValueError(
                f"observability must be one of {Tracer.LEVELS}, got "
                f"{self.observability!r}")
        if self.mesh_shards < 1:
            raise ValueError(
                f"mesh_shards must be >= 1, got {self.mesh_shards}")
        if self.attn_impl not in ("gather", "fused"):
            raise ValueError(
                f"attn_impl must be 'gather' or 'fused', got "
                f"{self.attn_impl!r}")
        jnp.dtype(self.cache_dtype)   # raises on nonsense dtypes


@dataclasses.dataclass(frozen=True)
class AdmitPlan:
    """What admission bought: `shared_tokens` effective-prompt tokens
    were already resident (the prefix-share discount — 0 for backends
    that cannot share sequence memory)."""
    shared_tokens: int = 0


class BudgetProbe(abc.ABC):
    """One scheduler decide()'s worth of free-capacity planning. The
    probe is a SNAPSHOT: granting decrements the probe's own budget,
    never the backend's real allocator — the engine funds the plan for
    real at execution time."""

    @abc.abstractmethod
    def grant_continue(self, req: Request, want: int,
                       forced: bool = False) -> int:
        """Tokens (<= want) a mid-prefill request's next chunk can
        absorb within the remaining budget. `forced` plans the chunk
        regardless of budget (the engine funds the oldest prefiller by
        evicting newer requests, so it is always plannable)."""

    @abc.abstractmethod
    def grant_admit(self, req: Request, want: int) -> int:
        """Tokens (<= want) a queued request's FIRST chunk can absorb
        if admitted now, charging the budget for the unshared part; 0
        means the admission is not fundable this step."""


class SequenceBackend(abc.ABC):
    """See the module docstring for the full protocol contract."""

    families: tuple[str, ...] = ()

    @abc.abstractmethod
    def validate(self, prompt_len: int, max_new_tokens: int) -> None: ...

    @abc.abstractmethod
    def admit(self, req: Request) -> AdmitPlan: ...

    @abc.abstractmethod
    def probe_shared(self, req: Request) -> int: ...

    @abc.abstractmethod
    def budget(self) -> BudgetProbe: ...

    @abc.abstractmethod
    def can_fund(self, req: Request, n_tokens: int) -> bool: ...

    @abc.abstractmethod
    def prepare_decode(self, reqs: list[Request], evict) -> None: ...

    @abc.abstractmethod
    def fund_prefill(self, req: Request, want: int, evict) -> int: ...

    @abc.abstractmethod
    def prefill_step(self, chunks: list[tuple[Request, int]]): ...

    @abc.abstractmethod
    def decode_step(self, reqs: list[Request]): ...

    @abc.abstractmethod
    def release(self, req: Request) -> None: ...

    @abc.abstractmethod
    def utilization(self) -> tuple[float, float]: ...

    @abc.abstractmethod
    def snapshot_metrics(self) -> dict: ...

    @abc.abstractmethod
    def check_invariants(self) -> None: ...


# ---------------------------------------------------------------------------
# paged KV backend (attention families)
# ---------------------------------------------------------------------------


def _count_prefill(reg, positions: int, chunks) -> None:
    """A chunked-prefill forward computes `positions` (max_batch x
    prefill_chunk) positions, of which the chunks' tokens are valid."""
    reg.inc("backend/prefill_positions", positions)
    reg.inc("backend/prefill_tokens", sum(n for _, n in chunks))


@functools.lru_cache(maxsize=None)
def _paged_steps(cfg: ModelConfig, policy: ArithmeticPolicy,
                 attn_impl: str = "gather"):
    """Jitted paged steps shared across backends with the same
    (cfg, policy, attn_impl): a fresh jax.jit wrapper per engine would
    recompile per instance, which both slows tests and lets compile
    time leak into benchmark drains (the warmup engine would warm
    nothing).  attn_impl="fused" swaps the step builders' `paged_core`
    seam for the Pallas block-table-walking kernel; the engine and
    scheduler never see the difference."""
    paged_core = (make_fused_paged_core(cfg, policy)
                  if attn_impl == "fused" else None)
    # donate the KV pool (arg 2): both steps return the updated pool
    # and the backend overwrites self.cache.kv with it, so XLA can
    # update pages in place instead of copying the whole pool
    return (jax.jit(make_paged_chunked_prefill(cfg, policy,
                                               paged_core=paged_core),
                    donate_argnums=(2,)),
            jax.jit(make_paged_decode(cfg, policy,
                                      paged_core=paged_core),
                    donate_argnums=(2,)))


@dataclasses.dataclass
class PagedSeqState:
    """PagedKVBackend's per-request `req.mem`."""
    pages: list[int] = dataclasses.field(default_factory=list)
    shared_len: int = 0          # leading tokens resident via prefix
    #                              sharing at admission: prefill skips
    #                              their writes, seq_len covers them


class PagedBudget(BudgetProbe):
    """Page-pool planning: charges whole pages, prefix-sharing aware —
    an admission is billed only for the UNSHARED pages of its first
    chunk (a fully-resident prompt admits at zero page cost; it only
    reruns its last token for logits)."""

    def __init__(self, page_size: int, free_pages: int, probe=None):
        self.page_size = page_size
        self.free = free_pages
        self.probe = probe or (lambda r: 0)

    def grant_continue(self, req: Request, want: int,
                       forced: bool = False) -> int:
        page = self.page_size
        pos = req.prefill_pos
        shared = req.mem.shared_len if req.mem is not None else 0
        # resident coverage: chunks written so far plus any shared
        # prefix (a sharer's cursor can sit BELOW its resident tokens
        # while it reruns the last prompt token for logits)
        covered = max(pos, shared)
        held = -(-covered // page)       # pages already allocated
        headroom = held * page - pos     # free slots in held pages
        n = want if forced else min(want, headroom + self.free * page)
        if n <= 0:
            return 0
        self.free -= max(0, -(-(pos + n) // page) - held)
        self.free = max(self.free, 0)
        return n

    def grant_admit(self, req: Request, want: int) -> int:
        page = self.page_size
        ep_len = len(req.effective_prompt())
        shared = min(self.probe(req), ep_len)
        # at least the last prompt token must run for its logits, so a
        # full prefix hit still admits a 1-token rerun chunk
        start = min(shared, ep_len - 1)
        held = -(-shared // page)        # pages sharing will grant
        n = min(want, ep_len - start,
                held * page + self.free * page - start)
        if n <= 0:
            return 0
        self.free -= max(0, -(-(start + n) // page) - held)
        return n


class PagedKVBackend(SequenceBackend):
    """Paged KV cache with refcounted copy-on-write prefix sharing.

    Memory = fixed-size token pages (`paged_cache.PageAllocator` +
    `PrefixIndex`); forwards = the jit-stable chunked-prefill / decode
    steps of `paged_model`. At admission the effective prompt is
    matched against the index of already-resident pages: matched pages
    are SHARED (refcount + 1) instead of re-prefilled, prefill skips
    their writes via the chunk's write_from mask, and a write landing
    in a co-owned page COW-forks it to a private device copy first.

    Device placement flows through the `serve.mesh` seam: parameters
    and the KV pool carry shardings from `parallel.sharding`
    (`_place_params` / `init_paged_cache(sharding=...)`), and on the
    default single-device mesh every placement helper is None — a
    strict no-op, bit-pinned by the conformance suite. Page ids,
    block tables, the allocator, and the PrefixIndex are LOGICAL
    (host-side), so the sharing/COW machinery is mesh-oblivious;
    `ShardedPagedBackend` (serve/sharded_backend.py) only overrides
    the jitted step factory.
    """

    families = ("dense", "moe")

    def __init__(self, cfg: ModelConfig, ecfg: EngineConfig,
                 policy: ArithmeticPolicy, params, obs: Tracer, clock,
                 mesh: ServeMesh | None = None):
        self.cfg = cfg
        self.ecfg = ecfg
        self.mesh = mesh if mesh is not None \
            else make_serve_mesh(ecfg.mesh_shards)
        self.params = self._place_params(params)
        self.cache = init_paged_cache(
            cfg, ecfg.n_pages, ecfg.page_size,
            dtype=jnp.dtype(ecfg.cache_dtype),
            sharding=kv_pool_sharding(self.mesh, cfg))
        self.prefix = PrefixIndex(ecfg.page_size)
        self._prefill_fn, self._decode_fn = self._steps(policy)
        self._obs = obs             # Tracer: events + metrics registry
        self._now = clock           # virtual-clock read: now() -> float
        # rid -> (index generation, matched, pages): the scheduler
        # probes every visible queued request each decide(), so match
        # results are memoized until the index mutates (a queued
        # request's effective prompt is fixed; invalidated on release)
        self._match_memo: dict[int, tuple[int, int, list[int]]] = {}

    # -- mesh seam ----------------------------------------------------------

    def _place_params(self, params):
        """Pin caller-supplied parameters to the mesh's TP shardings;
        identity (no device_put at all) on the single-device mesh.
        Parameters the engine initialized (`serve.mesh.init_params`)
        are created with these shardings already, so this is a no-op
        for them."""
        shardings = param_shardings(self.mesh, self.cfg, params)
        if shardings is None:
            return params
        return jax.device_put(params, shardings)

    def _steps(self, policy: ArithmeticPolicy):
        """Jitted (prefill, decode) step pair. The single-device base
        uses the shared `_paged_steps` cache (routing the engine
        config's `attn_impl` to the gather or fused attention core);
        `ShardedPagedBackend` overrides this with mesh-sharded steps."""
        return _paged_steps(self.cfg, policy, self.ecfg.attn_impl)

    # -- admission ----------------------------------------------------------

    def validate(self, prompt_len: int, max_new_tokens: int) -> None:
        # last cache write lands at position prompt+gen-2 (the final
        # sampled token is never fed back), so this bounds page usage
        worst_pages = self.cache.allocator.pages_for(
            prompt_len + max_new_tokens - 1)
        if worst_pages > self.ecfg.max_pages_per_seq:
            raise ValueError(
                f"request needs up to {worst_pages} pages, block table "
                f"holds {self.ecfg.max_pages_per_seq}")
        if worst_pages > self.ecfg.n_pages - 1:
            raise ValueError(
                f"request needs up to {worst_pages} pages, pool has "
                f"{self.ecfg.n_pages - 1}")

    def _match_prefix(self, req: Request) -> tuple[int, list[int]]:
        """Memoized PrefixIndex.match for a queued request (one match
        serves both the scheduler's budget probe and admission)."""
        gen = self.prefix.generation
        hit = self._match_memo.get(req.rid)
        if hit is None or hit[0] != gen:
            matched, pages = self.prefix.match(req.effective_prompt())
            hit = (gen, matched, pages)
            self._match_memo[req.rid] = hit
        return hit[1], hit[2]

    def probe_shared(self, req: Request) -> int:
        if not self.ecfg.prefix_sharing:
            return 0
        return self._match_prefix(req)[0]

    def admit(self, req: Request) -> AdmitPlan:
        """Attach a page table; share every resident page covering a
        leading run of the effective prompt, start the prefill cursor
        past the shared tokens (capped so the last prompt token always
        reruns for its logits), and count the hit."""
        req.mem = PagedSeqState()
        ep = req.effective_prompt()
        reg = self._obs.registry
        reg.inc("backend/prompt_tokens", len(ep))
        if not self.ecfg.prefix_sharing:
            return AdmitPlan()
        matched, spages = self._match_prefix(req)
        self._match_memo.pop(req.rid, None)   # ep changes once laned
        if matched <= 0:
            return AdmitPlan()
        self.cache.allocator.share(spages, req.rid)
        req.mem.pages = list(spages)
        req.mem.shared_len = matched
        req.seq_len = matched
        req.prefill_pos = min(matched, len(ep) - 1)
        reg.inc("backend/n_prefix_hits")
        reg.inc("backend/shared_tokens", matched)
        self._obs.emit(ShareEvent(ts=self._now(), rid=req.rid,
                                  matched=matched))
        return AdmitPlan(shared_tokens=matched)

    def budget(self) -> PagedBudget:
        return PagedBudget(self.ecfg.page_size,
                           self.cache.allocator.n_free,
                           probe=self.probe_shared)

    def can_fund(self, req: Request, n_tokens: int) -> bool:
        page = self.ecfg.page_size
        held = len(req.mem.pages) if req.mem is not None else 0
        pos = max(req.prefill_pos, req.seq_len)
        need = -(-(pos + n_tokens) // page) - held
        return need <= self.cache.allocator.n_free

    # -- memory pressure ----------------------------------------------------

    def _forget_released(self, pages: list[int], rid: int) -> None:
        """Drop `rid`'s ownership of `pages`; pages whose last owner
        left go back to the pool AND out of the prefix index."""
        released = self.cache.allocator.free(pages, owner=rid)
        self.prefix.forget(released)

    def _make_room(self, req: Request, evict) -> bool:
        """Free at least one page via the engine's eviction policy
        (evicting a sharer may release nothing physical, so keep
        going). False if req itself was evicted."""
        alloc = self.cache.allocator
        while not alloc.can_alloc(1):
            if not evict():
                # unreachable from engine flow (req itself is laned),
                # but external allocator users can drain the pool
                raise MemoryError("page pool dry with no evictable lane")
            if req.mem is None:
                return False      # req itself was the victim
        return True

    def _grow(self, req: Request, evict) -> bool:
        """Give `req` one more page, evicting under cache pressure.
        False if req itself was evicted."""
        if not self._make_room(req, evict):
            return False
        req.mem.pages.extend(self.cache.allocator.alloc(1, req.rid))
        return True

    def _divert_write(self, req: Request, j: int, evict) -> bool:
        """req is about to write into its page j, whose content other
        places may still rely on. Two cases: co-owned (refcount > 1) —
        COW-fork to a private device copy so the write cannot clobber
        co-owners' K/V; sole-owned but still in the prefix index (the
        co-owners left, e.g. the original writer finished) — the write
        diverges the page from its indexed content, so the index entry
        is dropped before a future admission can match stale K/V.
        False if req itself was evicted while making room for a fork."""
        if self.cache.allocator.refcount(req.mem.pages[j]) <= 1:
            self.prefix.forget([req.mem.pages[j]])
            return True
        return self._cow_fork(req, j, evict)

    def _cow_fork(self, req: Request, j: int, evict) -> bool:
        """Copy-on-write: replace `req`'s shared page j with a private
        device copy so its next write cannot clobber co-owners' K/V.
        False if req itself was evicted while making room."""
        if not self._make_room(req, evict):
            return False
        alloc = self.cache.allocator
        old = req.mem.pages[j]
        if alloc.refcount(old) <= 1:
            # co-owners were evicted while making room; the page may
            # still be indexed, and the write is about to diverge it
            self.prefix.forget([old])
            return True
        [new] = alloc.alloc(1, req.rid)
        self.cache.kv = cow_copy_page(
            self.cache.kv, jnp.int32(old), jnp.int32(new))
        req.mem.pages[j] = new
        self._forget_released([old], req.rid)
        self._obs.registry.inc("backend/n_cow_forks")
        self._obs.emit(CowForkEvent(ts=self._now(), rid=req.rid,
                                    old_page=old, new_page=new))
        return True

    def prepare_decode(self, reqs: list[Request], evict) -> None:
        """Prepare every decode lane's write target, oldest admissions
        first so eviction pressure lands on the newest request: lanes
        at a page boundary get a fresh page; lanes about to write into
        a SHARED page (another request references it) COW-fork it to a
        private copy first."""
        page = self.ecfg.page_size
        for req in reqs:
            if req.state is not RequestState.DECODE:
                continue   # evicted earlier in this very loop
            if req.seq_len >= len(req.mem.pages) * page:
                self._grow(req, evict)
            else:
                self._divert_write(req, req.seq_len // page, evict)

    def fund_prefill(self, req: Request, want: int, evict) -> int:
        """Allocate pages so `req` can absorb `want` more prompt
        tokens. Under pressure, only requests admitted AFTER `req` are
        evicted (pressure always lands on the newest, so a fresh
        admission can never evict an older request). Returns the
        granted token count — possibly < want, or 0, when the pool
        cannot fund the chunk without touching older requests."""
        page = self.ecfg.page_size
        alloc = self.cache.allocator
        end = req.prefill_pos + want
        while len(req.mem.pages) * page < end:
            if alloc.can_alloc(1):
                req.mem.pages.extend(alloc.alloc(1, req.rid))
                continue
            if not evict(exclude=req, newer_than=req):
                break
        n = min(want, len(req.mem.pages) * page - req.prefill_pos)
        if n <= 0:
            return 0
        # copy-on-write: this chunk WRITES positions [ws, we) (rerun
        # positions below shared_len only read); any of those pages
        # still co-owned must be forked before the scatter runs
        ws = max(req.prefill_pos, req.mem.shared_len)
        we = req.prefill_pos + n
        if ws < we:
            for j in range(ws // page, -(-we // page)):
                if not self._divert_write(req, j, evict):
                    return 0       # req itself evicted making room
        return n

    # -- forwards -----------------------------------------------------------

    def _register_full_pages(self, req: Request, from_seq: int) -> None:
        """Index every page that BECAME full while req's resident
        coverage grew from from_seq to req.seq_len (prefill only —
        decode-filled pages hold generated tokens no other prompt is
        likely to revisit, and keeping them out keeps forgetting
        simple)."""
        if not self.ecfg.prefix_sharing:
            return
        page = self.ecfg.page_size
        ep = req.effective_prompt()
        for j in range(from_seq // page, req.seq_len // page):
            self.prefix.register(ep[:(j + 1) * page], req.mem.pages[j])

    def prefill_step(self, chunks: list[tuple[Request, int]]):
        obs = self._obs
        b, c = self.ecfg.max_batch, self.ecfg.prefill_chunk
        pmax = self.ecfg.max_pages_per_seq
        with obs.span("serve.pack", phase="prefill"):
            tokens = np.zeros((b, c), np.int32)
            tables = np.full((b, pmax), TRASH_PAGE, np.int32)
            start = np.zeros((b,), np.int32)
            lens = np.zeros((b,), np.int32)
            active = np.zeros((b,), bool)
            wfrom = np.zeros((b,), np.int32)
            for i, (req, n) in enumerate(chunks):
                ep = req.effective_prompt()
                tokens[i, :n] = ep[req.prefill_pos:req.prefill_pos + n]
                tables[i, :len(req.mem.pages)] = req.mem.pages
                start[i] = req.prefill_pos
                lens[i] = n
                active[i] = True
                # positions below shared_len are resident in (possibly
                # shared) pages: rerun the query, skip the write
                wfrom[i] = req.mem.shared_len
            tokens, tables, start, lens, active, wfrom = (
                jnp.asarray(a)
                for a in (tokens, tables, start, lens, active, wfrom))
        with obs.span("serve.dispatch", phase="prefill"):
            logits, kv = self._prefill_fn(
                self.params, tokens, self.cache.kv, tables, start, lens,
                active, wfrom)
            self.cache.kv = kv
        _count_prefill(obs.registry, b * c, chunks)
        with obs.span("serve.apply", phase="prefill"):
            for req, n in chunks:
                old_seq = req.seq_len
                req.prefill_pos += n
                # a sharer rerunning inside its shared prefix already
                # has seq_len past the cursor — coverage never shrinks
                req.seq_len = max(req.seq_len, req.prefill_pos)
                self._register_full_pages(req, old_seq)
        return logits

    def decode_step(self, reqs: list[Request]):
        obs = self._obs
        b, pmax = self.ecfg.max_batch, self.ecfg.max_pages_per_seq
        with obs.span("serve.pack", phase="decode"):
            tokens = np.zeros((b, 1), np.int32)
            tables = np.full((b, pmax), TRASH_PAGE, np.int32)
            seq_lens = np.zeros((b,), np.int32)
            active = np.zeros((b,), bool)
            for req in reqs:
                tokens[req.lane, 0] = req.generated[-1]
                tables[req.lane, :len(req.mem.pages)] = req.mem.pages
                seq_lens[req.lane] = req.seq_len
                active[req.lane] = True
            tokens, tables, seq_lens, active = (
                jnp.asarray(a) for a in (tokens, tables, seq_lens, active))
        with obs.span("serve.dispatch", phase="decode"):
            logits, kv = self._decode_fn(
                self.params, tokens, self.cache.kv, tables, seq_lens,
                active)
            self.cache.kv = kv
        return logits

    # -- release / accounting -----------------------------------------------

    def release(self, req: Request) -> None:
        """Drop req's page references; co-owned pages stay resident
        for the other sharers."""
        if req.mem is None:
            return
        if req.mem.pages:
            self._forget_released(req.mem.pages, req.rid)
        req.mem = None
        # the effective prompt grows with generated tokens, so any
        # memoized prefix match is stale even at the same generation
        self._match_memo.pop(req.rid, None)

    def utilization(self) -> tuple[float, float]:
        return self.cache.utilization(), self.cache.logical_utilization()

    def snapshot_metrics(self) -> dict:
        reg = self._obs.registry
        return {
            "n_prefix_hits": int(reg.count("backend/n_prefix_hits")),
            "prefix_hit_rate": (
                reg.count("backend/shared_tokens")
                / max(reg.count("backend/prompt_tokens"), 1)),
            "n_cow_forks": int(reg.count("backend/n_cow_forks")),
            "physical_pages_allocated":
                self.cache.allocator.total_allocated,
        }

    def check_invariants(self) -> None:
        self.cache.allocator.check_invariants()
        for p in self.prefix.pages():
            assert self.cache.allocator.refcount(p) >= 1, \
                f"prefix index advertises non-resident page {p}"


# ---------------------------------------------------------------------------
# state-slot backend (recurrent families)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _slot_steps(cfg: ModelConfig, policy: ArithmeticPolicy):
    """Jitted state-slot steps shared across backends with the same
    (cfg, policy) — same sharing rationale as _paged_steps. The slot
    pool (arg 2) is donated: both steps return the updated pool and
    the backend overwrites self.pool with it."""
    return (jax.jit(make_slot_prefill_chunk(cfg, policy),
                    donate_argnums=(2,)),
            jax.jit(make_slot_decode(cfg, policy),
                    donate_argnums=(2,)))


@dataclasses.dataclass
class SlotSeqState:
    """StateSlotBackend's per-request `req.mem`."""
    slot: int


class SlotBudget(BudgetProbe):
    """Slot-pool planning: a sequence costs exactly ONE slot for its
    whole lifetime, so continuing chunks are free (the slot is already
    held) and an admission charges one slot."""

    def __init__(self, free_slots: int):
        self.free = free_slots

    def grant_continue(self, req: Request, want: int,
                       forced: bool = False) -> int:
        return want

    def grant_admit(self, req: Request, want: int) -> int:
        if self.free <= 0:
            return 0
        self.free -= 1
        return min(want, len(req.effective_prompt()))


class StateSlotBackend(SequenceBackend):
    """Fixed pool of per-lane recurrent state slots.

    A request holds exactly one slot from admission to release; the
    slot is reset to the family's pristine initial cache on
    allocation, chunked prefill absorbs the effective prompt into it
    (per-token, exact for any per-lane chunk length — see
    `state_model`), and decode advances it one token per step. State
    is a dense mixture of the whole history, so there is nothing to
    prefix-share (probe_shared == 0) and nothing to grow — once
    admitted, a request can ALWAYS decode to completion, so the only
    eviction this backend ever sees is externally forced, and
    preemption recovers by recompute into a fresh slot.
    """

    families = ("rwkv6", "zamba2")

    def __init__(self, cfg: ModelConfig, ecfg: EngineConfig,
                 policy: ArithmeticPolicy, params, obs: Tracer, clock):
        self.cfg = cfg
        self.ecfg = ecfg
        self.params = params
        self.n_slots = ecfg.n_slots or ecfg.max_batch + 1
        # the page allocator is a generic refcounting free list over
        # ids [1, n); reused here as the slot allocator (slot "size" 1,
        # refcounts stay at 1 — slots are never shared)
        self.allocator = PageAllocator(self.n_slots, 1)
        self.pool, self.init_slot = init_slot_pool(
            cfg, self.n_slots, ecfg.max_seq_len,
            dtype=jnp.dtype(ecfg.cache_dtype))
        self._prefill_fn, self._decode_fn = _slot_steps(cfg, policy)
        self._obs = obs
        self._now = clock

    # -- admission ----------------------------------------------------------

    def validate(self, prompt_len: int, max_new_tokens: int) -> None:
        # the final sampled token is never fed back into the state
        total = prompt_len + max_new_tokens - 1
        if total > self.ecfg.max_seq_len:
            raise ValueError(
                f"request absorbs up to {total} tokens, max_seq_len "
                f"is {self.ecfg.max_seq_len}")

    def admit(self, req: Request) -> AdmitPlan:
        if not self.allocator.can_alloc(1):
            # unreachable from engine flow: the scheduler budgets
            # admissions against free slots via SlotBudget
            raise MemoryError("state-slot pool dry at admission")
        [slot] = self.allocator.alloc(1, req.rid)
        # a freed slot holds its previous occupant's state; reset to
        # the pristine initial cache before the new prompt lands
        self.pool = reset_slot(self.pool, self.init_slot,
                               jnp.int32(slot))
        req.mem = SlotSeqState(slot=slot)
        self._obs.registry.inc("backend/prompt_tokens",
                               len(req.effective_prompt()))
        return AdmitPlan()

    def probe_shared(self, req: Request) -> int:
        return 0

    def budget(self) -> SlotBudget:
        return SlotBudget(self.allocator.n_free)

    def can_fund(self, req: Request, n_tokens: int) -> bool:
        if req.mem is not None:
            return True          # the slot absorbs any token count
        return self.allocator.can_alloc(1)

    def prepare_decode(self, reqs: list[Request], evict) -> None:
        pass                     # fixed-size state never grows

    def fund_prefill(self, req: Request, want: int, evict) -> int:
        return want              # the slot was funded at admission

    # -- forwards -----------------------------------------------------------

    def prefill_step(self, chunks: list[tuple[Request, int]]):
        obs = self._obs
        b, c = self.ecfg.max_batch, self.ecfg.prefill_chunk
        with obs.span("serve.pack", phase="prefill"):
            tokens = np.zeros((b, c), np.int32)
            slot_ids = np.full((b,), TRASH_SLOT, np.int32)
            lens = np.zeros((b,), np.int32)
            active = np.zeros((b,), bool)
            for i, (req, n) in enumerate(chunks):
                ep = req.effective_prompt()
                tokens[i, :n] = ep[req.prefill_pos:req.prefill_pos + n]
                slot_ids[i] = req.mem.slot
                lens[i] = n
                active[i] = True
            tokens, slot_ids, lens, active = (
                jnp.asarray(a) for a in (tokens, slot_ids, lens, active))
        with obs.span("serve.dispatch", phase="prefill"):
            logits, pool = self._prefill_fn(
                self.params, tokens, self.pool, slot_ids, lens, active)
            self.pool = pool
        _count_prefill(obs.registry, b * c, chunks)
        for req, n in chunks:
            req.prefill_pos += n
            req.seq_len = req.prefill_pos
        return logits

    def decode_step(self, reqs: list[Request]):
        obs = self._obs
        b = self.ecfg.max_batch
        with obs.span("serve.pack", phase="decode"):
            tokens = np.zeros((b, 1), np.int32)
            slot_ids = np.full((b,), TRASH_SLOT, np.int32)
            for req in reqs:
                tokens[req.lane, 0] = req.generated[-1]
                slot_ids[req.lane] = req.mem.slot
            tokens, slot_ids = jnp.asarray(tokens), jnp.asarray(slot_ids)
        with obs.span("serve.dispatch", phase="decode"):
            logits, pool = self._decode_fn(
                self.params, tokens, self.pool, slot_ids)
            self.pool = pool
        return logits

    # -- release / accounting -----------------------------------------------

    def release(self, req: Request) -> None:
        if req.mem is None:
            return
        self.allocator.free([req.mem.slot], owner=req.rid)
        req.mem = None

    def utilization(self) -> tuple[float, float]:
        u = self.allocator.n_used / max(self.n_slots - 1, 1)
        return u, u              # slots are never shared

    def snapshot_metrics(self) -> dict:
        return {
            "n_state_slots": self.n_slots - 1,
            "state_slots_allocated": self.allocator.total_allocated,
        }

    def check_invariants(self) -> None:
        self.allocator.check_invariants()
        assert self.allocator.n_logical == self.allocator.n_used, \
            "state slots must never be shared across requests"


# ---------------------------------------------------------------------------
# family routing
# ---------------------------------------------------------------------------


def make_backend(cfg: ModelConfig, ecfg: EngineConfig,
                 policy: ArithmeticPolicy, params, obs: Tracer,
                 clock, mesh: ServeMesh | None = None) -> SequenceBackend:
    """Route a model family (and mesh) to its sequence backend. `obs`
    is the engine's observability hub (repro.serve.obs.Tracer:
    typed-event sink + metrics registry), `clock` reads the engine's
    virtual time (clock() -> float) — see the module docstring's
    event-emission contract. `mesh` is the engine's serve-mesh seam
    (defaults from ecfg.mesh_shards); a multi-shard mesh routes paged
    families through the tensor-parallel `ShardedPagedBackend`."""
    mesh = mesh if mesh is not None else make_serve_mesh(ecfg.mesh_shards)
    if not mesh.is_single:
        from repro.serve.sharded_backend import ShardedPagedBackend
        if cfg.family in ShardedPagedBackend.families:
            return ShardedPagedBackend(cfg, ecfg, policy, params, obs,
                                       clock, mesh=mesh)
        raise ValueError(
            f"family {cfg.family!r} has no multi-device backend "
            f"(state-slot families serve single-device; set "
            f"mesh_shards=1)")
    for backend_cls in (PagedKVBackend, StateSlotBackend):
        if cfg.family in backend_cls.families:
            return backend_cls(cfg, ecfg, policy, params, obs, clock)
    served = PagedKVBackend.families + StateSlotBackend.families
    raise ValueError(
        f"no sequence backend serves family {cfg.family!r} "
        f"(available: {served})")
