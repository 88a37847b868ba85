"""Tests for the continuous-batching serving engine (repro.serve).

Covers the ISSUE acceptance points: paged-cache allocator invariants
(no aliasing, full free on completion), paged-attention decode and
chunked-prefill equivalence vs the dense-cache reference, scheduler
determinism under a fixed seed/trace (including mixed prefill+decode
actions), and the headline guarantee — engine-mode serving with mixed
prompt/gen lengths and chunked+batched prefill is token-identical to
sequential single-request dense decoding under greedy sampling,
including through cache-pressure preemptions landing mid-prefill.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:
    # hypothesis is a dev-only dep (requirements-dev.txt): without it
    # only the @given property tests skip — the deterministic tests in
    # this module still run.
    class _StrategyStub:
        def __getattr__(self, name):
            return lambda *a, **k: None

    st = _StrategyStub()

    def given(*a, **k):
        return pytest.mark.skip(
            reason="property test needs hypothesis (requirements-dev.txt)")

    def settings(*a, **k):
        return lambda f: f

from repro import configs
from repro.launch import steps as stepslib
from repro.models import model
from repro.serve import (
    ArtemisCostModel,
    EngineConfig,
    PageAllocator,
    ServeEngine,
    TrafficConfig,
    init_paged_cache,
    make_paged_chunked_prefill,
    make_paged_decode,
    make_paged_prefill,
    pad_to_page,
    percentile,
    synth_trace,
)
from repro.serve.paged_cache import TRASH_PAGE
from repro.serve.request import RequestState


@pytest.fixture(scope="module")
def dense_setup():
    cfg = dataclasses.replace(configs.get_config("qwen3_8b", smoke=True),
                              compute_dtype="float32")
    params = model.init(jax.random.PRNGKey(0), cfg)
    return cfg, params


@functools.lru_cache(maxsize=4)
def _dense_steps(cfg):
    """Jitted dense steps, shared across reference decodes so XLA's jit
    cache actually hits (a fresh jit wrapper per request recompiles)."""
    return (jax.jit(stepslib.make_prefill_step(cfg)),
            jax.jit(stepslib.make_decode_step(cfg)))


_REF_CACHE: dict = {}


def _sequential_reference(cfg, params, prompt, n_new):
    """Greedy decode of one request alone on the dense-cache path.
    Memoized: the chunk-size parametrizations replay the same trace."""
    key = (cfg.name, prompt.tobytes(), n_new)
    if key in _REF_CACHE:
        return _REF_CACHE[key]
    prefill, decode = _dense_steps(cfg)
    cache = model.init_cache(cfg, 1, len(prompt) + n_new,
                             dtype=jnp.float32)
    logits, cache = prefill(params, {"tokens": jnp.asarray(prompt[None])},
                            cache)
    out = [int(stepslib.greedy_sample(logits)[0])]
    for _ in range(n_new - 1):
        logits, cache = decode(
            params, jnp.asarray([[out[-1]]], jnp.int32), cache)
        out.append(int(stepslib.greedy_sample(logits)[0]))
    _REF_CACHE[key] = out
    return out


# ---------------------------------------------------------------------------
# allocator
# ---------------------------------------------------------------------------


class TestPageAllocator:
    def test_no_aliasing_and_full_free(self):
        a = PageAllocator(n_pages=16, page_size=4)
        p1 = a.alloc(5, owner=1)
        p2 = a.alloc(5, owner=2)
        assert not (set(p1) & set(p2)), "pages aliased across requests"
        assert 0 not in p1 + p2, "trash page handed out"
        a.check_invariants()
        a.free(p1)
        a.check_invariants()
        p3 = a.alloc(5, owner=3)
        assert not (set(p3) & set(p2))
        a.free(p2)
        a.free(p3)
        a.check_invariants()
        assert a.n_used == 0 and a.n_free == 15

    def test_exhaustion_and_double_free(self):
        a = PageAllocator(n_pages=8, page_size=4)
        pages = a.alloc(7, owner=1)
        with pytest.raises(MemoryError):
            a.alloc(1, owner=2)
        a.free(pages)
        with pytest.raises(ValueError):
            a.free(pages)

    def test_random_op_sequence_keeps_invariants(self):
        rng = np.random.default_rng(0)
        a = PageAllocator(n_pages=32, page_size=4)
        live = {}
        for i in range(200):
            if live and (rng.random() < 0.4 or a.n_free < 4):
                rid = int(rng.choice(list(live)))
                a.free(live.pop(rid))
            else:
                n = int(rng.integers(1, 5))
                if a.can_alloc(n):
                    live[i] = a.alloc(n, owner=i)
            a.check_invariants()
        for pages in live.values():
            a.free(pages)
        a.check_invariants()
        assert a.n_used == 0

    def test_pad_to_page(self):
        assert pad_to_page(1, 8) == 8
        assert pad_to_page(8, 8) == 8
        assert pad_to_page(9, 8) == 16

    def test_refcount_share_and_last_owner_release(self):
        a = PageAllocator(n_pages=8, page_size=4)
        pages = a.alloc(2, owner=1)
        a.share(pages, owner=2)
        assert all(a.refcount(p) == 2 for p in pages)
        assert a.n_used == 2 and a.n_logical == 4
        a.check_invariants()
        released = a.free(pages, owner=1)
        assert released == []            # owner 2 still holds them
        assert a.n_used == 2 and all(a.refcount(p) == 1 for p in pages)
        a.check_invariants()
        released = a.free(pages, owner=2)
        assert sorted(released) == sorted(pages)   # last owner releases
        assert a.n_used == 0 and a.n_free == 7
        a.check_invariants()

    def test_share_and_free_error_cases(self):
        a = PageAllocator(n_pages=8, page_size=4)
        [p] = a.alloc(1, owner=1)
        with pytest.raises(ValueError, match="already owns"):
            a.share([p], owner=1)
        a.share([p], owner=2)
        with pytest.raises(ValueError, match="explicit owner"):
            a.free([p])                  # shared: owner is ambiguous
        with pytest.raises(ValueError, match="does not own"):
            a.free([p], owner=3)
        a.free([p], owner=2)
        a.free([p], owner=1)
        with pytest.raises(ValueError, match="double free"):
            a.free([p], owner=1)
        with pytest.raises(ValueError, match="share free page"):
            a.share([p], owner=1)
        a.check_invariants()

    def test_free_order_is_normalized(self):
        """Regression: free() used to append pages to the free list in
        caller order, so LIFO reuse silently depended on each call
        site's list ordering — with COW adding new free paths, reuse
        order must be a function of the page SET, not its ordering."""
        seqs = []
        for order in ([3, 5, 2], [5, 2, 3], [2, 3, 5]):
            a = PageAllocator(n_pages=8, page_size=4)
            a.alloc(6, owner=1)              # pages 1..6
            a.free(order, owner=1)
            seqs.append(a.alloc(3, owner=2))
            a.check_invariants()
        assert seqs[0] == seqs[1] == seqs[2], seqs
        assert seqs[0] == [2, 3, 5]          # descending append, LIFO pop


# ---------------------------------------------------------------------------
# prefix index
# ---------------------------------------------------------------------------


class TestPrefixIndex:
    def _index(self, ps=4):
        from repro.serve import PrefixIndex
        return PrefixIndex(page_size=ps)

    def test_full_page_chain_match(self):
        idx = self._index()
        prompt = np.arange(2, 14, dtype=np.int32)        # 12 tokens
        assert idx.match(prompt) == (0, [])
        assert idx.register(prompt[:4], page=5)
        assert idx.register(prompt[:8], page=7)
        m, pages = idx.match(prompt)
        assert (m, pages) == (8, [5, 7])
        # diverging second page stops the chain after page one
        other = prompt.copy()
        other[6] = 99
        m, pages = idx.match(other[:8])
        assert (m, pages) == (4, [5])
        # a different FIRST page means no match at all, even though the
        # second page's own tokens are identical (content depends on
        # the whole prefix, which the chain key encodes)
        shifted = prompt.copy()
        shifted[0] = 99
        assert idx.match(shifted) == (0, [])

    def test_partial_last_page_match(self):
        idx = self._index()
        prompt = np.arange(2, 10, dtype=np.int32)        # 8 tokens
        idx.register(prompt[:4], page=3)
        idx.register(prompt[:8], page=4)
        # a prompt ending mid-page shares the resident page that covers
        # its remainder — the trailing garbage is masked by seq_len
        m, pages = idx.match(prompt[:6])
        assert (m, pages) == (6, [3, 4])
        # remainder diverging from every resident run: full pages only
        other = prompt[:6].copy()
        other[5] = 99
        assert idx.match(other) == (4, [3])

    def test_first_writer_wins_and_forget(self):
        idx = self._index()
        prompt = np.arange(2, 10, dtype=np.int32)
        assert idx.register(prompt[:4], page=3)
        assert not idx.register(prompt[:4], page=6)   # same content
        assert not idx.register(prompt[:8], page=3)   # page reused
        assert idx.match(prompt[:4]) == (4, [3])
        idx.forget([3])
        assert idx.match(prompt[:4]) == (0, [])
        assert len(idx) == 0
        idx.forget([3])                               # idempotent
        assert idx.register(prompt[:4], page=6)       # key free again
        assert idx.match(prompt[:4]) == (4, [6])

    def test_register_validates_prefix_length(self):
        idx = self._index()
        with pytest.raises(ValueError, match="multiple"):
            idx.register(np.arange(3, dtype=np.int32), page=1)
        with pytest.raises(ValueError, match="multiple"):
            idx.register(np.zeros(0, np.int32), page=1)


@settings(max_examples=60, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 3), st.integers(0, 5),
                          st.integers(0, 5)),
                max_size=80))
def test_allocator_share_free_cow_interleavings(ops):
    """Property: any interleaving of alloc / share / free / COW-style
    fork-and-release keeps the allocator invariants (free + live
    partition the pool, refcounts >= 1 for live pages, shared pages
    counted once physically) and releases everything at the end."""
    a = PageAllocator(n_pages=10, page_size=4)
    held: dict[int, list[int]] = {}       # owner -> pages (may repeat
    #                                        across owners = sharing)
    for code, x, y in ops:
        owners = sorted(held)
        if code == 0 and a.can_alloc(y % 2 + 1):             # alloc
            held.setdefault(x, []).extend(a.alloc(y % 2 + 1, x))
        elif code == 1 and owners:                           # share
            src = owners[x % len(owners)]
            cands = [p for p in held[src]
                     if y not in a.owners_of(p)]
            if cands and y not in (src,):
                p = cands[x % len(cands)]
                a.share([p], y)
                held.setdefault(y, []).append(p)
        elif code == 2 and owners:                           # free one
            o = owners[x % len(owners)]
            p = held[o][y % len(held[o])]
            a.free([p], owner=o)
            held[o].remove(p)
            if not held[o]:
                del held[o]
        elif code == 3 and owners and a.can_alloc(1):        # COW fork
            o = owners[x % len(owners)]
            shared = [p for p in held[o] if a.refcount(p) > 1]
            if shared:
                p = shared[y % len(shared)]
                [new] = a.alloc(1, o)
                a.free([p], owner=o)
                held[o][held[o].index(p)] = new
        a.check_invariants()
        assert a.n_logical == sum(len(v) for v in held.values())
    for o in sorted(held):
        a.free(held[o], owner=o)
    a.check_invariants()
    assert a.n_used == 0 and a.n_free == 9


# ---------------------------------------------------------------------------
# paged forward vs dense reference
# ---------------------------------------------------------------------------


def test_paged_decode_logits_match_dense(dense_setup):
    cfg, params = dense_setup
    prompt = np.arange(2, 12, dtype=np.int32)          # 10 tokens
    page = 4
    cache = init_paged_cache(cfg, n_pages=16, page_size=page)
    s_pad = pad_to_page(len(prompt), page)
    pages = cache.allocator.alloc(s_pad // page, owner=0)

    prefill = make_paged_prefill(cfg)
    decode = make_paged_decode(cfg)
    tokens = np.zeros((1, s_pad), np.int32)
    tokens[0, :len(prompt)] = prompt
    logits_p, kv = prefill(params, jnp.asarray(tokens), cache.kv,
                           jnp.asarray(pages, jnp.int32))
    cache.kv = kv

    # dense reference
    dcache = model.init_cache(cfg, 1, len(prompt) + 4, dtype=jnp.float32)
    logits_d, dcache = stepslib.make_prefill_step(cfg)(
        params, {"tokens": jnp.asarray(prompt[None])}, dcache)
    np.testing.assert_allclose(
        np.asarray(logits_p[len(prompt) - 1]), np.asarray(logits_d[0]),
        rtol=1e-4, atol=1e-4)

    # three decode steps, logits compared each step
    nxt = int(jnp.argmax(logits_d[0]))
    seq_len = len(prompt)
    tables = np.zeros((2, 4), np.int32)                # max_batch 2 lanes
    for _ in range(3):
        if seq_len >= len(pages) * page:
            pages += cache.allocator.alloc(1, owner=0)
        tables[0, :len(pages)] = pages
        lp, kv = decode(
            params, jnp.asarray([[nxt], [0]], jnp.int32), cache.kv,
            jnp.asarray(tables), jnp.asarray([seq_len, 0], jnp.int32),
            jnp.asarray([True, False]))
        cache.kv = kv
        ld, dcache = stepslib.make_decode_step(cfg)(
            params, jnp.asarray([[nxt]], jnp.int32), dcache)
        np.testing.assert_allclose(np.asarray(lp[0]), np.asarray(ld[0]),
                                   rtol=1e-4, atol=1e-4)
        nxt = int(jnp.argmax(ld[0]))
        seq_len += 1


def test_chunked_prefill_logits_match_dense(dense_setup):
    """Chunk-by-chunk prefill over the paged pool reproduces the dense
    prefill's last-position logits — chunks straddle page boundaries
    (13 tokens, chunks of 8, pages of 4)."""
    cfg, params = dense_setup
    prompt = np.arange(2, 15, dtype=np.int32)          # 13 tokens
    page, chunk_c, b, pmax = 4, 8, 2, 6
    cache = init_paged_cache(cfg, n_pages=16, page_size=page)
    cp = make_paged_chunked_prefill(cfg)

    pages, pos, last = [], 0, None
    while pos < len(prompt):
        n = min(chunk_c, len(prompt) - pos)
        while len(pages) * page < pos + n:
            pages += cache.allocator.alloc(1, owner=0)
        tokens = np.zeros((b, chunk_c), np.int32)
        tokens[0, :n] = prompt[pos:pos + n]
        tables = np.full((b, pmax), TRASH_PAGE, np.int32)
        tables[0, :len(pages)] = pages
        start = np.array([pos, 0], np.int32)
        lens = np.array([n, 0], np.int32)
        active = np.array([True, False])
        wfrom = np.zeros((b,), np.int32)
        logits, kv = cp(params, jnp.asarray(tokens), cache.kv,
                        jnp.asarray(tables), jnp.asarray(start),
                        jnp.asarray(lens), jnp.asarray(active),
                        jnp.asarray(wfrom))
        cache.kv = kv
        last = np.asarray(logits[0, n - 1])
        pos += n

    dcache = model.init_cache(cfg, 1, len(prompt), dtype=jnp.float32)
    logits_d, _ = stepslib.make_prefill_step(cfg)(
        params, {"tokens": jnp.asarray(prompt[None])}, dcache)
    np.testing.assert_allclose(last, np.asarray(logits_d[0]),
                               rtol=1e-4, atol=1e-4)

    # write-skip rerun (the prefix-sharing path): rerun the last token
    # with its K/V write masked — logits must still match, because the
    # query reads its own position's K/V from the already-resident page
    tokens = np.zeros((b, chunk_c), np.int32)
    tokens[0, 0] = prompt[-1]
    tables = np.full((b, pmax), TRASH_PAGE, np.int32)
    tables[0, :len(pages)] = pages
    kv_before = cache.kv["k"]
    logits, kv = cp(params, jnp.asarray(tokens), cache.kv,
                    jnp.asarray(tables),
                    jnp.asarray([len(prompt) - 1, 0], np.int32),
                    jnp.asarray([1, 0], np.int32),
                    jnp.asarray([True, False]),
                    jnp.asarray([len(prompt), 0], np.int32))
    cache.kv = kv
    np.testing.assert_allclose(np.asarray(logits[0, 0]),
                               np.asarray(logits_d[0]),
                               rtol=1e-4, atol=1e-4)
    # the skipped write must not have touched the request's pages
    np.testing.assert_array_equal(
        np.asarray(kv["k"][:, pages]), np.asarray(kv_before[:, pages]))


@pytest.mark.parametrize("step", ["chunked_prefill", "decode"])
def test_paged_step_writes_only_the_slots_its_tokens_own(dense_setup, step):
    """One step on a pool of noise matches the whole-prompt reference's
    logits and K/V, and changes the pool only at the (layer, page, slot)
    entries its tokens own and in each layer's trash page: a layer that
    reads or writes at the wrong page offset lands in another layer's
    pages, or leaves its own unwritten."""
    cfg, params = dense_setup
    page, n_pages, b, pmax, chunk_c = 4, 12, 2, 4, 16
    pages = np.array([5, 2, 9], np.int32)              # out of order
    prompt = np.arange(3, 14, dtype=np.int32)          # 11 tokens
    shape = (cfg.n_layers, n_pages, page, cfg.n_kv_heads,
             cfg.resolved_head_dim)
    keys = jax.random.split(jax.random.PRNGKey(7))
    noise = {n: jax.random.normal(k, shape) for n, k in zip("kv", keys)}
    ref = jax.jit(make_paged_prefill(cfg))

    def padded(toks):
        out = np.zeros((1, len(pages) * page), np.int32)
        out[0, :len(toks)] = toks
        return jnp.asarray(out)

    ref_logits, ref_kv = ref(params, padded(prompt), noise, pages)
    tables = np.full((b, pmax), TRASH_PAGE, np.int32)
    tables[0, :len(pages)] = pages
    if step == "chunked_prefill":
        before, first = noise, 0
        tokens = np.zeros((b, chunk_c), np.int32)
        tokens[0, :len(prompt)] = prompt
        logits, kv = make_paged_chunked_prefill(cfg)(
            params, jnp.asarray(tokens), before, jnp.asarray(tables),
            jnp.zeros((b,), jnp.int32),
            jnp.asarray([len(prompt), 0], jnp.int32),
            jnp.asarray([True, False]), jnp.zeros((b,), jnp.int32))
        got = logits[0, :len(prompt)]
    else:
        # the prompt but its last token is resident; decode that token
        first = len(prompt) - 1
        _, before = ref(params, padded(prompt[:first]), noise, pages)
        logits, kv = make_paged_decode(cfg)(
            params, jnp.asarray([[prompt[-1]], [0]], jnp.int32), before,
            jnp.asarray(tables), jnp.asarray([first, 0], jnp.int32),
            jnp.asarray([True, False]))
        got = logits[:1]
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref_logits[first:len(prompt)]),
                               rtol=1e-4, atol=1e-4)

    own = np.zeros(shape[:3], bool)                    # (layer, page, slot)
    own[:, TRASH_PAGE] = True
    for pos in range(first, len(prompt)):
        pg, sl = pages[pos // page], pos % page
        own[:, pg, sl] = True
        for name in "kv":
            np.testing.assert_allclose(
                np.asarray(kv[name][:, pg, sl]),
                np.asarray(ref_kv[name][:, pg, sl]), rtol=1e-4, atol=1e-4)
    for name in "kv":
        new, old = np.asarray(kv[name]), np.asarray(before[name])
        np.testing.assert_array_equal(new[~own], old[~own])


def test_paged_model_rejects_recurrent_families():
    cfg = configs.get_config("rwkv6_3b", smoke=True)
    with pytest.raises(ValueError, match="dense/moe"):
        make_paged_decode(cfg)
    with pytest.raises(ValueError, match="dense/moe"):
        make_paged_chunked_prefill(cfg)
    with pytest.raises(ValueError, match="attention family"):
        init_paged_cache(cfg, 8, 4)


# ---------------------------------------------------------------------------
# engine end-to-end
# ---------------------------------------------------------------------------


# chunk sizes that divide (4 | 8, 12, 16, 20), straddle (7), and
# exceed (32) the trace's prompt lengths (3..20)
@pytest.mark.parametrize("prefill_chunk", [4, 7, 32])
def test_engine_token_identical_to_sequential(dense_setup, prefill_chunk):
    cfg, params = dense_setup
    ecfg = EngineConfig(page_size=8, n_pages=64, max_batch=3,
                        max_pages_per_seq=8,
                        prefill_chunk=prefill_chunk)
    eng = ServeEngine(cfg, params=params, ecfg=ecfg)
    trace = synth_trace(TrafficConfig(
        n_requests=5, arrival_rate=1e4, prompt_len_min=3,
        prompt_len_max=20, gen_len_min=2, gen_len_max=10,
        vocab_size=cfg.vocab_size, seed=1))
    eng.submit_trace(trace)
    eng.drain()
    got = eng.results()
    for i, it in enumerate(trace):
        ref = _sequential_reference(cfg, params, it.prompt,
                                    it.max_new_tokens)
        assert got[i].tolist() == ref, \
            f"request {i} diverged at chunk={prefill_chunk}"
    eng.backend.cache.allocator.check_invariants()
    assert eng.backend.cache.allocator.n_used == 0, "pages leaked after drain"


def test_engine_batched_prefill_shares_a_step(dense_setup):
    """Simultaneous arrivals prefill as ONE batched chunk step, not one
    request per step."""
    cfg, params = dense_setup
    ecfg = EngineConfig(page_size=8, n_pages=64, max_batch=3,
                        max_pages_per_seq=8, prefill_chunk=32)
    eng = ServeEngine(cfg, params=params, ecfg=ecfg)
    rng = np.random.default_rng(5)
    for plen in (6, 11, 17):
        eng.submit(rng.integers(2, cfg.vocab_size, plen).astype(np.int32),
                   max_new_tokens=3)
    ev = eng.step()
    assert ev[0] == "prefill"
    assert sorted(rid for rid, _ in ev[1]) == [0, 1, 2]
    assert [n for _, n in sorted(ev[1])] == [6, 11, 17]
    eng.drain()
    for i, r in eng.results().items():
        assert len(r) == 3


def test_engine_preemption_under_cache_pressure(dense_setup):
    cfg, params = dense_setup
    # 9 usable pages of 4 tokens, simultaneous arrivals, chunked
    # prefill: forced eviction, including preemptions landing
    # MID-PREFILL (a half-prefilled request loses its pages, requeues,
    # and restarts its cursor from 0)
    ecfg = EngineConfig(page_size=4, n_pages=10, max_batch=3,
                        max_pages_per_seq=8, prefill_chunk=6,
                        observability="trace")
    eng = ServeEngine(cfg, params=params, ecfg=ecfg)
    trace = synth_trace(TrafficConfig(
        n_requests=6, arrival_rate=1e9, prompt_len_min=3,
        prompt_len_max=12, gen_len_min=6, gen_len_max=16,
        vocab_size=cfg.vocab_size, seed=3))
    eng.submit_trace(trace)
    eng.drain()
    m = eng.metrics()
    assert m["n_preemptions"] > 0, "pressure scenario never preempted"
    assert any(e[0] == "preempt" and e[2] == "prefill"
               for e in eng.events), "no preemption landed mid-prefill"
    assert m["n_done"] == 6
    eng.backend.cache.allocator.check_invariants()
    assert eng.backend.cache.allocator.n_used == 0
    # recompute-style preemption keeps greedy outputs token-identical
    got = eng.results()
    for i, it in enumerate(trace):
        ref = _sequential_reference(cfg, params, it.prompt,
                                    it.max_new_tokens)
        assert got[i].tolist() == ref, f"request {i} diverged"


def test_engine_drain_survives_all_lanes_preempted(dense_setup):
    """Regression: when every lane is preempted in one step (page pool
    dry at a page boundary), step() must report ("preempt_all", ...)
    progress rather than None — the freed pages make the re-queued
    request immediately prefillable, so drain() must NOT raise."""
    cfg, params = dense_setup
    ecfg = EngineConfig(page_size=4, n_pages=4, max_batch=1,
                        max_pages_per_seq=3, prefill_chunk=8)
    eng = ServeEngine(cfg, params=params, ecfg=ecfg)
    prompt = np.arange(2, 6, dtype=np.int32)
    rid = eng.submit(prompt, max_new_tokens=6)
    ev = eng.step()
    assert ev[0] == "prefill"          # whole prompt in one chunk
    # external pressure: hog every free page so the decode lane's
    # page-boundary growth can only preempt the lane itself
    hog = eng.backend.cache.allocator.alloc(eng.backend.cache.allocator.n_free, owner=-1)
    ev = eng.step()
    assert ev is not None and ev[0] == "preempt_all", ev
    assert eng.requests[rid].state is RequestState.QUEUED
    eng.backend.cache.allocator.free(hog)
    eng.drain()                         # must not raise "drain stalled"
    assert eng.metrics()["n_done"] == 1
    ref = _sequential_reference(cfg, params, prompt, 6)
    assert eng.results()[rid].tolist() == ref


@pytest.mark.parametrize("scheduler", ["cost", "fcfs"])
def test_engine_unfundable_chunk_falls_back_to_decode(dense_setup,
                                                      scheduler):
    """Regression: a planned prefill chunk whose missing pages are held
    by OLDER requests (which eviction never touches) must not stall
    drain — the engine runs a decode round in its place so the holders
    keep progressing and eventually free the pages."""
    cfg, params = dense_setup
    ecfg = EngineConfig(page_size=4, n_pages=8, max_batch=3,
                        max_pages_per_seq=5, prefill_chunk=4,
                        scheduler=scheduler)
    eng = ServeEngine(cfg, params=params, ecfg=ecfg)
    reqs = [(np.arange(2, 10, dtype=np.int32), 8),    # A: 8 prompt / 8 gen
            (np.arange(2, 6, dtype=np.int32), 4),     # B: 4 / 4
            (np.arange(2, 14, dtype=np.int32), 2)]    # C: 12 / 2
    for prompt, glen in reqs:
        eng.submit(prompt, max_new_tokens=glen)
    eng.drain()                         # must not raise "drain stalled"
    assert eng.metrics()["n_done"] == 3
    eng.backend.cache.allocator.check_invariants()
    assert eng.backend.cache.allocator.n_used == 0
    for i, (prompt, glen) in enumerate(reqs):
        ref = _sequential_reference(cfg, params, prompt, glen)
        assert eng.results()[i].tolist() == ref, f"request {i} diverged"


@pytest.mark.parametrize("scheduler", ["cost", "fcfs"])
def test_engine_deterministic_under_fixed_trace(dense_setup, scheduler):
    cfg, params = dense_setup
    ecfg = EngineConfig(page_size=8, n_pages=32, max_batch=2,
                        max_pages_per_seq=6, prefill_chunk=8,
                        scheduler=scheduler, observability="trace")
    trace = synth_trace(TrafficConfig(
        n_requests=4, arrival_rate=1e9, prompt_len_min=3,
        prompt_len_max=16, gen_len_min=2, gen_len_max=8,
        vocab_size=cfg.vocab_size, seed=7))
    runs = []
    for _ in range(2):
        eng = ServeEngine(cfg, params=params, ecfg=ecfg)
        eng.submit_trace(trace)
        eng.drain()
        runs.append((eng.events, eng.results()))
    assert runs[0][0] == runs[1][0], "scheduler event order diverged"
    for rid in runs[0][1]:
        np.testing.assert_array_equal(runs[0][1][rid], runs[1][1][rid])
    if scheduler == "cost":
        # the saturating trace must exercise mixed composition, and the
        # mixed event stream itself must be deterministic (asserted by
        # the event equality above)
        assert any(e[0] == "mixed" for e in runs[0][0]), \
            "cost policy never composed a mixed step"


def test_engine_chunked_cost_beats_unchunked_fcfs_ttft(dense_setup):
    """The head-of-line-blocking acceptance criterion: on a long-prompt
    trace, chunked prefill + mixed cost scheduling yields lower p99 and
    mean TTFT (virtual clock, deterministic) than the seed engine's
    behavior (whole-prompt prefill, prompt-first fcfs)."""
    cfg, params = dense_setup
    rng = np.random.default_rng(0)
    long_p = rng.integers(2, cfg.vocab_size, 256).astype(np.int32)
    shorts = [rng.integers(2, cfg.vocab_size,
                           int(rng.integers(4, 10))).astype(np.int32)
              for _ in range(4)]
    ttft = {}
    for label, sched, chunk in (("chunked_cost", "cost", 64),
                                ("unchunked_fcfs", "fcfs", 256)):
        eng = ServeEngine(cfg, params=params, ecfg=EngineConfig(
            page_size=8, n_pages=64, max_batch=4, max_pages_per_seq=36,
            prefill_chunk=chunk, scheduler=sched), seed=0)
        eng.submit(long_p, max_new_tokens=4, arrival_time=0.0)
        for i, s in enumerate(shorts):
            eng.submit(s, max_new_tokens=6, arrival_time=1e-7 * (i + 1))
        eng.drain()
        m = eng.metrics()
        assert m["n_done"] == 5
        ttft[label] = (m["p99_ttft_s"], m["mean_ttft_s"])
    assert ttft["chunked_cost"][0] < ttft["unchunked_fcfs"][0], ttft
    assert ttft["chunked_cost"][1] < ttft["unchunked_fcfs"][1], ttft


def test_engine_moe_family_smoke():
    cfg = dataclasses.replace(
        configs.get_config("qwen2_moe_a2_7b", smoke=True),
        compute_dtype="float32")
    ecfg = EngineConfig(page_size=8, n_pages=32, max_batch=2,
                        max_pages_per_seq=4)
    eng = ServeEngine(cfg, ecfg=ecfg)
    rng = np.random.default_rng(0)
    for plen, glen in ((5, 3), (9, 2)):
        eng.submit(rng.integers(2, cfg.vocab_size, plen).astype(np.int32),
                   max_new_tokens=glen)
    eng.drain()
    res = eng.results()
    assert len(res[0]) == 3 and len(res[1]) == 2
    assert eng.backend.cache.allocator.n_used == 0


def test_engine_submit_validation(dense_setup):
    cfg, params = dense_setup
    ecfg = EngineConfig(page_size=4, n_pages=8, max_batch=1,
                        max_pages_per_seq=4)
    eng = ServeEngine(cfg, params=params, ecfg=ecfg)
    with pytest.raises(ValueError, match="block table"):
        eng.submit(np.arange(2, 20, dtype=np.int32), max_new_tokens=8)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(np.arange(2, 6, dtype=np.int32), max_new_tokens=0)
    with pytest.raises(ValueError, match="prompt"):
        eng.submit(np.zeros(0, np.int32), max_new_tokens=2)


# ---------------------------------------------------------------------------
# prefix sharing / copy-on-write
# ---------------------------------------------------------------------------


def test_engine_prefix_sharing_cow_and_sharer_preemption(dense_setup):
    """The ISSUE acceptance pin: requests sharing a resident prompt
    prefix admit onto refcounted pages; a sharer whose prompt ends
    mid-page COW-forks the shared page on its first decode write;
    another sharer is preempted (releasing only its references) and
    re-prefilled — and every output stays token-identical to the
    sequential dense-cache decode."""
    cfg, params = dense_setup
    ecfg = EngineConfig(page_size=8, n_pages=64, max_batch=4,
                        max_pages_per_seq=8, prefill_chunk=32,
                        observability="trace")
    eng = ServeEngine(cfg, params=params, ecfg=ecfg)
    rng = np.random.default_rng(11)
    prefix = rng.integers(2, cfg.vocab_size, 16).astype(np.int32)  # 2 pages
    prompts = [
        np.concatenate([prefix,
                        rng.integers(2, cfg.vocab_size, 5).astype(np.int32)]),
        np.concatenate([prefix,
                        rng.integers(2, cfg.vocab_size, 3).astype(np.int32)]),
        prefix.copy(),        # page-aligned full hit -> 1-token rerun
        prefix[:13].copy(),   # mid-page full hit -> decode COW-forks
    ]
    gens = [8, 10, 6, 8]
    for i, (p, g) in enumerate(zip(prompts, gens)):
        eng.submit(p, max_new_tokens=g,
                   arrival_time=0.0 if i == 0 else 1e-7 * i)
    # step until every sharer is admitted against request 0's pages
    for _ in range(200):
        if sum(1 for e in eng.events if e[0] == "share") >= 3:
            break
        assert eng.step() is not None, "drained before sharers admitted"
    shares = [e for e in eng.events if e[0] == "share"]
    assert [(e[1], e[2]) for e in shares] == [(1, 16), (2, 16), (3, 13)]
    alloc = eng.backend.cache.allocator
    assert any(alloc.refcount(p) > 1
               for p in eng.requests[0].mem.pages), \
        "no page is physically shared"
    # preempt sharer 1 mid-flight: co-owned pages must stay resident
    victim = eng.requests[1]
    assert victim.state is not RequestState.DONE
    shared_pages = [p for p in victim.mem.pages if alloc.refcount(p) > 1]
    eng._preempt(victim)
    assert victim.state is RequestState.QUEUED and victim.mem is None
    for p in shared_pages:
        assert alloc.refcount(p) >= 1, "preempting a sharer freed a page"
    eng.drain()
    m = eng.metrics()
    assert m["n_done"] == 4
    assert m["n_cow_forks"] >= 1
    assert any(e[0] == "cow" and e[1] == 3 for e in eng.events), \
        "the mid-page sharer never COW-forked"
    assert any(e[0] == "preempt" and e[1] == 1 for e in eng.events)
    assert m["n_prefix_hits"] >= 4    # incl. the re-admitted sharer
    assert m["prefix_hit_rate"] > 0
    eng.backend.cache.allocator.check_invariants()
    assert eng.backend.cache.allocator.n_used == 0, "pages leaked after drain"
    assert all(r.t_first_token is not None
               for r in eng.requests.values())
    for i, (p, g) in enumerate(zip(prompts, gens)):
        ref = _sequential_reference(cfg, params, p, g)
        assert eng.results()[i].tolist() == ref, f"request {i} diverged"


def test_engine_prefix_sharing_saves_physical_pages(dense_setup):
    """Under a shared-prefix trace (4 groups x ~2.5-page prefixes) the
    sharing engine reports a positive hit rate and allocates strictly
    fewer physical pages than the same engine with sharing disabled,
    with bit-identical outputs."""
    cfg, params = dense_setup
    trace = synth_trace(TrafficConfig(
        n_requests=10, arrival_rate=2e6, prompt_len_min=2,
        prompt_len_max=8, gen_len_min=2, gen_len_max=6,
        vocab_size=cfg.vocab_size, seed=9,
        n_prefix_groups=4, prefix_len=20))
    results, mets = [], []
    for sharing in (True, False):
        eng = ServeEngine(cfg, params=params, ecfg=EngineConfig(
            page_size=8, n_pages=96, max_batch=4, max_pages_per_seq=8,
            prefill_chunk=32, prefix_sharing=sharing))
        eng.submit_trace(trace)
        eng.drain()
        eng.backend.cache.allocator.check_invariants()
        assert eng.backend.cache.allocator.n_used == 0
        results.append(eng.results())
        mets.append(eng.metrics())
    m_share, m_none = mets
    assert m_share["n_prefix_hits"] > 0 and m_share["prefix_hit_rate"] > 0
    assert m_none["prefix_hit_rate"] == 0
    assert (m_share["physical_pages_allocated"]
            < m_none["physical_pages_allocated"]), (m_share, m_none)
    assert (m_share["logical_cache_utilization"]
            >= m_share["cache_utilization"])
    for rid in results[0]:
        np.testing.assert_array_equal(results[0][rid], results[1][rid])
    for i, it in enumerate(trace):
        ref = _sequential_reference(cfg, params, it.prompt,
                                    it.max_new_tokens)
        assert results[0][i].tolist() == ref, f"request {i} diverged"


def test_engine_sole_owner_write_invalidates_index(dense_setup):
    """Regression: when the original writer finishes, a sharer can
    become the SOLE owner of a still-indexed page; its decode then
    writes into the page in place (no co-owner to protect), which
    diverges the content from what the index advertises. The write
    must drop the index entry, or a later admission with the original
    prompt would match stale K/V and decode garbage."""
    cfg, params = dense_setup
    ecfg = EngineConfig(page_size=8, n_pages=64, max_batch=3,
                        max_pages_per_seq=8, prefill_chunk=32,
                        observability="trace")
    eng = ServeEngine(cfg, params=params, ecfg=ecfg)
    rng = np.random.default_rng(21)
    base = rng.integers(2, cfg.vocab_size, 16).astype(np.int32)
    ra = eng.submit(base, max_new_tokens=2)                  # writer
    ev = eng.step()
    assert ev[0] == "prefill"                # base's 2 pages registered
    rd = eng.submit(base[:13], max_new_tokens=6,
                    arrival_time=eng.now)                    # sharer
    for _ in range(50):                      # sharer admitted + shared
        if any(e[0] == "share" and e[1] == rd for e in eng.events):
            break
        assert eng.step() is not None
    d = eng.requests[rd]
    for _ in range(50):                      # writer done, refs dropped
        if eng.requests[ra].state is RequestState.DONE:
            break
        assert eng.step() is not None
    for _ in range(50):                      # sharer's first DECODE
        if len(d.generated) >= 2:            # write (pos 13, page j=1)
            break
        assert eng.step() is not None
    # sole-owner write: no COW fork, but the diverged page must be out
    # of the index — only the untouched first page still matches
    assert eng.metrics()["n_cow_forks"] == 0
    assert eng.backend.prefix.match(base)[0] == 8
    re_ = eng.submit(base, max_new_tokens=4,
                     arrival_time=eng.now)   # original prompt again
    eng.drain()
    eng.backend.cache.allocator.check_invariants()
    assert eng.backend.cache.allocator.n_used == 0
    for rid, prompt, glen in ((ra, base, 2), (rd, base[:13], 6),
                              (re_, base, 4)):
        ref = _sequential_reference(cfg, params, prompt, glen)
        assert eng.results()[rid].tolist() == ref, f"request {rid}"


def test_scheduler_prices_only_unshared_pages(dense_setup):
    """Admission budgeting with a prefix probe: a fully-resident prompt
    admits at ZERO page cost (only its last token reruns for logits), a
    half-resident prompt is charged only its unshared tail."""
    from repro.serve import PagedBudget, Request, Scheduler, SchedulerConfig
    cfg, _ = dense_setup
    cm = ArtemisCostModel(cfg)
    shared = {1: 16, 2: 8, 3: 0}
    sched = Scheduler(SchedulerConfig(policy="fcfs"), cm,
                      prefill_chunk=32)

    def budget(free_pages):
        return PagedBudget(8, free_pages, probe=lambda r: shared[r.rid])

    full = Request(rid=1, prompt=np.zeros(16, np.int32), max_new_tokens=2)
    part = Request(rid=2, prompt=np.zeros(12, np.int32), max_new_tokens=2)
    cold = Request(rid=3, prompt=np.zeros(12, np.int32), max_new_tokens=2)
    common = dict(next_arrival=None, prefilling=[], decoding=[])
    # zero free pages: only the fully-resident prompt can admit
    a = sched.decide([full], free_lanes=2, budget=budget(0), **common)
    assert a.kind == "prefill" and a.prefill == ((1, 1),)
    a = sched.decide([part], free_lanes=2, budget=budget(0), **common)
    assert a.kind == "idle"
    # one free page funds exactly the half-resident prompt's tail; the
    # cold request behind it is starved (strict FCFS)
    a = sched.decide([full, part, cold], free_lanes=3, budget=budget(1),
                     **common)
    assert a.prefill == ((1, 1), (2, 4))
    # without sharing the probe reports 0 and the old budgeting holds
    a = sched.decide([cold], free_lanes=3, budget=budget(2), **common)
    assert a.prefill == ((3, 12),)


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------


def test_cost_model_price_per_token_is_u_shaped(dense_setup):
    cfg, _ = dense_setup
    cm = ArtemisCostModel(cfg)
    # token-based sharding amortizes the K/V ring broadcast: per-token
    # price falls with batch size over the decode-batch range ...
    prices = [cm.price_per_token(n) for n in (1, 4, 16, 64)]
    assert all(b <= a * 1.001 for a, b in zip(prices, prices[1:])), prices
    # ... then rises again once the O(N^2) attention terms dominate —
    # the crossover that lets the cost scheduler defer giant prefills
    assert cm.price_per_token(8192) > cm.price_per_token(8)
    assert cm.price(16) > 0


def _dummy_requests(n, plen=12, state=RequestState.DECODE):
    from repro.serve import Request
    reqs = []
    for i in range(n):
        r = Request(rid=100 + i, prompt=np.zeros(plen, np.int32),
                    max_new_tokens=4)
        r.state = state
        reqs.append(r)
    return reqs


def test_cost_policy_defers_unchunked_long_prefill_while_decoding(
        dense_setup):
    """With chunking DISABLED (chunk >= prompt) the original decision
    boundary survives: a multi-thousand-token prefill prices worse per
    token than a busy decode batch, so the cost policy runs decode
    first; fcfs stalls the lanes behind the prefill instead."""
    from repro.serve import PagedBudget, Request, Scheduler, SchedulerConfig
    cfg, _ = dense_setup
    cm = ArtemisCostModel(cfg)
    huge = Request(rid=0, prompt=np.zeros(8192, np.int32),
                   max_new_tokens=4)
    small = Request(rid=1, prompt=np.zeros(12, np.int32),
                    max_new_tokens=4)
    decoding = _dummy_requests(8)
    cost = Scheduler(SchedulerConfig(policy="cost"), cm,
                     prefill_chunk=8192)
    fcfs = Scheduler(SchedulerConfig(policy="fcfs"), cm,
                     prefill_chunk=8192)

    def common():
        return dict(next_arrival=None, prefilling=[], decoding=decoding,
                    free_lanes=2, budget=PagedBudget(8, 4096))

    assert cost.decide([huge], **common()).kind == "decode"
    assert fcfs.decide([huge], **common()).kind == "prefill"
    # short prompts ride the falling edge of the per-token price curve:
    # cost composes them WITH the decode batch; fcfs stays prompt-first
    a = cost.decide([small], **common())
    assert a.kind == "mixed" and a.prefill == ((1, 12),) and a.decode
    assert fcfs.decide([small], **common()).kind == "prefill"


def test_cost_policy_chunks_long_prefill_into_mixed_steps(dense_setup):
    """With chunking ON, the same long prompt no longer blocks: the
    scheduler plans one chunk and composes it with the decode batch."""
    from repro.serve import PagedBudget, Request, Scheduler, SchedulerConfig
    cfg, _ = dense_setup
    cm = ArtemisCostModel(cfg)
    huge = Request(rid=0, prompt=np.zeros(8192, np.int32),
                   max_new_tokens=4)
    sched = Scheduler(SchedulerConfig(policy="cost"), cm,
                      prefill_chunk=64)
    a = sched.decide([huge], next_arrival=None, prefilling=[],
                     decoding=_dummy_requests(8), free_lanes=2,
                     budget=PagedBudget(8, 4096))
    assert a.kind == "mixed" and a.prefill == ((0, 64),) and a.decode


def test_scheduler_plans_batched_and_continuing_chunks(dense_setup):
    """Chunk planning: mid-prefill requests continue first (oldest
    admission uncapped by the page budget), then FCFS admissions fill
    free lanes while the budget lasts."""
    from repro.serve import PagedBudget, Request, Scheduler, SchedulerConfig
    cfg, _ = dense_setup
    cm = ArtemisCostModel(cfg)
    sched = Scheduler(SchedulerConfig(policy="fcfs"), cm,
                      prefill_chunk=8)

    def budget(free_pages):
        return PagedBudget(4, free_pages)

    mid = Request(rid=0, prompt=np.zeros(20, np.int32), max_new_tokens=2)
    mid.state = RequestState.PREFILL
    mid.prefill_pos = 8
    q1 = Request(rid=1, prompt=np.zeros(6, np.int32), max_new_tokens=2)
    q2 = Request(rid=2, prompt=np.zeros(9, np.int32), max_new_tokens=2)
    a = sched.decide([q1, q2], next_arrival=None, prefilling=[mid],
                     decoding=[], free_lanes=2, budget=budget(100))
    assert a.kind == "prefill"
    assert a.prefill == ((0, 8), (1, 6), (2, 8))
    # tight page budget: 3 free pages — the continuing request is
    # planned anyway and charged 2 pages, the first admission is
    # clipped to the 1 remaining page (4 tokens), the second starved
    a = sched.decide([q1, q2], next_arrival=None, prefilling=[mid],
                     decoding=[], free_lanes=2, budget=budget(3))
    assert a.prefill == ((0, 8), (1, 4))
    # budget exhausted by the forced continuation -> no admissions
    a = sched.decide([q1, q2], next_arrival=None, prefilling=[mid],
                     decoding=[], free_lanes=2, budget=budget(1))
    assert a.prefill == ((0, 8),)
    # no lanes -> no admissions, continuation only
    a = sched.decide([q1, q2], next_arrival=None, prefilling=[mid],
                     decoding=[], free_lanes=0, budget=budget(100))
    assert a.prefill == ((0, 8),)


def test_percentile_nearest_rank():
    """Regression for the metrics off-by-one: int(p/100*n) indexed one
    element high at exact-multiple ranks (p50 of two latencies returned
    the LARGER one); nearest-rank is ceil(p/100*n)-1."""
    assert percentile([1.0, 2.0], 50) == 1.0
    assert percentile([1.0, 2.0], 100) == 2.0
    vals = [float(i) for i in range(1, 101)]
    assert percentile(vals, 99) == 99.0
    assert percentile(vals, 50) == 50.0
    assert percentile(vals, 1) == 1.0
    assert percentile([5.0], 99) == 5.0
    assert percentile([], 50) == 0.0
    assert percentile([3.0, 4.0, 5.0], 0) == 3.0   # clamps to first


def test_cost_model_rejects_empty_compositions(dense_setup):
    """Regression: _simulate used to clamp n_tokens=0 to a 1-token
    pass, silently pricing empty compositions a buggy scheduler should
    never have asked about."""
    cfg, _ = dense_setup
    cm = ArtemisCostModel(cfg)
    for n in (0, -3):
        for fn in (cm.price, cm.energy, cm.price_per_token,
                   cm.energy_per_token):
            with pytest.raises(ValueError, match="n_tokens"):
                fn(n)
    assert cm.price(1) > 0


def test_traffic_config_validation():
    """Bad traffic bounds used to fail deep inside np.random with
    confusing errors; they are rejected at construction now."""
    for bad in (dict(prompt_len_min=10, prompt_len_max=5),
                dict(prompt_len_min=0),
                dict(arrival_rate=0.0), dict(arrival_rate=-1.0),
                dict(n_requests=0),
                dict(gen_len_min=0), dict(gen_len_min=9, gen_len_max=2),
                dict(vocab_size=2),
                dict(n_prefix_groups=-1),
                dict(n_prefix_groups=2, prefix_len=0),
                dict(prefix_len=4)):
        with pytest.raises(ValueError):
            TrafficConfig(**bad)
    TrafficConfig()   # defaults stay valid


def test_shared_prefix_trace_structure():
    tc = TrafficConfig(n_requests=12, n_prefix_groups=3, prefix_len=9,
                       prompt_len_min=2, prompt_len_max=5, seed=4)
    items = synth_trace(tc)
    assert len(items) == 12
    groups = {}
    for it in items:
        assert 0 <= it.prefix_group < 3
        assert 9 + 2 <= len(it.prompt) <= 9 + 5
        groups.setdefault(it.prefix_group, []).append(it.prompt[:9])
    # every member of a group carries the identical prefix
    for prefs in groups.values():
        for p in prefs[1:]:
            np.testing.assert_array_equal(p, prefs[0])
    # independent mode keeps the old shape
    assert synth_trace(TrafficConfig(n_requests=3,
                                     seed=1))[0].prefix_group == -1


def test_engine_ttft_metrics_complete(dense_setup):
    """max_new_tokens < 1 is rejected at submit (pinned in
    test_engine_submit_validation), so every DONE request records a
    first-token time — including the gen=1 edge where the first token
    comes straight from the prefill chunk — and TTFT percentiles cover
    the full done set."""
    cfg, params = dense_setup
    eng = ServeEngine(cfg, params=params, ecfg=EngineConfig(
        page_size=8, n_pages=32, max_batch=2, max_pages_per_seq=4))
    rng = np.random.default_rng(2)
    for plen, glen in ((5, 1), (9, 3)):
        eng.submit(rng.integers(2, cfg.vocab_size, plen).astype(np.int32),
                   max_new_tokens=glen)
    eng.drain()
    assert all(r.t_first_token is not None
               for r in eng.requests.values())
    m = eng.metrics()
    assert m["n_done"] == 2
    assert m["mean_ttft_s"] > 0 and m["p99_ttft_s"] > 0
    # defensive: a None first-token time (only possible by driving the
    # engine around submit()) must not crash the percentile sort
    eng.requests[0].t_first_token = None
    m2 = eng.metrics()
    assert m2["p99_ttft_s"] > 0


def test_engine_config_validation():
    for bad in (dict(page_size=0), dict(n_pages=1), dict(max_batch=0),
                dict(max_pages_per_seq=0), dict(prefill_chunk=0),
                dict(scheduler="lifo")):
        with pytest.raises(ValueError):
            EngineConfig(**bad)
    with pytest.raises(TypeError):
        EngineConfig(cache_dtype="not-a-dtype")
    EngineConfig()   # defaults stay valid
