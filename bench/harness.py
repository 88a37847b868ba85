"""The load side of one run: it offers the mix's load to the engine's public
entry and records what it sees on the host clock.

It calls `ServeEngine.submit(prompt, max_new_tokens,
arrival_time=eng.now, sampling=...)` when a request is due, then
`ServeEngine.step()`, and after each step reads every live request's
`generated` and `state`.  Each step that emits a token ends in the
sampler's host sync, so a token's time is taken when `step()` returns.

Open loop: each request is due on a schedule and timed from when it
was due; how late the harness submitted it is recorded.  Closed loop:
each client sends its next request when its last one finishes.
"""
from __future__ import annotations

import contextlib
import time

from window import Step, Track


class Harness:
    def __init__(self, eng, gen, mix):
        from repro.serve.request import RequestState, SamplingParams
        self.eng = eng
        self.gen = gen
        self.mix = mix
        self.clock = time.perf_counter
        self.annotate = False       # host spans for the profiler
        self._queued = RequestState.QUEUED
        self._prefill = RequestState.PREFILL
        self._done = RequestState.DONE
        self._sampling = SamplingParams
        self.tracks: dict[int, Track] = {}
        self.prompts: dict[int, object] = {}
        self.live: list[int] = []
        self.steps: list[Step] = []
        self.clients: dict[int, int] = {}      # closed loop: rid -> client

    def span(self, name: str):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    # -- requests -----------------------------------------------------------

    def submit(self, item, due: float, in_window: bool,
               client: int = -1) -> int:
        mix = self.mix
        sp = self._sampling()
        if item.sampled:
            sp = self._sampling(temperature=mix.temperature, top_k=mix.top_k,
                                top_p=mix.top_p, seed=item.sample_seed)
        with self.span("submit"):
            rid = self.eng.submit(item.prompt, item.max_new_tokens,
                                  arrival_time=self.eng.now, sampling=sp)
        now = self.clock()
        self.tracks[rid] = Track(
            rid=rid, due=due, submitted=now, prompt_len=len(item.prompt),
            max_new_tokens=item.max_new_tokens, sampled=item.sampled,
            document=item.document, in_window=in_window)
        self.prompts[rid] = item.prompt
        self.live.append(rid)
        if client >= 0:
            self.clients[rid] = client
        return rid

    # -- stepping -----------------------------------------------------------

    def step(self) -> list:
        """One `engine.step()`; returns the rids that finished in it
        (None when the engine had nothing to do)."""
        t0 = self.clock()
        with self.span("engine.step"):
            ev = self.eng.step()
        t1 = self.clock()
        if ev is None:
            self.steps.append(Step(t0=t0, t1=t1, kind="idle"))
            return None
        finished, emitted = [], 0
        with self.span("poll"):
            reqs = self.eng.requests
            chunks = tuple(getattr(ev, "chunks", ()))
            dec = tuple(getattr(ev, "decode_rids", ()))
            starts = tuple(reqs[r].prefill_pos - n for r, n in chunks)
            ctx = tuple(reqs[r].seq_len for r in dec)
            for rid in self.live:
                req, tr = reqs[rid], self.tracks[rid]
                if tr.admitted is None and req.state is not self._queued:
                    tr.admitted = t0
                while len(tr.tokens) < len(req.generated):
                    tr.tokens.append(t1)
                    emitted += 1
                if req.state is self._done:
                    tr.done = t1
                    finished.append(rid)
            if finished:
                gone = set(finished)
                self.live = [r for r in self.live if r not in gone]
        self.steps.append(Step(
            t0=t0, t1=t1, kind=ev.kind, chunks=chunks, decode_rids=dec,
            emitted=emitted, chunk_starts=starts, decode_ctx=ctx,
            decode_kv_tokens=self._distinct_kv(dec, ctx)))
        return finished

    def _distinct_kv(self, rids, ctx) -> int:
        """Distinct resident tokens the decode lanes attend: a shared
        document counts once, however many lanes read it."""
        docs, total = set(), 0
        for rid, c in zip(rids, ctx):
            doc = self.tracks[rid].document if rid in self.tracks else -1
            if doc >= 0:
                docs.add(doc)
                c -= self.mix.prefix_len
            total += c
        return total + len(docs) * self.mix.prefix_len

    def wait_until(self, t: float) -> None:
        with self.span("wait_arrival"):
            delay = t - self.clock()
            if delay > 0:
                time.sleep(delay)

    # -- loops --------------------------------------------------------------

    def open_loop(self, offsets, t_begin: float, t_stop: float,
                  in_window: bool) -> None:
        """Submit each request at t_begin + offset and step the engine
        until t_stop."""
        i, n = 0, len(offsets)
        while True:
            now = self.clock()
            while i < n and t_begin + offsets[i] <= now:
                self.submit(self.gen.next_item(), t_begin + offsets[i],
                            in_window)
                i += 1
            if now >= t_stop:
                return
            if self.step() is None:
                nxt = t_begin + offsets[i] if i < n else t_stop
                self.wait_until(min(nxt, t_stop))

    def start_documents(self) -> None:
        """Closed loop over shared documents: one client per document
        sends first, and the engine steps until each of those has its
        whole prompt in the cache, so that the other clients find their
        document resident instead of all computing it at once."""
        n = min(len(self.gen.documents), self.mix.clients)
        rids = [self.submit(self.gen.next_item(c), self.clock(), False, c)
                for c in range(n)]
        reqs = self.eng.requests
        while any(reqs[r].state in (self._queued, self._prefill)
                  for r in rids):
            self.step()

    def closed_loop(self, t_stop: float, in_window: bool) -> None:
        """Keep every client's request in flight until t_stop: a client
        sends its next request when its last one finishes."""
        busy = set(self.clients[r] for r in self.live if r in self.clients)
        for c in range(self.mix.clients):
            if c not in busy:
                self.submit(self.gen.next_item(c), self.clock(), in_window,
                            c)
        while self.clock() < t_stop:
            finished = self.step()
            for rid in finished or ():
                c = self.clients.pop(rid, -1)
                if c >= 0 and self.clock() < t_stop:
                    self.submit(self.gen.next_item(c), self.clock(),
                                in_window, c)

    def drain(self, rids, t_stop: float) -> None:
        """Step until every request in `rids` has finished or t_stop."""
        want = set(rids)
        while want & set(self.live) and self.clock() < t_stop:
            if self.step() is None:
                self.wait_until(min(self.clock() + 1e-3, t_stop))
