"""Traffic generator: one general generator that reads a mix's data file.

Copied from the program's `serve/traffic.py` (`synth_trace`: Poisson
arrivals, length ranges, prefix groups, a sampled share) and extended
with lognormal lengths, a closed loop and a fixed pool of sizes.

Every seed gets the same work: the pool's prompt lengths, output
lengths, arrival gaps and sampled flags are fixed quantiles of the
mix's distributions, and the seed only permutes them and draws the
token ids.  So two seeds differ in the order of the work and not in
its amount.

A mix file (`bench/traffic/<name>.json`) holds:

    loop          "open" (arrivals on a schedule) or "closed" (clients
                  that each wait for their last answer)
    rate_per_s    open loop: mean arrival rate
    clients       closed loop: number of clients
    pool          closed loop: requests per permutation of the pool
                  (open loop: the pool is the window's arrivals)
    prompt        {"dist": "lognormal", "median", "sigma", "min", "max"}
                  or {"dist": "uniform", "min", "max"}; with a prefix,
                  the length of the part after it
    output        the same, for the tokens to generate
    prefix        null, or {"documents": n, "length": tokens}: each
                  request starts with one of n shared documents.  A
                  closed loop's client c always asks about document
                  c mod n, so every document has the same number of
                  readers whatever the seed; an open loop's request
                  draws its document uniformly
    sampled_share share of requests that sample (the rest are greedy)
    temperature, top_p, top_k   sampling parameters of those
    ramp_s        seconds of the mix run before the window, in set-up
    grace_s       seconds after the window for its requests to finish
    check_tokens  served tokens the correctness sample should reach
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist

import numpy as np

FIRST_ID = 2        # token ids start at 2, as in the program's traffic


@dataclasses.dataclass(frozen=True)
class Mix:
    name: str
    loop: str
    prompt: dict
    output: dict
    rate_per_s: float = 0.0
    clients: int = 0
    pool: int = 256
    prefix: dict | None = None
    sampled_share: float = 0.0
    temperature: float = 0.8
    top_p: float = 1.0
    top_k: int = 0
    ramp_s: float = 10.0
    grace_s: float = 15.0
    check_tokens: int = 256

    def __post_init__(self):
        if self.loop not in ("open", "closed"):
            raise ValueError(f"{self.name}: loop must be open or closed")
        if self.loop == "open" and not self.rate_per_s > 0:
            raise ValueError(f"{self.name}: an open loop needs rate_per_s")
        if self.loop == "closed" and self.clients < 1:
            raise ValueError(f"{self.name}: a closed loop needs clients")
        for d in (self.prompt, self.output):
            if d["dist"] not in ("lognormal", "uniform"):
                raise ValueError(f"{self.name}: unknown dist {d['dist']!r}")
            if not 1 <= d["min"] <= d["max"]:
                raise ValueError(f"{self.name}: bad length bounds {d}")
        if not 0.0 <= self.sampled_share <= 1.0:
            raise ValueError(f"{self.name}: sampled_share outside [0, 1]")

    @property
    def prefix_len(self) -> int:
        return int(self.prefix["length"]) if self.prefix else 0

    @property
    def max_prompt(self) -> int:
        return self.prefix_len + int(self.prompt["max"])

    @property
    def max_output(self) -> int:
        return int(self.output["max"])


def load_mix(path: Path) -> Mix:
    data = json.loads(Path(path).read_text())
    return Mix(name=Path(path).stem, **data)


def quantile_lengths(dist: dict, n: int) -> np.ndarray:
    """n lengths at the quantiles (i + 0.5) / n of `dist`, clipped to
    its bounds: the same multiset for every seed."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "uniform":
        x = lo + np.floor(u * (hi - lo + 1))
    else:
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        x = np.rint(np.exp(math.log(dist["median"]) + dist["sigma"] * z))
    return np.clip(x, lo, hi).astype(np.int64)


def exponential_gaps(rate: float, n: int) -> np.ndarray:
    """n inter-arrival gaps at the quantiles of an exponential of mean
    1/rate (a Poisson process's gaps, as a fixed multiset)."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


@dataclasses.dataclass(frozen=True)
class Item:
    """One request as the generator hands it to the harness."""
    prompt: np.ndarray          # (S,) int32 token ids
    max_new_tokens: int
    sampled: bool
    sample_seed: int            # RNG-lane seed of a sampled request
    document: int = -1          # shared document, -1 = none


class Generator:
    """Deterministic request stream of one mix under one seed.

    `next_item()` hands out requests in pool order, one fresh seeded
    permutation of the pool per cycle.  For an open loop,
    `window_schedule(seconds)` gives the window's due offsets: one
    permutation of `round(rate * seconds)` fixed gaps."""

    def __init__(self, mix: Mix, vocab: int, seed: int):
        self.mix = mix
        self.vocab = vocab
        self.rng = np.random.default_rng(seed)
        self.documents = [
            self.rng.integers(FIRST_ID, vocab, mix.prefix_len,
                              dtype=np.int64).astype(np.int32)
            for _ in range(int(mix.prefix["documents"]) if mix.prefix else 0)]
        self._order: list[int] = []
        self._pool_n = 0

    def set_pool(self, n: int) -> None:
        """Fix the pool size (the open loop sizes it to its window)."""
        n = max(1, int(n))
        self._pool_n = n
        self._prompt = quantile_lengths(self.mix.prompt, n)
        self._output = quantile_lengths(self.mix.output, n)
        n_sampled = int(round(self.mix.sampled_share * n))
        self._sampled = np.arange(n) < n_sampled
        self._order = []

    def _cycle(self) -> None:
        n = self._pool_n
        perm = [self.rng.permutation(n) for _ in range(3)]
        self._order = list(zip(perm[0], perm[1], perm[2]))

    def next_item(self, client: int = -1) -> Item:
        """The next request; `client` is the closed loop's client that
        sends it (-1 for an open loop)."""
        if not self._pool_n:
            self.set_pool(self.mix.pool)
        if not self._order:
            self._cycle()
        ip, io, is_ = self._order.pop(0)
        body = self.rng.integers(FIRST_ID, self.vocab, int(self._prompt[ip]),
                                 dtype=np.int64).astype(np.int32)
        doc = -1
        if self.documents:
            doc = (client % len(self.documents) if client >= 0 else
                   int(self.rng.integers(0, len(self.documents))))
            body = np.concatenate([self.documents[doc], body])
        return Item(prompt=body, max_new_tokens=int(self._output[io]),
                    sampled=bool(self._sampled[is_]),
                    sample_seed=int(self.rng.integers(0, 2 ** 31)),
                    document=doc)

    def window_schedule(self, seconds: float) -> np.ndarray:
        """Due offsets (s) of the window's arrivals, from 0: the pool of
        round(rate * seconds) fixed gaps in a seeded order."""
        n = max(1, int(round(self.mix.rate_per_s * seconds)))
        gaps = self.rng.permutation(exponential_gaps(self.mix.rate_per_s, n))
        return np.cumsum(gaps) - gaps[0]

    def ramp_schedule(self, seconds: float) -> np.ndarray:
        """Due offsets of the arrivals in the ramp before the window."""
        n = max(1, int(round(self.mix.rate_per_s * seconds)))
        gaps = self.rng.permutation(exponential_gaps(self.mix.rate_per_s, n))
        due = np.cumsum(gaps) - gaps[0]
        return due[due < seconds]
