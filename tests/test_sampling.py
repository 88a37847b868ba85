"""Unit + statistics suite for the pure batched sampler.

`repro.serve.sampler.sample_tokens` is the one compiled sampler every
engine token goes through; this module pins its semantics in
isolation: greedy/argmax convergence, top-k and top-p truncation on
hand-built logits, parameter validation, RNG-lane batch invariance
(lane result is a pure function of (seed, position) — never the batch
around it), and a seeded chi-square check that sampled frequencies
match the softmax distribution. Engine-level conformance (batch
composition, preemption replay, mixed greedy/sampled traffic over
both sequence backends) lives in tests/test_serve_backend.py.
"""
import numpy as np
import pytest

from repro.launch import steps as stepslib
from repro.serve import SamplingParams, sample_tokens

VOCAB = 16


def _sample(logits, temperature=1.0, top_k=0, top_p=1.0, seed=0, pos=None):
    """Row-wise convenience wrapper: scalars broadcast over the batch."""
    logits = np.asarray(logits, np.float32)
    b = logits.shape[0]
    full = np.full
    if pos is None:
        pos = np.arange(b, dtype=np.int32)
    return np.asarray(sample_tokens(
        logits, full(b, temperature, np.float32),
        full(b, top_k, np.int32), full(b, top_p, np.float32),
        full(b, seed, np.uint32), np.asarray(pos, np.int32)))


def _rand_logits(n, vocab=VOCAB, seed=0):
    return np.random.default_rng(seed).normal(
        size=(n, vocab)).astype(np.float32)


# ---------------------------------------------------------------------------
# greedy / argmax convergence
# ---------------------------------------------------------------------------


def test_temperature_zero_is_exactly_greedy_sample():
    """The greedy fast path is bit-identical to the pre-sampling
    `greedy_sample` argmax (the anchor every token-identity suite in
    the repo leans on)."""
    logits = _rand_logits(8, seed=3)
    got = _sample(logits, temperature=0.0, top_k=7, top_p=0.5, seed=99)
    ref = np.asarray(stepslib.greedy_sample(logits))
    np.testing.assert_array_equal(got, ref)


def test_temperature_to_zero_converges_to_argmax():
    """As temperature -> 0 the sampled draw converges to argmax: with
    a >=1-logit gap, t=0.01 scales the gap to 100, far beyond any
    plausible Gumbel perturbation."""
    rng = np.random.default_rng(7)
    logits = rng.normal(size=(16, VOCAB)).astype(np.float32)
    logits[np.arange(16), rng.integers(0, VOCAB, 16)] += 5.0
    ref = np.argmax(logits, axis=-1)
    for t in (0.01, 0.003, 0.0):
        np.testing.assert_array_equal(
            _sample(logits, temperature=t, seed=5), ref)


def test_top_k_one_equals_argmax():
    logits = _rand_logits(16, seed=11)
    for t in (0.5, 1.0, 2.0):
        np.testing.assert_array_equal(
            _sample(logits, temperature=t, top_k=1, seed=21),
            np.argmax(logits, axis=-1))


def test_top_k_restricts_support():
    """With top_k=3 every draw lands in the 3 largest logits."""
    row = np.log(np.linspace(1.0, 9.0, VOCAB)).astype(np.float32)
    logits = np.tile(row, (512, 1))
    toks = _sample(logits, temperature=1.5, top_k=3, seed=2)
    top3 = set(np.argsort(row)[-3:].tolist())
    assert set(toks.tolist()) <= top3
    assert len(set(toks.tolist())) > 1, "top-k support collapsed"


# ---------------------------------------------------------------------------
# top-p (nucleus) mass cutoff on hand-built logits
# ---------------------------------------------------------------------------


def test_top_p_mass_cutoff_hand_built():
    """probs (0.5, 0.25, 0.15, 0.1): top_p=0.6 keeps the minimal
    descending set reaching 0.6 mass = {0, 1} and nothing else;
    top_p=0.8 adds token 2; top_p=0.45 keeps only the top token."""
    row = np.log(np.array([0.5, 0.25, 0.15, 0.1], np.float32))
    logits = np.tile(row, (512, 1))
    for top_p, allowed in ((0.45, {0}), (0.6, {0, 1}), (0.8, {0, 1, 2}),
                           (1.0, {0, 1, 2, 3})):
        toks = _sample(logits, temperature=1.0, top_p=top_p, seed=6)
        got = set(toks.tolist())
        assert got <= allowed, f"top_p={top_p} leaked {got - allowed}"
        if top_p >= 0.6:
            assert len(got) > 1, f"top_p={top_p} support collapsed"


def test_top_p_always_keeps_top_token():
    """Even a top_p below the top token's own probability keeps it
    (its exclusive cumulative mass is 0 < top_p), so sampling never
    lands on an empty support."""
    row = np.log(np.array([0.9, 0.06, 0.04], np.float32))
    toks = _sample(np.tile(row, (64, 1)), temperature=1.0, top_p=0.05,
                   seed=8)
    assert set(toks.tolist()) == {0}


def test_top_k_then_top_p_compose():
    """top_p is applied to the top-k-truncated distribution: with
    top_k=2 over (0.4, 0.3, 0.2, 0.1) the renormalized probs are
    (4/7 ~ 0.57, 3/7), so top_p=0.5 keeps only token 0 — while
    without the top-k (token 1's exclusive mass is 0.4 < 0.5) it
    keeps {0, 1}."""
    row = np.log(np.array([0.4, 0.3, 0.2, 0.1], np.float32))
    logits = np.tile(row, (256, 1))
    both = _sample(logits, temperature=1.0, top_k=2, top_p=0.5, seed=9)
    assert set(both.tolist()) == {0}
    p_only = _sample(logits, temperature=1.0, top_p=0.5, seed=9)
    assert set(p_only.tolist()) == {0, 1}


# ---------------------------------------------------------------------------
# SamplingParams validation
# ---------------------------------------------------------------------------


def test_invalid_params_raise():
    with pytest.raises(ValueError, match="temperature"):
        SamplingParams(temperature=-0.1)
    with pytest.raises(ValueError, match="top_k"):
        SamplingParams(top_k=-1)
    for bad_p in (0.0, -0.2, 1.5):
        with pytest.raises(ValueError, match="top_p"):
            SamplingParams(top_p=bad_p)
    for bad_seed in (-1, 2 ** 32):
        with pytest.raises(ValueError, match="seed"):
            SamplingParams(seed=bad_seed)
    # the full surface is one valid object
    sp = SamplingParams(temperature=0.7, top_k=40, top_p=0.9, seed=7)
    assert not sp.greedy
    assert SamplingParams().greedy
    # greedy is the temperature=0 fast path regardless of truncation
    assert SamplingParams(temperature=0.0, top_k=5, top_p=0.5).greedy


# ---------------------------------------------------------------------------
# RNG lanes: batch invariance + determinism
# ---------------------------------------------------------------------------


def test_lane_is_batch_invariant():
    """A lane's draw is a pure function of (its logits, its params,
    its seed, its position): sampling a row alone must give exactly
    the token it gets packed in a batch — the property the engine's
    whole sampled-determinism story reduces to."""
    logits = _rand_logits(6, seed=13)
    temp = np.array([0.0, 0.9, 1.3, 0.7, 1.0, 0.5], np.float32)
    top_k = np.array([0, 5, 0, 3, 0, 0], np.int32)
    top_p = np.array([1.0, 0.9, 0.7, 1.0, 0.8, 1.0], np.float32)
    seed = np.array([0, 7, 7, 11, 3, 3], np.uint32)
    pos = np.array([0, 4, 4, 2, 9, 9], np.int32)
    batch = np.asarray(sample_tokens(logits, temp, top_k, top_p, seed, pos))
    for i in range(6):
        alone = np.asarray(sample_tokens(
            logits[i:i + 1], temp[i:i + 1], top_k[i:i + 1],
            top_p[i:i + 1], seed[i:i + 1], pos[i:i + 1]))
        assert alone[0] == batch[i], f"lane {i} depends on its batch"


def test_same_seed_position_replays_same_token():
    """Replay: the draw for (seed, pos) is stable across calls — the
    property recompute-style preemption recovery relies on."""
    # rows 0/1 and 2/3 are identical lanes: same logits, seed and pos
    logits = np.repeat(_rand_logits(2, seed=17), 2, axis=0)
    a = _sample(logits, temperature=1.0, seed=42, pos=[3, 3, 5, 5])
    b = _sample(logits, temperature=1.0, seed=42, pos=[3, 3, 5, 5])
    np.testing.assert_array_equal(a, b)
    # identical (logits, params, seed, pos) lanes draw identically
    assert a[0] == a[1] and a[2] == a[3]


def test_distinct_seeds_and_positions_decorrelate():
    """Different seeds (and different positions under one seed) give
    different streams — near-uniform logits, 64 draws each."""
    logits = np.tile(_rand_logits(1, seed=19) * 0.1, (64, 1))
    s1 = _sample(logits, temperature=1.0, seed=1)
    s2 = _sample(logits, temperature=1.0, seed=2)
    assert s1.tolist() != s2.tolist()
    same_pos = _sample(logits, temperature=1.0, seed=1,
                       pos=np.zeros(64, np.int32))
    assert len(set(same_pos.tolist())) == 1, \
        "position did not enter the key"
    assert len(set(s1.tolist())) > 4, "positions did not decorrelate"


# ---------------------------------------------------------------------------
# distribution-level statistics
# ---------------------------------------------------------------------------


def test_chi_square_frequencies_match_softmax():
    """Seeded chi-square: ~2k draws from a fixed 8-token softmax. The
    draw stream is deterministic (seed + positions fixed), so this is
    a regression pin, not a flaky tolerance: chi2 stays under the
    p=0.0005 tail of chi2(df=7) ~ 26.0."""
    rng = np.random.default_rng(23)
    row = rng.normal(size=8).astype(np.float32)
    probs = np.exp(row) / np.exp(row).sum()
    n = 2048
    toks = _sample(np.tile(row, (n, 1)), temperature=1.0, seed=31)
    counts = np.bincount(toks, minlength=8)
    expected = probs * n
    chi2 = float(((counts - expected) ** 2 / expected).sum())
    assert chi2 < 26.0, f"chi2={chi2:.2f}, counts={counts.tolist()}"


def test_temperature_flattens_distribution():
    """Higher temperature spreads mass: the argmax token's frequency
    at t=2.5 is strictly below its frequency at t=0.6."""
    rng = np.random.default_rng(29)
    row = rng.normal(size=8).astype(np.float32) * 2.0
    n = 1024
    top = int(np.argmax(row))
    freq = {}
    for t in (0.6, 2.5):
        toks = _sample(np.tile(row, (n, 1)), temperature=t, seed=37)
        freq[t] = (toks == top).mean()
    assert freq[2.5] < freq[0.6]
