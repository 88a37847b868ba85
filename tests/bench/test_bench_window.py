"""Window arithmetic on synthetic timestamps: a stall moves the tail and
the mean of the inter-token gaps and the token rate."""
import numpy as np
import pytest

import bench_fixture  # noqa: F401
from window import Record, Step, Track, end_to_end, percentile


@pytest.mark.parametrize("q", [0, 10, 50, 90, 95, 100])
def test_percentile_matches_numpy(q):
    xs = list(np.random.default_rng(0).exponential(size=101))
    assert percentile(xs, q) == pytest.approx(np.percentile(xs, q))


STALLS = (1.0, 2.0, 3.0)


def record(stall: float = 0.0) -> Record:
    """8 requests, due every 0.25 s, each 40 tokens 50 ms apart after a
    100 ms first token; `stall` seconds are added to every gap that
    spans one of STALLS (device hiccups all live requests feel: three
    of each request's 39 gaps, more than the 5% above the p95)."""
    tracks = []
    for i in range(8):
        due = 0.25 * i
        times, t = [], due + 0.1
        for _ in range(40):
            times.append(t)
            t += 0.05 + stall * sum(t < s <= t + 0.05 for s in STALLS)
        tracks.append(Track(rid=i, due=due, submitted=due, prompt_len=10,
                            max_new_tokens=40, sampled=False,
                            in_window=True, tokens=times, done=times[-1]))
    return Record(t_start=0.0, t_end=4.0, t_grace_end=6.0, tracks=tracks,
                  steps=[Step(t0=0.0, t1=0.1, kind="decode")])


def test_clean_window():
    m = end_to_end(record())
    assert m["ttft_p90_ms"] == pytest.approx(100.0)
    assert m["itl_mean_ms"] == pytest.approx(50.0)
    assert m["itl_p95_ms"] == pytest.approx(50.0)
    n = sum(1 for t in record().tracks for x in t.tokens if x < 4.0)
    assert m["tok_per_s"] == pytest.approx(n / 4.0)


def test_stall_moves_the_tail_and_the_rate():
    clean, stalled = end_to_end(record()), end_to_end(record(stall=0.5))
    assert stalled["itl_p95_ms"] > clean["itl_p95_ms"] + 400
    gaps = [b - a for t in record(stall=0.5).tracks
            for a, b in zip(t.tokens, t.tokens[1:]) if b < 4.0]
    assert stalled["itl_mean_ms"] == pytest.approx(
        sum(gaps) / len(gaps) * 1e3)
    assert stalled["itl_mean_ms"] > clean["itl_mean_ms"] + 30
    assert stalled["tok_per_s"] < clean["tok_per_s"]


def test_request_without_a_token_counts_until_grace_end():
    rec = record()
    rec.tracks.append(Track(rid=99, due=3.0, submitted=3.0, prompt_len=10,
                            max_new_tokens=5, sampled=False, in_window=True))
    rec.tracks += [Track(rid=100 + i, due=3.0, submitted=3.0, prompt_len=1,
                         max_new_tokens=1, sampled=False, in_window=True)
                   for i in range(2)]
    assert end_to_end(rec)["ttft_p90_ms"] == pytest.approx(3000.0)


def test_finished_tracks_include_the_ramps_and_not_the_earlier():
    rec = record()
    rec.tracks += [
        Track(rid=200, due=-3.0, submitted=-3.0, prompt_len=10,
              max_new_tokens=5, sampled=False, done=1.0),
        Track(rid=201, due=-3.0, submitted=-3.0, prompt_len=10,
              max_new_tokens=5, sampled=False, done=-0.5),
        Track(rid=202, due=3.5, submitted=3.5, prompt_len=10,
              max_new_tokens=5, sampled=False, in_window=True)]
    rids = {t.rid for t in rec.finished_tracks()}
    assert rids == set(range(8)) | {200}
