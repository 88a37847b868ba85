"""The benchmark's traffic generator: deterministic per seed, the same
work for every seed, lengths within their bounds and distributions."""
import numpy as np
import pytest

import bench_fixture  # noqa: F401  (puts bench/ on the path)
from traffic import Generator, Mix, exponential_gaps, quantile_lengths

CHAT = Mix(name="chat", loop="open", rate_per_s=2.0,
           prompt={"dist": "lognormal", "median": 512, "sigma": 1.0,
                   "min": 32, "max": 2048},
           output={"dist": "lognormal", "median": 128, "sigma": 0.8,
                   "min": 16, "max": 512},
           sampled_share=0.5)
DOCS = Mix(name="docs", loop="closed", clients=4, pool=64,
           prefix={"documents": 3, "length": 64},
           prompt={"dist": "uniform", "min": 32, "max": 256},
           output={"dist": "uniform", "min": 64, "max": 256})


def draw(mix, seed, n):
    gen = Generator(mix, vocab=1000, seed=seed)
    gen.set_pool(n)
    return gen, [gen.next_item() for _ in range(n)]


@pytest.mark.parametrize("mix", [CHAT, DOCS], ids=["open", "closed"])
def test_same_seed_same_stream(mix):
    _, a = draw(mix, 2 ** 33 + 5, 40)
    _, b = draw(mix, 2 ** 33 + 5, 40)
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_new_tokens, x.sampled, x.sample_seed, x.document) == \
            (y.max_new_tokens, y.sampled, y.sample_seed, y.document)


@pytest.mark.parametrize("mix", [CHAT, DOCS], ids=["open", "closed"])
def test_seeds_share_the_work_in_another_order(mix):
    _, a = draw(mix, 1, 64)
    _, b = draw(mix, 2 ** 32 + 1, 64)
    la = [(len(x.prompt), x.max_new_tokens) for x in a]
    lb = [(len(x.prompt), x.max_new_tokens) for x in b]
    assert la != lb
    assert sorted(x[0] for x in la) == sorted(x[0] for x in lb)
    assert sorted(x[1] for x in la) == sorted(x[1] for x in lb)
    assert sum(x.sampled for x in a) == sum(x.sampled for x in b)


def test_lengths_clipped_and_distributed():
    p = quantile_lengths(CHAT.prompt, 1000)
    o = quantile_lengths(CHAT.output, 1000)
    assert p.min() >= 32 and p.max() <= 2048
    assert o.min() >= 16 and o.max() <= 512
    assert abs(np.median(p) - 512) <= 2 and abs(np.median(o) - 128) <= 1
    # lognormal sigma 1: the quartiles sit at median * e^(+-0.674)
    assert abs(np.percentile(p, 75) / 512 - np.exp(0.674)) < 0.02
    u = quantile_lengths(DOCS.output, 193)
    assert sorted(set(u)) == list(range(64, 257))


def test_prefix_documents_and_token_range():
    gen, items = draw(DOCS, 7, 64)
    for it in items:
        assert 0 <= it.document < 3
        assert np.array_equal(it.prompt[:64], gen.documents[it.document])
        assert 32 <= len(it.prompt) - 64 <= 256
        assert it.prompt.min() >= 2 and it.prompt.max() < 1000
    assert len({it.document for it in items}) == 3


def test_open_loop_schedule_spans_the_window():
    gen = Generator(CHAT, vocab=1000, seed=3)
    due = gen.window_schedule(40.0)
    assert len(due) == 80 and due[0] == 0.0
    assert np.all(np.diff(due) > 0)
    assert 35.0 < due[-1] < 45.0
    gaps = exponential_gaps(2.0, 10_000)
    assert abs(gaps.mean() - 0.5) < 0.01
    ramp = gen.ramp_schedule(10.0)
    assert np.all(ramp < 10.0) and len(ramp) >= 15


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 9])
def test_closed_loop_client_keeps_its_document(seed):
    gen = Generator(DOCS, vocab=1000, seed=seed)
    for client in [0, 1, 2, 3, 4, 7, 3, 0]:
        it = gen.next_item(client)
        assert it.document == client % 3
        assert np.array_equal(it.prompt[:64], gen.documents[client % 3])
