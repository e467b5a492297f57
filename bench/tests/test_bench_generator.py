"""The traffic generator: deterministic given the seed, and the same work
(lengths, gaps between arrivals) for every seed."""
import numpy as np
import pytest

from bench import generator

BASE = {"tokens": "template", "n_templates": 8, "slot_fraction": 0.25,
        "length": 64, "arrivals": "poisson", "rate_per_s": 200}
VOCAB = 1000


def _reqs(traffic, seed, seconds=2.0, stream=0):
    t = generator.corpus(traffic, VOCAB, seed)
    return generator.requests(traffic, seconds, seed, t, stream)


VARIANTS = {
    "poisson": BASE,
    "backlog": dict(BASE, arrivals="backlog", count=50),
}


@pytest.mark.parametrize("name", sorted(VARIANTS))
def test_same_seed_same_requests(name):
    a, b = _reqs(VARIANTS[name], 2**31 + 11), _reqs(VARIANTS[name],
                                                   2**31 + 11)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert x.arrival == y.arrival
        np.testing.assert_array_equal(x.tokens, y.tokens)


@pytest.mark.parametrize("name", sorted(VARIANTS))
def test_seeds_share_the_work(name):
    """Two seeds: the same count, lengths and gaps, in another order, and
    other tokens; arrivals sorted inside the window."""
    tr = VARIANTS[name]
    a, b = _reqs(tr, 3), _reqs(tr, 4)
    assert len(a) == len(b)
    assert sorted(r.tokens.size for r in a) == sorted(r.tokens.size
                                                     for r in b)
    ta = np.asarray([r.arrival for r in a])
    assert np.all(np.diff(ta) >= 0) and ta[0] >= 0 and ta[-1] < 2.0
    if tr["arrivals"] == "poisson":
        ga = np.sort(np.diff(np.r_[0, ta]))
        gb = np.sort(np.diff(np.r_[0, [r.arrival for r in b]]))
        np.testing.assert_allclose(ga, gb, rtol=1e-9, atol=1e-12)
    assert any(not np.array_equal(x.tokens, y.tokens) for x, y in zip(a, b))


def test_rate_sets_the_count():
    assert len(_reqs(BASE, 1, seconds=3.0)) == 600


def test_calibration_shares_the_skeletons():
    """The store's calibration passages and the window's requests come
    from the same templates, and differ in their slots."""
    t = generator.corpus(BASE, VOCAB, 9)
    calib = generator.calibration(t, 128, 9)
    reqs = _reqs(BASE, 9)
    same = [np.mean(r.tokens == calib, axis=1).max() for r in reqs]
    assert min(same) > 0.5
    rows = {c.tobytes() for c in calib}
    assert not any(r.tokens.tobytes() in rows for r in reqs)


def test_template_shares_skeleton():
    t = generator.corpus(BASE, VOCAB, 5)
    x = t.sample(2, np.random.default_rng(0))
    same = np.mean(x[0] == x[1])
    assert same > 0.5 or np.mean(x[0] != x[1]) == 1.0   # same or other template
