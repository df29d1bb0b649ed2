"""Traffic generators: the same seed gives the same requests, every seed
offers the same sizes, and the stated distributions hold."""
import numpy as np
import pytest

from bench import registry
from bench.traffic import generators as gen

BUCKETS = (196, 729)


def _plan(name, seed, seconds=30.0):
    return gen.generate(registry.load_traffic(name), BUCKETS, 151936, seed,
                        seconds)


@pytest.mark.parametrize("name", ["chat-mixedres", "decode-backlog",
                                  "camera-ondemand", "backlog-mixedres"])
def test_same_seed_same_requests(name):
    a, b = _plan(name, 2**33 + 5), _plan(name, 2**33 + 5)
    assert len(a.requests) == len(b.requests)
    for x, y in zip(a.requests, b.requests):
        assert (x.n_patches, x.max_new, x.feat_seed) == \
            (y.n_patches, y.max_new, y.feat_seed)
        assert np.array_equal(x.text, y.text)
    np.testing.assert_array_equal(
        gen.vision_features(a.requests[3], 16),
        gen.vision_features(b.requests[3], 16))


@pytest.mark.parametrize("name", ["chat-mixedres", "decode-backlog"])
def test_seeds_offer_the_same_sizes_in_another_order(name):
    a, b = _plan(name, 1), _plan(name, 2)
    for attr in (lambda r: r.n_patches, lambda r: len(r.text),
                 lambda r: r.max_new):
        assert sorted(map(attr, a.requests)) == sorted(map(attr, b.requests))
    assert [r.max_new for r in a.requests] != [r.max_new for r in b.requests]


def test_chat_distributions():
    t = registry.load_traffic("chat-mixedres")
    p = _plan("chat-mixedres", 7, seconds=100.0)
    n = len(p.requests)
    assert n == int(np.ceil(t["rate_rps"] * 100.0))
    full = sum(r.n_patches == 729 for r in p.requests)
    assert full == round(0.4 * n)
    text = np.array([len(r.text) for r in p.requests])
    out = np.array([r.max_new for r in p.requests])
    assert text.min() >= 8 and text.max() <= 256
    assert out.min() >= 16 and out.max() <= 256
    assert abs(np.median(text) - 32) <= 1 and abs(np.median(out) - 64) <= 1
    # Poisson arrivals: mean gap 1/rate, starting at 0, in order
    gaps = np.diff(p.due_s)
    assert p.due_s[0] == 0 and (gaps >= 0).all()
    assert abs(p.due_s[-1] / (n - 1) - 1 / t["rate_rps"]) < 0.1 / t["rate_rps"]


def test_uniform_and_closed_loop():
    p = _plan("camera-ondemand", 3)
    out = [r.max_new for r in p.requests]
    assert min(out) == 16 and max(out) == 48
    assert all(r.n_patches == 729 for r in p.requests)
    assert abs(np.mean(p.think_s) - 0.5) < 0.05


def test_draw_buckets_exact_shares():
    rng = np.random.default_rng(0)
    b = gen.draw_buckets([{"bucket": 0, "share": 0.6},
                          {"bucket": 1, "share": 0.4}], 11, rng)
    assert sorted(b.tolist()) == [0] * 7 + [1] * 4
    with pytest.raises(ValueError):
        gen.draw_buckets([{"bucket": 0, "share": 0.5}], 4, rng)


def test_unknown_kind_refused():
    with pytest.raises(ValueError):
        gen.generate({"kind": "bursty"}, BUCKETS, 100, 0, 1.0)
