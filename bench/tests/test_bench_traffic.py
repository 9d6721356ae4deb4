"""The traffic generator: deterministic for a seed, the same multiset of
sizes and gaps for every seed in a balanced order, and lengths that follow
the mix file."""
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(BENCH, "traffic"))

import generator  # noqa: E402

MIXES = ("chat",)
BIG_SEED = 2 ** 31 + 12345


def _key(specs):
    return [(s.prompt.tolist(), s.max_new_tokens, s.temperature, s.top_k,
             s.due_s) for s in specs]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = generator.load(name)
    a = generator.generate(mix, BIG_SEED, 30, 1000)
    b = generator.generate(mix, BIG_SEED, 30, 1000)
    assert _key(a) == _key(b)
    c = generator.generate(mix, BIG_SEED + 1, 30, 1000)
    assert _key(a) != _key(c)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_offers_the_same_work(name):
    mix = generator.load(name)
    a = generator.generate(mix, 1, 30, 1000)
    b = generator.generate(mix, 2, 30, 1000)
    for attr in ("max_new_tokens", "temperature"):
        assert sorted(getattr(s, attr) for s in a) == \
            sorted(getattr(s, attr) for s in b)
    assert sorted(len(s.prompt) for s in a) == \
        sorted(len(s.prompt) for s in b)


def test_open_loop_rate_and_due_times():
    mix = generator.load("chat")
    seconds = 40
    specs = generator.generate(mix, 7, seconds, 1000)
    assert len(specs) == int(np.ceil(mix["arrivals"]["rate_per_s"]
                                     * seconds))
    due = [s.due_s for s in specs]
    assert due == sorted(due) and due[0] == 0.0 and due[-1] < seconds
    gaps = np.diff(due)
    # exponential gaps: standard deviation close to the mean
    assert 0.8 < gaps.std() / gaps.mean() < 1.2


def test_lognormal_lengths_follow_the_mix():
    mix = generator.load("chat")
    p = generator.lengths(mix["prompt"], 2001)
    assert p.min() == mix["prompt"]["min"] and p.max() == mix["prompt"]["max"]
    assert np.median(p) == mix["prompt"]["median"]
    inner = p[(p > mix["prompt"]["min"]) & (p < mix["prompt"]["max"])]
    sigma = np.log(inner).std()
    assert 0.6 * mix["prompt"]["sigma"] < sigma <= mix["prompt"]["sigma"]
    o = generator.lengths(mix["output"], 2001)
    assert np.median(o) == mix["output"]["median"]


@pytest.mark.parametrize("spec", [
    {"dist": "lognormal", "median": 100, "sigma": 1.0, "min": 5,
     "max": 900},
    {"dist": "uniform", "min": 768, "max": 1536},
    {"dist": "poisson", "rate_per_s": 2.5}])
def test_each_distribution_file_gives_ascending_values(spec):
    """A distribution is a file of its own under ``dists``, read by name:
    its values at evenly spaced quantiles ascend and stay in range."""
    v = generator.values(spec, 999)
    assert len(v) == 999 and (np.diff(v) >= 0).all()
    if "min" in spec:
        assert v.min() >= spec["min"] and v.max() <= spec["max"]
    if spec["dist"] == "uniform":
        assert abs(v.mean() - (spec["min"] + spec["max"]) / 2) < 1
    if spec["dist"] == "poisson":
        assert abs(v.mean() * spec["rate_per_s"] - 1) < 0.01


def test_unknown_distribution_is_refused():
    with pytest.raises(ValueError, match="unknown distribution"):
        generator.values({"dist": "no-such-dist"}, 10)


def test_rate_override_keeps_the_sizes():
    """The sweep's rate changes the count and the gaps, not the sizes'
    distribution."""
    mix = generator.load("chat")
    a = generator.generate(mix, 9, 40, 1000, rate=1.0)
    b = generator.generate(mix, 9, 40, 1000, rate=3.0)
    assert len(a) == 40 and len(b) == 120
    assert a[-1].due_s < 40 and b[-1].due_s < 40


def test_sampled_share():
    mix = generator.load("chat")
    specs = generator.generate(mix, 5, 30, 1000)
    greedy = sum(s.greedy for s in specs)
    assert greedy == round(mix["greedy_share"] * len(specs))
    assert {(s.temperature, s.top_k) for s in specs if not s.greedy} == \
        {(mix["sampled"]["temperature"], mix["sampled"]["top_k"])}


@pytest.mark.parametrize("n, run", [(56, 8), (49, 7), (8, 8), (3, 3)])
def test_balanced_order_spreads_every_slice_over_the_runs(n, run):
    """Every run of consecutive places holds one value of each slice of
    consecutive ranks: here ``n`` ranks, runs of ``run`` places."""
    rng = np.random.default_rng(BIG_SEED)
    out = generator.balanced(rng, np.arange(n), generator.BLOCK)
    assert sorted(out) == list(range(n))
    blocks = -(-n // generator.BLOCK)
    for lo in range(0, n, run):
        assert sorted(out[lo:lo + run] // blocks) == list(range(run))
    again = generator.balanced(np.random.default_rng(BIG_SEED + 1),
                               np.arange(n), generator.BLOCK)
    assert n < 8 or list(again) != list(out)
