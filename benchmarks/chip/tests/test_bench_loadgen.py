"""The traffic generator: seeded, the same work for every seed."""
import json
import os

import numpy as np

import loadgen

TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "traffic", "chat_dense.json")


def chat():
    with open(TRAFFIC) as f:
        return json.load(f)


def test_same_seed_same_requests():
    a = loadgen.schedule(chat(), 2**33 + 7, 10.0, vocab_size=151936)
    b = loadgen.schedule(chat(), 2**33 + 7, 10.0, vocab_size=151936)
    assert [p.arrival_s for p in a] == [p.arrival_s for p in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert [p.max_new for p in a] == [p.max_new for p in b]


def test_every_seed_offers_the_same_work_in_its_own_order():
    t = chat()
    a = loadgen.schedule(t, 1, 10.0, vocab_size=151936)
    b = loadgen.schedule(t, 2, 10.0, vocab_size=151936)
    n = round(t["arrivals"]["rate_per_s"] * 10.0)
    assert len(a) == len(b) == n
    assert sorted(len(p.prompt) for p in a) == \
        sorted(len(p.prompt) for p in b)
    assert sorted(p.max_new for p in a) == sorted(p.max_new for p in b)
    gaps = lambda ps: sorted(np.round(np.diff(  # noqa: E731
        [0.0] + [p.arrival_s for p in ps]), 9))
    assert np.allclose(gaps(a), gaps(b))
    assert [len(p.prompt) for p in a] != [len(p.prompt) for p in b]


def test_arrivals_fill_the_window_and_lengths_keep_their_clip():
    t = chat()
    ps = loadgen.schedule(t, 5, 10.0, vocab_size=100)
    arr = [p.arrival_s for p in ps]
    assert arr == sorted(arr) and 0 < arr[0] and arr[-1] < 10.0
    assert arr[-1] > 9.5
    lo, hi = t["prompt_len"]["min"], t["prompt_len"]["max"]
    assert all(lo <= len(p.prompt) <= hi for p in ps)
    lo, hi = t["output_len"]["min"], t["output_len"]["max"]
    assert all(lo <= p.max_new <= hi for p in ps)
    assert all(0 <= p.prompt.min() and p.prompt.max() < 100 for p in ps)


def test_lognormal_quantiles_centre_on_the_median():
    spec = {"dist": "lognormal", "median": 512, "sigma": 0.8, "min": 1,
            "max": 10**9}
    q = loadgen.length_quantiles(spec, 1001)
    assert q[500] == 512
    assert q[0] < 512 < q[-1]


def test_poisson_gaps_keep_the_rate():
    g = loadgen.gap_quantiles({"process": "poisson", "rate_per_s": 4.0},
                              20000)
    assert abs(g.mean() - 0.25) < 0.005
    assert abs(g.std() / g.mean() - 1.0) < 0.05


def test_seed_words_differ_by_tag_and_by_high_bits():
    assert loadgen.seed_words(7, "a") != loadgen.seed_words(7, "b")
    assert loadgen.seed_words(2**40 + 5, "a") != loadgen.seed_words(5, "a")
