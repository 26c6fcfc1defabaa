"""The open-loop generator: every seed gets the same work in another
order, exactly round(rate * seconds) requests, all due inside the
window."""
import numpy as np
import pytest

import bench_tiny  # noqa: F401  (puts the checkout on sys.path)
from bench import manifest
from bench.traffic import open_loop

CHAT = dict(manifest.load_mix("chat"), rate_rps=3.0)


@pytest.mark.parametrize("seconds,rate", [(51, 3.0), (10, 2.5), (7, 0.3)])
def test_exact_count_and_window(seconds, rate):
    arr = open_loop.generate(dict(CHAT, rate_rps=rate), seconds, 5, 1000)
    assert len(arr) == round(rate * seconds)
    due = [a.due for a in arr]
    assert due == sorted(due)
    assert 0 < due[0] and due[-1] < seconds


def test_same_multiset_of_lengths_for_every_seed():
    want = sorted(open_loop.work(CHAT, 51))
    for seed in (0, 1, 2**31 + 7, 3_000_000_001):
        arr = open_loop.generate(CHAT, 51, seed, 131072)
        assert sorted((len(a.prompt), a.max_new) for a in arr) == want
    gaps = [np.diff([0.0] + [a.due for a in open_loop.generate(
        CHAT, 51, s, 100)]) for s in (3, 4)]
    # the same gaps in another order (up to the half gap at the start)
    assert abs(sum(gaps[0]) - sum(gaps[1])) < 51


def test_seed_orders_the_work_and_draws_the_tokens():
    a = open_loop.generate(CHAT, 20, 1, 131072)
    b = open_loop.generate(CHAT, 20, 1, 131072)
    c = open_loop.generate(CHAT, 20, 2, 131072)
    assert [(x.due, x.prompt, x.max_new) for x in a] == \
        [(x.due, x.prompt, x.max_new) for x in b]
    assert [x.max_new for x in a] != [x.max_new for x in c]
    assert all(0 <= t < 131072 for x in a for t in x.prompt)


def test_lengths_follow_the_mix():
    p = open_loop.lengths(CHAT["prompt"], 1001)
    o = open_loop.lengths(CHAT["output"], 1001)
    assert p[500] == 1020 and o[500] == 129          # the medians
    assert p.min() >= 32 and p.max() == 3000 and o.max() == 1024
    assert list(p) == sorted(p)


def test_no_request_is_an_error():
    with pytest.raises(ValueError):
        open_loop.work(dict(CHAT, rate_rps=0.01), 10)


@pytest.mark.parametrize("n,k", [(80, 4), (653, 4), (10, 4), (7, 3)])
def test_stratified_blocks_hold_one_of_each_stratum(n, k):
    rng = np.random.default_rng(2**31 + 11)
    order = open_loop.stratified(rng, n, k)
    assert sorted(order.tolist()) == list(range(n))
    m = -(-n // k)
    s = -(-n // m)                     # strata; the top one may be short
    full = n - (s - 1) * m             # blocks that hold the top stratum
    strata = [i // m for i in order]
    cuts = [0]
    for b in range(m):
        cuts.append(cuts[-1] + (s if b < full else s - 1))
    assert cuts[-1] == n
    for lo, hi in zip(cuts, cuts[1:]):
        want = range(s) if hi - lo == s else range(s - 1)
        assert sorted(strata[lo:hi]) == list(want)


def test_stratified_mix_keeps_the_work_and_spreads_it():
    mix = dict(CHAT, rate_rps=1.56, strata=4)
    want = sorted(open_loop.work(mix, 51))
    for seed in (1, 3_000_000_001):
        arr = open_loop.generate(mix, 51, seed, 1000)
        assert sorted((len(a.prompt), a.max_new) for a in arr) == want
        due = [a.due for a in arr]
        assert due == sorted(due) and 0 < due[0] and due[-1] < 51
        # every four consecutive arrivals: one prompt from each quartile
        rank = {p: i for i, p in enumerate(sorted(len(a.prompt)
                                                  for a in arr))}
        q = [rank[len(a.prompt)] * 4 // len(arr) for a in arr]
        assert all(sorted(q[b:b + 4]) == [0, 1, 2, 3]
                   for b in range(0, len(arr), 4))
    a = open_loop.generate(mix, 51, 5, 1000)
    b = open_loop.generate(mix, 51, 6, 1000)
    assert [x.due for x in a] != [x.due for x in b]
