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
