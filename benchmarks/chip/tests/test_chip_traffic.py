"""The traffic generator: fixed work per seed, order from the seed."""
import collections

import numpy as np

import chipbench_tiny  # noqa: F401
from benchmarks.chip import traffic as TR

CHAT = {"arrivals": "stratified_poisson", "rate_per_s": 2.5,
        "stratum_s": 10, "prompt_tokens": [513, 1024], "output_tokens": [128, 512]}
SPANS = (15.0, 51.0, 60.0)   # ramp, window, drain
BACKLOG = {"arrivals": "backlog", "backlog_requests": 100,
           "block_requests": 64, "prompt_tokens": [1025, 2048],
           "output_tokens": [8, 32]}
BIG = 2 ** 31 + 977          # seeds may exceed 32 signed bits


def test_same_seed_same_inputs():
    a = TR.schedule(CHAT, BIG, SPANS)
    b = TR.schedule(CHAT, BIG, SPANS)
    assert a == b
    assert np.array_equal(TR.prompt_tokens(BIG, 5, 700, 152064),
                          TR.prompt_tokens(BIG, 5, 700, 152064))
    assert not np.array_equal(TR.prompt_tokens(BIG, 5, 700, 152064),
                              TR.prompt_tokens(BIG + 1, 5, 700, 152064))


def test_seeds_differ_in_order_not_in_work():
    def window(seed):
        return [x for x in TR.schedule(CHAT, seed, SPANS)
                if 15.0 <= x.due < 66.0]

    a, b = window(1), window(BIG)
    # each span holds round(rate * length) requests, whatever the seed,
    # dealt into 5 strata of 10.2 s with 25 or 26 each
    assert len(a) == len(b) == round(2.5 * 51.0)
    for x in (a, b):
        per = collections.Counter(int((r.due - 15.0) // 10.2) for r in x)
        assert sorted(per.values()) == [25, 25, 26, 26, 26]
    assert [x.plen for x in a] != [x.plen for x in b]
    assert [x.due for x in a] != [x.due for x in b]
    for key in ("plen", "out"):
        assert collections.Counter(getattr(x, key) for x in a) == \
            collections.Counter(getattr(x, key) for x in b)
    whole = TR.schedule(CHAT, 3, SPANS)
    assert len(whole) == round(2.5 * 15) + round(2.5 * 51) + round(2.5 * 60)
    assert [x.due for x in whole] == sorted(x.due for x in whole)
    assert whole[-1].due < sum(SPANS)
    assert [x.rid for x in whole] == list(range(1, len(whole) + 1))


def test_lengths_stay_in_range_and_backlog_is_due_at_once():
    s = TR.schedule(BACKLOG, BIG, SPANS)
    assert len(s) == 100 and all(x.due == 0.0 for x in s)
    assert all(1025 <= x.plen <= 2048 and 8 <= x.out <= 32 for x in s)
    assert [x.rid for x in s] == list(range(1, 101))


def test_padding_matches_the_bucket():
    t = TR.prompt_tokens(7, 1, 600, 100)
    p = TR.padded(t, 1024)
    assert p.shape == (1024,) and np.array_equal(p[:600], t)
    assert not p[600:].any()
