"""Open-loop arrivals: a seed fixes them, and every seed offers the
same work."""

from __future__ import annotations

import json

import numpy as np

from bench import arrivals
from bench.tests.tiny_root import BENCH

MIX = json.loads((BENCH / "traffic" / "chat_open_loop.json").read_text())


def _plan(seed, seconds=51.0):
    return arrivals.open_loop(MIX, 102400, seed, seconds)


def test_same_seed_same_arrivals():
    a, b = _plan(123), _plan(123)
    assert [x.due for x in a] == [x.due for x in b]
    assert [x.n_out for x in a] == [x.n_out for x in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_every_seed_offers_the_same_work():
    a, b = _plan(1), _plan((1 << 33) + 7)
    assert [x.due for x in a] != [x.due for x in b]
    for part in (True, False):
        for key in (lambda x: x.n_out, lambda x: x.prompt.size):
            assert sorted(key(x) for x in a if x.counted is part) == \
                sorted(key(x) for x in b if x.counted is part)


def test_window_holds_the_rate_times_the_seconds():
    plan = _plan(5, seconds=51.0)
    win = [x for x in plan if x.counted]
    assert len(win) == round(MIX["rate_per_s"] * 51.0)
    lead = MIX["lead_in_s"]
    assert all(lead <= x.due < lead + 51.0 for x in win)
    assert all(x.due < lead for x in plan if not x.counted)
    assert all(x.prompt.size + x.n_out <= MIX["max_len"] for x in plan)


def test_lengths_follow_the_clipped_lognormal():
    lens = arrivals.quantile_lengths(MIX["prompt_len"], 1001)
    assert lens.min() >= 16 and lens.max() <= 1024
    assert np.median(lens) == 256
