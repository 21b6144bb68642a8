"""The serving traffic generator and its statistics."""
from __future__ import annotations

import numpy as np
import pytest

import harness

serve = harness.part("drivers", "serve")
TRAFFIC = harness.load_json("bench/traffic/serve-poisson.json")


def test_every_seed_serves_the_same_work_in_another_order():
    a = serve.schedule(TRAFFIC, 600.0, 11, 1000)
    b = serve.schedule(TRAFFIC, 600.0, 2 ** 31 + 5, 1000)
    assert [x[0] for x in a] != [x[0] for x in b]
    # the same multiset of sizes for every seed, drawn from shape_seed
    assert sorted((len(p), o) for _t, p, o in a) == \
        sorted((len(p), o) for _t, p, o in b)
    lo, hi = TRAFFIC["prompt_len"]["min"], TRAFFIC["prompt_len"]["max"]
    assert all(lo <= len(p) <= hi for _t, p, _o in a)
    olo, ohi = TRAFFIC["output_len"]["min"], TRAFFIC["output_len"]["max"]
    assert all(olo <= o <= ohi for _t, _p, o in a)
    # arrivals: Poisson at the file's rate, due times increasing
    due = np.array([t for t, _p, _o in a])
    assert np.all(np.diff(due) >= 0) and due[0] == 0.0 and due[-1] < 600.0
    assert len(a) == round(600.0 * TRAFFIC["rate_per_s"])
    gaps = np.diff(due)
    assert np.std(gaps) / np.mean(gaps) == pytest.approx(1.0, abs=0.15)
    # the same seed gives the same inputs
    assert serve.schedule(TRAFFIC, 60.0, 11, 1000) == \
        serve.schedule(TRAFFIC, 60.0, 11, 1000)


def test_percentile_is_exact_over_raw_samples():
    assert serve.percentile([3, 1, 2, 4], 50) == 2.5
    assert serve.percentile(range(1, 101), 90) == pytest.approx(90.1)
    assert serve.percentile([], 90) is None
    assert serve.percentile([1.0, float("inf")], 90) == float("inf")


def test_book_counts_only_tokens_handed_back_in_the_window():
    book = serve.Book(10.0)
    book.tokens(1, 1, 2.0)
    book.tokens(1, 9, 9.0)
    book.tokens(1, 17, 11.0)
    book.tokens(1, 17, 12.0)
    assert book.in_window == 9
    assert (book.first[1], book.last[1], book.ntok[1]) == (2.0, 11.0, 17)
