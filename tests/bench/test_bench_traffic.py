"""The benchmark's data generators: a pure function of the seed."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench.traffic import generators  # noqa: E402

GENERATORS = {
    "digits": lambda k, **kw: generators.digits(k, per_set=32, **kw),
    "gunshots": generators.gunshots,
}


def _make(name, words):
    out = GENERATORS[name](np.asarray(words, np.uint32), n_stored=64,
                           n_queries=16)
    return [np.asarray(a) for a in out]


@pytest.mark.parametrize("name", sorted(GENERATORS))
def test_same_seed_same_data(name):
    a, b = _make(name, [1, 2]), _make(name, [1, 2])
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


@pytest.mark.parametrize("name", sorted(GENERATORS))
def test_other_seed_other_data(name):
    a, b = _make(name, [1, 2]), _make(name, [1, 3])
    assert not np.array_equal(a[0], b[0])      # stored set
    assert not np.array_equal(a[2], b[2])      # query pool


@pytest.mark.parametrize("name", sorted(GENERATORS))
def test_shapes_and_words(name):
    stored, s_lab, queries, q_lab = _make(name, [5, 6])
    assert stored.shape == (64, 256) and stored.dtype == np.uint8
    assert queries.shape == (16, 256) and queries.dtype == np.uint8
    assert s_lab.shape == (64,) and q_lab.shape == (16,)
    assert stored.std() > 5 and queries.std() > 5


def test_digit_classes_fill_contiguous_blocks_in_each_set():
    stored, s_lab, _, q_lab = _make("digits", [7, 8])
    np.testing.assert_array_equal(s_lab,
                                  np.tile(np.repeat(np.arange(4), 8), 2))
    assert set(q_lab) <= set(range(4))
    # each set is its own draw, not a copy of the first
    assert not np.array_equal(stored[:32], stored[32:])


def test_gunshot_windows_alternate_signal_and_noise():
    _, ids, _, labels = _make("gunshots", [7, 8])
    np.testing.assert_array_equal(ids, np.arange(64))
    assert np.all(labels[1::2] == -1)
    assert np.all((labels[0::2] >= 0) & (labels[0::2] < 64))


def test_a_signal_window_correlates_best_with_its_template():
    stored, _, queries, labels = _make("gunshots", [9, 10])
    d = stored.astype(np.float64) - 128
    q = queries.astype(np.float64) - 128
    best = (q @ d.T).argmax(-1)
    hits = np.mean(best[0::2] == labels[0::2])
    assert hits >= 0.75
