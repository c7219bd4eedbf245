"""The traffic generator is seeded, deterministic, and keeps the mixes'
medians and clips; every seed gets the same work in another order."""

import numpy as np
import pytest
from benchlib import CHIP  # noqa: F401  (puts the harness on the path)

import traffic

CHAT = traffic.load("chat-lognormal")


def _draw(seed, rate=8.0, seconds=50.0):
    return traffic.open_loop(CHAT, rate, seconds, 49152, seed)


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5, 2**40 + 3])
def test_open_loop_is_deterministic(seed):
    a, b = _draw(seed), _draw(seed)
    assert [x.due_s for x in a] == [x.due_s for x in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert [x.max_new for x in a] == [x.max_new for x in b]


def test_seeds_share_the_work_not_the_order():
    a, b = _draw(1), _draw(2)
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt) for x in b)
    assert sorted(x.max_new for x in a) == sorted(x.max_new for x in b)
    assert sum(x.temperature == 0 for x in a) == sum(x.temperature == 0 for x in b)
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in b]
    assert not np.array_equal(a[0].prompt[:8], b[0].prompt[:8])


@pytest.mark.parametrize("part", ["prompt", "output"])
def test_lengths_keep_median_and_clips(part):
    spec = CHAT[part]
    x = traffic.lognormal_lengths(spec, 2001)
    assert x.min() >= spec["min"] and x.max() <= spec["max"]
    assert abs(np.median(x) - spec["median"]) <= 1
    assert x.min() == spec["min"] and x.max() == spec["max"]  # the tails clip


def test_arrivals_fill_the_window_at_the_rate():
    arr = _draw(3, rate=8.0, seconds=50.0)
    due = np.array([x.due_s for x in arr])
    assert len(arr) == 400 and due[0] == 0.0
    assert np.all(np.diff(due) > 0) and due[-1] < 50.0
    gaps = np.diff(due)
    assert 0.8 < gaps.std() / gaps.mean() < 1.2  # exponential: cv near 1
    assert sum(x.temperature == 0.0 for x in arr) == round(0.25 * 400)


def test_packed_batches_differ_by_step_and_repeat_by_seed():
    mix = {"kind": "packed", "batch": 2, "seq": 16}
    f, g = traffic.packed_batch_fn(mix, 100, 5), traffic.packed_batch_fn(mix, 100, 5)
    a, b = np.asarray(f(3)), np.asarray(g(3))
    assert a.shape == (2, 16) and a.dtype == np.int32
    assert np.array_equal(a, b)
    assert not np.array_equal(a, np.asarray(f(4)))
    assert a.min() >= 0 and a.max() < 100
